"""Readings that the limits of ``reference/limits.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 1 2 ... \
        [--requests R] [--dump DIR]
    python bench/calibrate.py --workload <cell> --readings DIR [--diagnostics]

The first form runs, in one process (set-up and warm-up paid once) and
for each seed, the cell's request generator built from that seed through
``R`` requests of the program's timed path. With ``--dump`` it writes
each seed's answers (and the reference's inputs that set-up made) to
``DIR/<cell>.<seed>.pkl.gz`` and compares nothing,
so that the chip's time goes to the program alone; without it, it
compares as ``--readings`` does.

``--readings`` compares dumped answers on any host: each answer twice,
as the program gave it (the lower readings) and with the precision
control in the program's place (the upper readings). Prints one JSON line
per seed and, last, the largest program reading and the smallest control
reading of each number. ``--diagnostics`` adds, per seed, the largest of
the readings that the kind gives for calibration only (``diagnostics``).
"""

from __future__ import annotations

import argparse
import gzip
import json
import pickle
import sys
from pathlib import Path

import numpy as np

import run


def readings(mix, outcomes, acc_lo: dict, acc_hi: dict) -> tuple:
    lo: dict[str, float] = {}
    hi: dict[str, float] = {}
    for out in outcomes:
        for got, acc in ((mix.check(out), lo), (mix.control(out), hi)):
            for name, v in got.items():
                acc[name] = max(acc.get(name, 0.0), v)
    for name, v in lo.items():
        acc_lo[name] = max(acc_lo.get(name, 0.0), v)
    for name, v in hi.items():
        acc_hi[name] = min(acc_hi.get(name, np.inf), v)
    return lo, hi


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--requests", type=int, default=2)
    ap.add_argument("--dump", type=Path)
    ap.add_argument("--readings", type=Path)
    ap.add_argument("--diagnostics", action="store_true")
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    config = run.load_json(run.BENCH / "configs" / f"{cell['config']}.json")
    sys.path[:0] = [str(run.ROOT / "src")]
    import workload

    traffic = workload.resolve(run.load_json(
        run.BENCH / "traffic" / f"{cell['traffic']}.json"), config)
    program: dict[str, float] = {}
    control: dict[str, float] = {}
    if args.readings:
        files = sorted(args.readings.glob(f"{args.workload}.*.pkl.gz"))
        for path in files:
            seed = int(path.name.split(".")[-3])
            with gzip.open(path, "rb") as f:
                outcomes, ref_jobs = pickle.load(f)
            mix = workload.build(config, traffic, seed)
            if ref_jobs is not None:
                mix.ref_jobs = ref_jobs
            lo, hi = readings(mix, outcomes, program, control)
            line = {"seed": seed, "program": lo, "control": hi}
            if args.diagnostics:
                line["diagnostics"] = {}
                for out in outcomes:
                    for name, v in mix.diagnostics(out).items():
                        line["diagnostics"][name] = max(
                            line["diagnostics"].get(name, -np.inf), v)
            print(json.dumps(line), flush=True)
    else:
        try:
            run.init_jax(cell["chips"])
        except run.BenchError as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        if args.dump:
            args.dump.mkdir(parents=True, exist_ok=True)
        for i, seed in enumerate(args.seeds):
            mix = workload.build(config, traffic, seed)
            if i == 0:
                mix.warmup()
            outcomes = [mix.request(k) for k in range(args.requests)]
            if args.dump:
                path = args.dump / f"{args.workload}.{seed}.pkl.gz"
                # with the reference's inputs that set-up made on this host
                with gzip.open(path, "wb") as f:
                    pickle.dump((outcomes, getattr(mix, "ref_jobs", None)), f)
                print(json.dumps({"seed": seed, "dumped": str(path)}),
                      flush=True)
                continue
            lo, hi = readings(mix, outcomes, program, control)
            print(json.dumps({"seed": seed, "program": lo, "control": hi}),
                  flush=True)
    print(json.dumps({"workload": args.workload, "program_max": program,
                      "control_min": control}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
