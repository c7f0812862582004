"""Run one benchmark cell once and print one JSON result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration (``bench/configs/<config>.json``), its traffic
mix (``bench/traffic/<traffic>.json``), the generator of the mix's kind
(``bench/traffic/kinds/<kind>.py``) and its metrics
(``bench/metrics/<metric>.py``) are found by the names in
``BENCHMARK.json``. A run loads the program from ``src/`` of the checkout,
sets up and warms up the cell's requests, then drives one client in a
closed loop for ``--seconds``: it issues requests while the elapsed time
is under the limit, lets the request in flight finish, and rates the work
completed over the time from the window's start to the last completion.
After the window the program's state is freed and the answers (all of
them, or where the mix caps it a seeded sample with the longest) are
compared with the plain references under ``bench/reference/``.

With ``--trace 1`` the window is cut to its first requests (see
``TRACE_SECONDS``), runs under the JAX profiler, and the line carries the
per-layer metrics read from that trace and the program's counters; with
``--trace 0`` it carries the end-to-end ones.
The run fails, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
LIMITS = BENCH / "reference" / "limits.json"
# A traced run's window: requests while under this many seconds, at least
# one. The profiler records every device operation, and writing out each
# costs ~25 us: one 11.6 s plan records 4.7M of them and takes 116 s to
# stop on one TPU v5e, so whole 51 s windows cannot be traced.
TRACE_SECONDS = 2.0


class BenchError(Exception):
    """A run that cannot produce a result."""


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_reader(name: str):
    """The ``read(ctx)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports: its end-to-end ones, or with a trace
    the per-layer ones that list it or, listing none, move an end-to-end
    metric it reports."""
    def here(m):
        return "workloads" not in m or cell in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if here(m)]
    if not trace:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


class CompileCounter:
    """Compile events and seconds from ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.n += 1
            self.s += secs


def check_devices(devs, chips: int) -> None:
    """A TPU with at least ``chips`` chips, or BenchError."""
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: the first JAX device is {devs[0].platform}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")


def init_jax(chips: int):
    """Point the persistent compile cache into the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says) and check the devices."""
    import jax

    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    check_devices(devs, chips)
    return devs


def counters() -> dict:
    from repro.obs.metrics import REGISTRY

    return {name: float(v) for name, v in REGISTRY.snapshot().items()
            if isinstance(v, (int, float))}


def window(mix, seconds: float):
    """Closed loop: issue requests while under ``seconds``, finish the one
    in flight. Returns (per-request records, start, end)."""
    import jax

    records = []
    start = time.perf_counter()
    k = 0
    while time.perf_counter() - start < seconds:
        with jax.profiler.TraceAnnotation("bench.request"):
            try:
                out, err = mix.request(k), None
            except Exception as e:  # a failed request counts as failed
                out, err = None, f"{type(e).__name__}: {e}"
        records.append({"k": k, "t1": time.perf_counter(), "out": out,
                        "err": err})
        k += 1
    return records, start, records[-1]["t1"]


def sample(records, n, seed: int):
    """Indices of the answers to compare: all of them, or where the mix
    caps it at ``n``, the longest answer and a seeded draw of the rest."""
    if n is None or len(records) <= n:
        return list(range(len(records)))
    import numpy as np

    done = [i for i, r in enumerate(records) if r["out"] is not None]
    if not done:
        return list(range(len(records)))
    longest = max(done, key=lambda i: records[i]["out"].work)
    rest = [i for i in range(len(records)) if i != longest]
    pick = np.random.default_rng([seed, 7]).choice(rest, n - 1,
                                                   replace=False)
    return sorted([longest, *map(int, pick)])


def judge(mix, records, limits: dict, picked):
    """Compare the picked answers with the reference; a request that
    raised fails whether picked or not. Returns (failed, worst number per
    name)."""
    failed = 0
    worst: dict[str, float] = {}
    for i, r in enumerate(records):
        if r["out"] is not None and i not in picked:
            continue
        if r["out"] is None:
            failed += 1
            print(f"[check] request {r['k']} raised {r['err']}",
                  file=sys.stderr)
            continue
        got = mix.check(r["out"])
        for name, v in got.items():
            worst[name] = max(worst.get(name, 0.0), v)
        if any(not v <= limits[name] for name, v in got.items()):
            failed += 1
    return failed, worst


def traced(fn, chips: int):
    """``fn()`` under the JAX profiler: its result and the reduction of
    its trace."""
    import jax

    import trace_reduce

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                out = fn()
        finally:
            jax.profiler.stop_trace()
        return out, trace_reduce.reduce_dir(trace_dir, chips)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cell = next((w for w in bench["workloads"]
                     if w["name"] == args.workload), None)
        if cell is None:
            raise BenchError(f"no workload {args.workload!r} in BENCHMARK.json")
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError(f"the program (src/repro) is not in {ROOT}")
        config = load_json(BENCH / "configs" / f"{cell['config']}.json")
        sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
        import workload

        traffic = workload.resolve(
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"), config)
        try:
            kind = workload.load_kind(traffic["kind"])
        except FileNotFoundError as e:
            raise BenchError(str(e)) from None
        limits = load_json(LIMITS)
        metrics = cell_metrics(bench, cell["name"], bool(args.trace))
        readers = {m["name"]: load_reader(m["name"]) for m in metrics}
        devs = init_jax(cell["chips"])
        peaks = load_json(BENCH / "peaks.json").get(devs[0].device_kind)
        if args.trace and peaks is None:
            raise BenchError(f"no peaks for {devs[0].device_kind!r} in "
                             "bench/peaks.json")
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    import jax

    compiles = CompileCounter()
    mix = kind(config, traffic, args.seed)
    mix.warmup()
    setup_s = time.perf_counter() - T_PROCESS
    print(f"[setup] {setup_s:.3f} s, {compiles.n} compile events "
          f"({compiles.s:.3f} s)", file=sys.stderr)

    c0, n0 = counters(), compiles.n
    if args.trace:
        (records, start, end), red = traced(
            lambda: window(mix, TRACE_SECONDS), cell["chips"])
    else:
        records, start, end = window(mix, args.seconds)
    c1 = counters()
    in_window = compiles.n - n0
    print(f"[window] {len(records)} requests in {end - start:.3f} s, "
          f"{in_window} compile events inside", file=sys.stderr)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[: cell["chips"]])
    # the program's device state goes before the references run
    jax.clear_caches()
    gc.collect()
    picked = sample(records, traffic.get("check_sample"), args.seed)
    print(f"[check] {len(picked)} of {len(records)} answers compared with "
          "the reference", file=sys.stderr)
    failed, worst = judge(mix, records, limits, set(picked))

    ctx = {
        "setup_s": setup_s,
        "elapsed_s": end - start,
        "work": {mix.work_unit: sum(r["out"].work for r in records
                                       if r["out"] is not None)},
        "counters": {k: c1.get(k, 0.0) - c0.get(k, 0.0) for k in c1},
        "sizes": mix.sizes(),
        "peaks": peaks,
        "trace": red if args.trace else None,
    }
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}
    breakdown = None
    if args.trace:
        if red.dropped_s:
            print(f"[trace] the profiler dropped {red.dropped_s:.3f} s of "
                  "device events; left out of the window", file=sys.stderr)
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10),
                     "idle_gaps": red.top_gaps(10)}
    out = {}
    for m in metrics:
        v = readers[m["name"]](ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}

    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in sorted(worst.items())}
    correct = bool(records) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": out, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
