"""Benchmark harness — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,table2] [--json out.json]

Prints ``name,us_per_call,derived`` CSV rows (common.emit). Set
REPRO_BENCH_FAST=1 for the abbreviated suite used in CI. ``--json PATH``
additionally writes a perf snapshot (every emitted metric plus per-module
wall time) so future PRs have a trajectory to compare against — see
BENCH_planner_hotpath.json at the repo root for the recorded baselines.
"""

from __future__ import annotations

import argparse
import json
import platform
import sys
import time
import traceback

from . import (  # noqa: F401
    calibration_bench,
    chaos_bench,
    common,
    fig3_grid,
    fig6_transfer_comparison,
    fig7_overlay_ablation,
    fig8_bottlenecks,
    fig9_microbench,
    fig10_overlay_vs_vms,
    fleet_bench,
    flowsim_bench,
    multicast_bench,
    multijob_bench,
    obs_bench,
    probe_policy_bench,
    roofline,
    solver_bench,
    table2_academic,
)

MODULES = {
    "fig3": fig3_grid,
    "fig6": fig6_transfer_comparison,
    "fig7": fig7_overlay_ablation,
    "fig8": fig8_bottlenecks,
    "fig9": fig9_microbench,
    "fig10": fig10_overlay_vs_vms,
    "table2": table2_academic,
    "solver": solver_bench,
    "flowsim": flowsim_bench,
    "multijob": multijob_bench,
    "multicast": multicast_bench,
    "calibration": calibration_bench,
    "chaos": chaos_bench,
    "fleet": fleet_bench,
    "probe_policies": probe_policy_bench,
    "obs": obs_bench,
    "roofline": roofline,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module names (default: all)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a BENCH_<name>.json perf snapshot of this run")
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache  # src/ is on the path

    use_compile_cache()
    names = list(MODULES) if not args.only else args.only.split(",")
    print("name,us_per_call,derived")
    failures = 0
    module_s = {}
    for name in names:
        mod = MODULES[name]
        t0 = time.time()
        try:
            mod.run()
            module_s[name] = round(time.time() - t0, 1)
            print(f"# {name} done in {module_s[name]}s", file=sys.stderr)
        except Exception:  # noqa: BLE001
            failures += 1
            module_s[name] = None
            print(f"# {name} FAILED:\n{traceback.format_exc()}",
                  file=sys.stderr)
    if args.json:
        snapshot = {
            "schema": 1,
            "generated": time.strftime("%Y-%m-%d %H:%M:%S"),
            "fast_mode": common.FAST,
            "python": platform.python_version(),
            "machine": platform.machine(),
            "modules_run": names,
            "module_wall_s": module_s,
            "metrics": common.RESULTS,
        }
        with open(args.json, "w") as fh:
            json.dump(snapshot, fh, indent=1)
        print(f"# snapshot -> {args.json}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
