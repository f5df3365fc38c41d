"""Benchmark driver: one benchmark per paper figure/table, plus the
kernel micro-bench, the fed-round perf trajectory, and the
roofline-table assembler.

``PYTHONPATH=src python -m benchmarks.run [--scale 2e-3] [--quick]
[--json] [--only fedround]``

``--json`` writes the machine-readable ``BENCH_fedround.json`` perf
trajectory at the repo root (the fedround bench always runs when the
flag is set); ``--only NAME`` restricts the run to one bench.
"""
from __future__ import annotations

import argparse
import sys
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=None,
                    help="dataset scale factor (default env BENCH_SCALE "
                         "or 2e-3)")
    ap.add_argument("--quick", action="store_true",
                    help="small client grid")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_fedround.json at the repo root")
    ap.add_argument("--only", default=None,
                    choices=["fig2", "fig3", "fig4", "table3", "scenario",
                             "fedround", "ledger", "privacy", "faults",
                             "contribution", "obs", "kernel",
                             "roofline"],
                    help="run a single benchmark")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (fedround_bench, fig2_clients_iid, fig3_energy,
                   fig4_noniid, kernel_bench, ledger_bench,
                   privacy_bench, roofline_table, scenario_bench,
                   table3_accuracy)
    from . import common
    if args.quick:
        common.CLIENTS_GRID = [1, 10, 100]

    def want(name):
        return args.only is None or args.only == name

    t0 = time.time()
    if want("fig2"):
        print("== Fig 2: accuracy/time vs clients (IID) ==")
        fig2_clients_iid.run(args.scale)
    if want("fig3"):
        print("== Fig 3: energy vs clients (IID) ==")
        fig3_energy.run(args.scale)
    if want("fig4"):
        print("== Fig 4/5: non-IID scenario ==")
        fig4_noniid.run(args.scale)
    if want("table3"):
        print("== Table 3: accuracy comparison vs baselines ==")
        table3_accuracy.run(args.scale)
    if want("scenario"):
        print("== Scenario sweep: partition x dropout x late-join x wire ==")
        scenario_bench.run(args.scale)
    if want("fedround") and (args.json or args.only == "fedround"):
        print("== Fed-round trajectory: loop vs fleet dispatch ==")
        fedround_bench.run(args.scale, quick=args.quick)
    if want("ledger") and (args.json or args.only == "ledger"):
        print("== Ledger delta rounds vs full re-aggregation ==")
        ledger_bench.run(quick=args.quick)
    if want("privacy") and (args.json or args.only == "privacy"):
        print("== Privacy overhead + accuracy-vs-eps ==")
        privacy_bench.run(quick=args.quick)
    if args.only == "faults":
        # the fedround bench already embeds the faults section; the
        # standalone entry re-measures and merges it into the JSON
        print("== Fault tolerance: availability vs retry joules ==")
        fedround_bench.run_faults(quick=args.quick)
    if args.only == "contribution":
        # same merge idiom: re-measure just the selection section
        print("== Client selection: accuracy per joule (exact LOO) ==")
        fedround_bench.run_contribution(quick=args.quick)
    if args.only == "obs":
        # same merge idiom: re-measure just the flight-recorder section
        print("== Flight recorder: tracing overhead + joule split ==")
        fedround_bench.run_obs(quick=args.quick)
    if want("kernel"):
        print("== Kernel micro-bench ==")
        kernel_bench.run()
        kernel_bench.run_multi()
    if want("roofline"):
        print("== Roofline table (from dry-run artifacts) ==")
        roofline_table.run()
    print(f"[bench] all done in {time.time() - t0:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
