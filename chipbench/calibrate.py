"""Readings that a cell's limits are set from, in one process on the chip.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds 3 [--out FILE]

For every seed it builds the cell's driver as a run does, drives it
through a short window at the cell's own load, and reads the numbers
that decide ``correct`` (the lower readings: sound runs of the program).
On the control seeds it also reads, at the cell's own size:

* the control (``control.py``: the reference in the program's place,
  its products in bf16) over the same rows against the same float64
  reference (the upper readings), and beside it the same algebra in
  three bf16 passes (``three_pass``) and at the stated precision
  (``stated_precision``);
* each fault that the cell's driver lists (``faults.py``), planted
  before the driver is built, through a run's own check.

Each reading is judged by the comparison a run makes against the cell's
limits, and carries its ``correct``. The benchmark's own runs never run
any of this. One JSON line per seed.
"""
import contextlib
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from chipbench import common, control, faults, harness, reference  # noqa: E402

# rows per control block: the (c, rows, m) weighted copy stays near 2**25
CONTROL_ELEMS = 1 << 25


def judged(checks: dict, limits: dict) -> dict:
    return dict(checks, correct=harness.judge(checks, limits))


def drive(cell, seed: int, seconds: float):
    """The cell's driver, built from ``seed`` and driven through a short
    window; returns it with the set-up and window readings."""
    driver = common.by_name("drivers", cell.workload["driver"])
    t0 = time.perf_counter()
    drv = driver.Driver(cell.config, cell.workload, seed, None)
    setup_s = time.perf_counter() - t0
    _, window_s, lat = harness.run_window(
        drv.step, seconds, lambda name: contextlib.nullcontext())
    return drv, {"setup_s": setup_s, "steps": len(lat),
                 "window_s": window_s}


def read_seed(cell, seed: int, seconds: float, with_control: bool) -> dict:
    import jax
    limits = cell.workload["limits"]
    drv, out = drive(cell, seed, seconds)
    W = drv.result_W()
    drv.engine = None
    t0 = time.perf_counter()
    ref = reference.stats(common.host_blocks(*drv.live_parts()))
    out["reference_s"] = time.perf_counter() - t0
    out["program"] = judged(reference.check(W, drv.solved.stats, ref,
                                            drv.lam), limits)
    if with_control:
        m, _, c = common.shape(cell.config)
        rows = max(CONTROL_ELEMS // (c * m), 1)
        for name, dot in (("control", control.dot_bf16),
                          ("three_pass", control.dot_three_pass),
                          ("stated_precision", control.dot_highest)):
            blocks = (jax.device_put(b) for b in common.host_blocks(
                *drv.live_parts(), rows=rows))
            Wc, stats = control.control(blocks, drv.lam, dot)
            out[name] = judged(reference.check(Wc, stats, ref, drv.lam),
                               limits)
    del drv
    gc.collect()
    if with_control:
        out["faults"] = {}
        for name in common.by_name("drivers",
                                   cell.workload["driver"]).FAULTS:
            patch = faults.Patch()
            getattr(faults, name)(patch.setattr)
            try:
                drv, _ = drive(cell, seed, seconds)
                out["faults"][name] = judged(drv.check(), limits)
            finally:
                patch.undo()
            del drv
            gc.collect()
    return dict(seed=seed, **out)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    harness.configure_jax_cache()
    try:
        harness.require_chips(int(cell.entry["chips"]))
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    seeds = [int(s) for s in args.seeds.split(",") if s]
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    lines = []
    for seed in seeds + sorted(ctrl - set(seeds)):
        rec = read_seed(cell, seed, args.seconds, seed in ctrl)
        rec["cell"] = cell.name
        lines.append(json.dumps(rec))
        print(lines[-1], flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text("\n".join(lines) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
