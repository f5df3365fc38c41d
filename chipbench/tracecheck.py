"""The program's spans against the profiler's clock, where each step's
host time goes, and what tracing costs, in one process on the chip.

    python3 chipbench/tracecheck.py --workload <cell> --seed <n> \
        --seconds <s>

It sets the cell up as a run does, then drives windows of
``--seconds`` in four settings, in turn, twice over:

* ``off``: no tracer (the engine's null tracer), the profiler off;
* ``tracer``: a live ``Tracer``, the profiler off;
* ``profiler``: the profiler on, no tracer;
* ``both``: both on, as in a ``--trace 1`` run.

For each window it gives the steps, the window's seconds over its steps
and the median step. In each window with a tracer it also splits the
steps' host time by span (``host``, ms per step): each span name's self
time (its duration less its children's), and the time in no span, in
three parts: ``before`` the step's first top-level span (the driver
before it calls the program), ``between`` top-level spans and ``after``
the last (the driver after the call returns); ``harness`` is the window
less its steps. These add up to the window.

In each ``both`` window it also places every span on the trace clock as
``harness.run_cell`` does (one offset, through the window mark; the
formula is a copy of run_cell's and must follow it) and compares that
start with the start of the span's own ``TraceAnnotation`` on the
trace's host plane, matched by name and nearest start: the median and
largest offset, and the drift, the median offset in the window's last
step less that in its first. One JSON line per window on stdout; the
benchmark's own runs never run any of this.
"""
import bisect
import collections
import gc
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from chipbench import harness, trace_reduce  # noqa: E402

SETTINGS = ("off", "tracer", "profiler", "both")
REPEATS = 2


def host_annotations(path, names):
    """Start times (ns) of the host plane's events named in ``names``."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.setdefault(e.name, []).append(int(e.start_ns))
    return {k: sorted(v) for k, v in out.items()}


def offsets(spans, base, lo, hi, annotations):
    """``(aligned start, annotation start - aligned start)`` in ns for
    every span that the alignment puts inside ``[lo, hi)``."""
    rows = []
    for s in spans:
        at = base + int(round(s.t0 * 1e9))
        starts = annotations.get(s.name, [])
        if not lo <= at < hi or not starts:
            continue
        j = bisect.bisect_left(starts, at)
        near = min(starts[max(j - 1, 0):j + 1], key=lambda a: abs(a - at))
        rows.append((at, near - at))
    return rows


def clock_check(tracer, t0, log_dir):
    path = trace_reduce.find_xplane(log_dir)
    ops, marks = trace_reduce.read_xplane(path)
    win = trace_reduce.summarize(ops, marks)
    base = win.lo - int(round((t0 - tracer.t_origin) * 1e9))
    names = {s.name for s in tracer.spans}
    rows = offsets(tracer.spans, base, win.lo, win.hi,
                   host_annotations(path, names))
    steps = sorted((a, b) for n, a, b in marks
                   if n == trace_reduce.STEP_MARK and win.lo <= a < win.hi)
    off = [d for _, d in rows]

    def in_step(step):
        a, b = step
        return [d for t, d in rows if a <= t < b]

    first, last = in_step(steps[0]), in_step(steps[-1])
    return {"spans": len(tracer.spans), "matched": len(rows),
            "offset_median_us": statistics.median(off) / 1e3,
            "offset_max_abs_us": max(abs(d) for d in off) / 1e3,
            "drift_us": (statistics.median(last)
                         - statistics.median(first)) / 1e3,
            "first_step_median_us": statistics.median(first) / 1e3,
            "last_step_median_us": statistics.median(last) / 1e3,
            "window_s": win.window_s}


def host_split(spans, steps, window_s):
    """The steps' host time by span, in ms per step. ``steps`` are
    ``(start, end)`` on the spans' clock."""
    inner = collections.Counter()
    for s in spans:
        if s.parent is not None:
            inner[s.parent] += s.dur_s
    own = collections.Counter()
    for s in spans:
        own[s.name] += s.dur_s - inner[s.id]
    # top-level spans nest in no other, so they never overlap
    top = sorted((s.t0, s.t0 + s.dur_s) for s in spans if s.parent is None)
    no_span = collections.Counter()
    for a, b in steps:
        cut = [(max(x, a), min(y, b)) for x, y in top if x < b and y > a]
        if not cut:
            no_span["before"] += b - a
            continue
        no_span["before"] += cut[0][0] - a
        no_span["after"] += b - cut[-1][1]
        no_span["between"] += (cut[-1][1] - cut[0][0]
                               - sum(y - x for x, y in cut))
    n = len(steps)
    in_steps = sum(b - a for a, b in steps)
    return {"step_ms": 1e3 * in_steps / n,
            "self_ms": {k: 1e3 * v / n for k, v in own.most_common()},
            "no_span_ms": {k: 1e3 * no_span[k] / n
                           for k in ("before", "between", "after")},
            "harness_ms": 1e3 * (window_s - in_steps) / n}


def window(drv, setting, seconds):
    import jax
    from repro.obs.trace import NULL_TRACER, Tracer
    tracer = Tracer() if setting in ("tracer", "both") else None
    drv.engine.trace = tracer or NULL_TRACER
    gc.collect()
    log_dir = None
    if setting in ("profiler", "both"):
        log_dir = Path(tempfile.mkdtemp(prefix="chipbench-tracecheck-"))
        jax.profiler.start_trace(str(log_dir))
    if tracer is not None:
        tracer.clear()
    steps = []

    def step():
        a = time.perf_counter()
        drv.step()
        steps.append((a, time.perf_counter()))

    t0, window_s, lat = harness.run_window(step, seconds,
                                           jax.profiler.TraceAnnotation)
    if log_dir is not None:
        jax.profiler.stop_trace()
    out = {"setting": setting, "steps": len(lat), "window_s": window_s,
           "mean_step_s": window_s / len(lat),
           "median_step_s": statistics.median(lat)}
    if tracer is not None:
        at = tracer.t_origin
        out["host"] = host_split(tracer.spans,
                                 [(a - at, b - at) for a, b in steps],
                                 window_s)
    if setting == "both":
        out["clock"] = clock_check(tracer, t0, log_dir)
    if log_dir is not None:
        shutil.rmtree(log_dir, ignore_errors=True)
    drv.engine.trace = NULL_TRACER
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.Cell.load(args.workload)
    harness.configure_jax_cache()
    harness.require_chips(int(cell.entry["chips"]))
    drv = harness.by_name("drivers", cell.workload["driver"]).Driver(
        cell.config, cell.workload, args.seed, None)
    for r in range(REPEATS):
        for setting in SETTINGS:
            row = dict(window(drv, setting, args.seconds), cell=cell.name,
                       seed=args.seed, repeat=r, at=time.time())
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
