"""The reduction from a profiler trace to busy time, kernel time and the
breakdown (chipbench/trace_reduce.py)."""
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, trace_reduce as tr  # noqa: E402


def test_union_merges_overlaps_and_touching_intervals():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == \
        [(0, 4), (5, 7), (9, 10)]


def test_busy_and_gaps_inside_the_window():
    ev = [("a", -5, 2), ("b", 1, 3), ("c", 6, 8), ("d", 12, 20)]
    assert tr.busy_ns(ev, 0, 10) == 3 + 2
    assert tr.gaps(ev, 0, 10) == [(3, 6), (8, 10)]
    assert tr.gaps([], 0, 10) == [(0, 10)]


def test_kernel_time_and_op_families():
    ev = [("gram_kernel.1", 0, 4), ("gram_kernel.22", 10, 13),
          ("fusion.3", 4, 10)]
    assert tr.kernel_ns(ev, r"^gram_kernel") == 7
    assert tr.op_family("fusion.123") == "fusion"
    assert tr.op_family("%pad_maximum_fusion.4 = f32[4096,29]{0,1} "
                        "fusion(f32[4096,28] %a)") == "pad_maximum_fusion"
    assert tr.op_family("copy_start.2.1") == "copy_start"
    s = tr.Summary({"/device:TPU:0": ev}, 0, 20, 2)
    assert s.busy_s == pytest.approx(13e-9)
    assert s.device_ops() == [["fusion", 6e-9], ["gram_kernel", 7e-9]][::-1] \
        or s.device_ops()[0][0] == "gram_kernel"


def test_idle_gaps_go_to_the_innermost_open_span():
    ev = [("k", 0, 10), ("k", 30, 40), ("k", 60, 70)]
    # client spans sit on a track of their own at depth 0: nesting is by
    # time, not by the tracer's per-track depth
    spans = [("round", 0, 100), ("client.stats", 5, 35),
             ("solve", 50, 65)]
    s = tr.Summary({"/device:TPU:0": ev}, 0, 100, 1)
    got = dict((n, v) for n, v in s.idle_gaps(spans))
    # gaps (10, 30) → client.stats; (40, 60), midpoint 50 → solve;
    # (70, 100) → round
    assert got == pytest.approx({"client.stats": 20e-9, "solve": 20e-9,
                                 "round": 30e-9})
    assert tr.attribute(spans, [1, 20, 55, 200]) == \
        ["round", "client.stats", "solve", None]


def test_summary_needs_the_window_mark():
    with pytest.raises(ValueError, match="chipbench.window"):
        tr.summarize({}, [("chipbench.step", 0, 1)])
    s = tr.summarize({"/device:TPU:0": [("k", 5, 15)]},
                     [("chipbench.window", 10, 30),
                      ("chipbench.step", 10, 20), ("chipbench.step", 20, 30),
                      ("chipbench.step", 40, 50)])
    assert (s.n_steps, s.window_s, s.busy_s) == (2, 20e-9, 5e-9)


TINY = ROOT / "chipbench" / "traces" / "tiny_silo.xplane.pb"


def test_recorded_chip_trace():
    """A traced window of one small round (4 clients of 4,096 HIGGS rows,
    per-client loop) recorded on a v5e; its run reported busy_s
    8.995e-05 and window_s 0.038205246."""
    mod = harness.by_name("metrics", "gram_roofline.round")
    ops, marks = tr.read_xplane(TINY)
    assert list(ops) == ["/device:TPU:0"]
    s = tr.summarize(ops, marks)
    assert s.n_steps == 1
    assert s.window_s == pytest.approx(0.038205246)
    assert s.busy_s == pytest.approx(8.995e-05)
    # busy time again, by a plain sweep over the sorted clipped intervals
    ev = sorted((max(a, s.lo), min(b, s.hi))
                for _, a, b in ops["/device:TPU:0"] if b > s.lo and a < s.hi)
    total, end = 0, s.lo
    for a, b in ev:
        if b > end:
            total += b - max(a, end)
            end = b
    assert s.busy_s == pytest.approx(total / 1e9)
    # four clients: four kernel events, the kernel the largest op family
    hits = [n for n, _, _ in s.all_ops if re.search(mod.KERNEL, n)]
    assert len(hits) == 4
    assert s.device_ops()[0][0] == "gram_stats_fleet"
    assert 0 < s.kernel_s(mod.KERNEL) < s.busy_s
    gaps = s.idle_gaps([])
    assert gaps[0][0] == tr.NO_SPAN
    assert sum(v for _, v in gaps) == pytest.approx(s.window_s - s.busy_s)
