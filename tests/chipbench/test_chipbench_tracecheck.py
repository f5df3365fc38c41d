"""The tracing checks' arithmetic (chipbench/tracecheck.py): the steps'
host time split by span, and spans matched to their annotations."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import tracecheck  # noqa: E402


def span(id, name, t0, dur, parent=None):
    return SimpleNamespace(id=id, name=name, t0=t0, dur_s=dur,
                           parent=parent)


def test_host_split_adds_up_to_the_window():
    spans = [span(0, "round", 0.0, 10.0),
             span(1, "round.prep", 0.0, 2.0, parent=0),
             span(2, "client.stats", 3.0, 4.0, parent=0),
             span(3, "client.wait", 5.0, 2.0, parent=2),
             span(4, "gc", 11.0, 0.5)]
    out = tracecheck.host_split(spans, [(-1.0, 12.0)], window_s=13.5)
    ms = {k: v / 1e3 for k, v in out["self_ms"].items()}
    assert ms == pytest.approx({"round": 4.0, "round.prep": 2.0,
                                "client.stats": 2.0, "client.wait": 2.0,
                                "gc": 0.5})
    gaps = {k: v / 1e3 for k, v in out["no_span_ms"].items()}
    assert gaps == pytest.approx({"before": 1.0, "between": 1.0,
                                  "after": 0.5})
    assert out["step_ms"] == pytest.approx(13e3)
    assert out["harness_ms"] == pytest.approx(0.5e3)
    assert sum(out["self_ms"].values()) + sum(
        out["no_span_ms"].values()) == pytest.approx(out["step_ms"])


def test_host_split_per_step_and_a_step_in_no_span():
    spans = [span(0, "round", 1.0, 2.0)]
    out = tracecheck.host_split(spans, [(0.0, 4.0), (4.0, 5.0)],
                                window_s=5.0)
    assert out["self_ms"] == pytest.approx({"round": 1e3})
    assert out["no_span_ms"] == pytest.approx(
        {"before": 1e3, "between": 0.0, "after": 0.5e3})
    assert out["harness_ms"] == pytest.approx(0.0)


def test_offsets_match_the_nearest_annotation_of_the_same_name():
    spans = [SimpleNamespace(name="solve", t0=1e-6),
             SimpleNamespace(name="merge", t0=5e-6),
             SimpleNamespace(name="gc", t0=1.0)]
    notes = {"solve": [900, 1_030, 9_000], "merge": [4_990]}
    rows = tracecheck.offsets(spans, base=0, lo=0, hi=10 ** 6,
                              annotations=notes)
    assert rows == [(1_000, 30), (5_000, -10)]
