"""Readers of the program's linked spans (chipbench/spans.py and the
metrics that read ``round.prep``, ``bucket.stack``, ``client.wait``,
``merge``, ``ledger.snapshot`` and ``gc``), on synthetic records and in
a small traced run of each cell on the CPU."""
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


def reader(name):
    return harness.by_name("metrics", name)


def span(name, dur):
    return SimpleNamespace(name=name, dur_s=dur)


def lspan(name, dur, id, parent=None, **attrs):
    return SimpleNamespace(name=name, dur_s=dur, id=id, parent=parent,
                           attrs=attrs)


def record(unit="round", steps=4, **kw):
    base = dict(unit=unit, steps=steps, window_s=1.0, latencies=[0.25] * 4,
                reports=[SimpleNamespace(dispatches=100)] * steps,
                spans=[span("client.stats", 0.1),
                       span("bucket.dispatch", 0.02), span("solve", 0.004),
                       span("ledger.apply", 0.008), span("round", 1.0)],
                trace=None, work=None, peaks=None)
    base.update(kw)
    return harness.Record(**base)


def linked_spans():
    """Two steps of a program whose spans carry parent links."""
    out = []
    for k in range(2):
        b = 100 * k
        out += [lspan("round", 1.0, b), lspan("round.prep", 0.003, b + 1, b),
                lspan("bucket.stack", 0.2, b + 2, b, bytes=3_000_000),
                lspan("bucket.dispatch", 0.05, b + 3, b),
                lspan("client.wait", 0.04, b + 4, b + 3),
                lspan("client.stats", 0.01, b + 5, b),
                lspan("client.wait", 0.006, b + 6, b + 5),
                lspan("gc", 0.0005, b + 7, b + 6, generation=0,
                      collected=3),
                lspan("merge", 0.08, b + 8, b),
                lspan("solve", 0.012, b + 9, b),
                lspan("ledger.snapshot", 0.009, b + 10, b + 9),
                lspan("gc", 0.0015, b + 11, None, generation=2,
                      collected=0)]
    return out
def test_prep_ms_reads_round_prep_per_step():
    assert reader("prep_ms.round").read(
        record(steps=2, spans=linked_spans())) == pytest.approx(3.0)
    assert reader("prep_ms.round").read(record()) is None
    assert reader("prep_ms.round").read(
        record(unit="event", steps=2, spans=linked_spans())) is None


def test_prep_ms_event_reads_round_prep_per_event():
    assert reader("prep_ms.event").read(
        record(unit="event", steps=2, spans=linked_spans())) == \
        pytest.approx(3.0)
    assert reader("prep_ms.event").read(record(unit="event")) is None
    assert reader("prep_ms.event").read(
        record(steps=2, spans=linked_spans())) is None


def test_stack_ms_reads_bucket_stack_per_round():
    assert reader("stack_ms.round").read(
        record(steps=2, spans=linked_spans())) == pytest.approx(200.0)
    assert reader("stack_ms.round").read(record()) is None


def test_stack_mb_sums_the_stacks_bytes():
    assert reader("stack_mb.round").read(
        record(steps=2, spans=linked_spans())) == pytest.approx(3.0)
    # a span without the count reads nothing
    bare = [lspan("bucket.stack", 0.2, 1)]
    assert reader("stack_mb.round").read(record(steps=2, spans=bare)) \
        is None


def test_stats_host_ms_is_self_time_less_the_waits():
    # per step: (0.05 - 0.04) + (0.01 - 0.006) s; the gc under the wait
    # is not subtracted a second time
    assert reader("stats_host_ms.round").read(
        record(steps=2, spans=linked_spans())) == pytest.approx(14.0)
    # unlinked spans (no client.wait) read nothing
    assert reader("stats_host_ms.round").read(record()) is None


def test_stats_host_ms_event():
    assert reader("stats_host_ms.event").read(
        record(unit="event", steps=2, spans=linked_spans())) == \
        pytest.approx(14.0)
    assert reader("stats_host_ms.event").read(
        record(steps=2, spans=linked_spans())) is None


def test_merge_ms_needs_linked_spans():
    assert reader("merge_ms.round").read(
        record(steps=2, spans=linked_spans())) == pytest.approx(80.0)
    # an enqueue-only merge (spans without links) reads nothing
    assert reader("merge_ms.round").read(
        record(spans=[span("merge", 0.08)])) is None


def test_snapshot_ms_reads_the_ledger_snapshot_per_event():
    assert reader("snapshot_ms.event").read(
        record(unit="event", steps=2, spans=linked_spans())) == \
        pytest.approx(9.0)
    assert reader("snapshot_ms.event").read(record(unit="event")) is None


def test_gc_ms_round_sums_collector_passes():
    assert reader("gc_ms.round").read(
        record(steps=2, spans=linked_spans())) == pytest.approx(2.0)
    no_gc = [s for s in linked_spans() if s.name != "gc"]
    assert reader("gc_ms.round").read(record(steps=2, spans=no_gc)) == 0.0
    assert reader("gc_ms.round").read(record()) is None


def test_gc_ms_event_sums_collector_passes():
    assert reader("gc_ms.event").read(
        record(unit="event", steps=2, spans=linked_spans())) == \
        pytest.approx(2.0)
    assert reader("gc_ms.event").read(record(unit="event")) is None
    assert reader("gc_ms.event").read(
        record(steps=2, spans=linked_spans())) is None


SMALL = {"higgs.silo100": {"rows_train": 8_192, "clients": 4},
         "higgs.device10k": {"rows_train": 8_192, "clients": 16},
         "higgs.churn": {"rows_train": 8_192, "clients": 8,
                         "events": {"block": {"revise": 2, "leave": 1,
                                              "join": 1},
                                    "zipf_s": 1.1, "revise_fraction": 0.25,
                                    "min_active": 4}}}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_program_metrics_read_in_a_traced_run(name):
    """A traced run at a small size on the CPU: every metric the program
    records (spans and counters) reads a value in the cells its entry
    lists."""
    cell = harness.Cell.load(name, overrides=SMALL[name])
    res = harness.run_cell(cell, 2 ** 31 + 11, 0.3, True,
                           t_start=time.perf_counter(), require_tpu=False,
                           log=lambda msg: None)
    want = {m["name"] for m in cell.metrics("per_layer")
            if m["source"] in ("program_span", "program_counter")}
    assert want <= set(res["metrics"])
    assert all(v["value"] >= 0 for v in res["metrics"].values())
