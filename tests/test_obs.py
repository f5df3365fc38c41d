"""Flight-recorder suite (ISSUE 10, DESIGN.md §14): tracing, exporters,
energy attribution, and the two structural invariants —

* **tracing off is free and exact**: a round run with ``trace=None``
  (the engine's NULL_TRACER default) returns the bit-identical ``W``
  and dispatch counts of a traced run, on the loop, fused and tiered
  paths alike;
* **sizes and timings, never statistics**: span/event attributes
  reject arrays by construction, and a secagg round's exported trace
  carries none of the wire's statistic values (the spy test).

The golden-schema tests pin the closed span/event taxonomy and the
Prometheus metric-name contract — drifting either is an exporter
schema change that must be made loudly, here and in DESIGN.md §14.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.core import activations as acts
from repro.core.engine import FederationEngine, RoundReport
from repro.core.scenario import Scenario
from repro.core.wire import get_wire
from repro.data import partition, synthetic
from repro.obs import (CATEGORIES, EVENT_NAMES, NULL_TRACER, PROM_METRICS,
                       SPAN_NAMES, SPAN_REQUIRED_FIELDS, EnergyLedger,
                       NullTracer, Tracer, console_summary, sanitize_attrs,
                       to_perfetto, to_prometheus, write_perfetto,
                       write_prometheus)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _parts(P=8, n=480, m=6, seed=3):
    spec = synthetic.DatasetSpec("toy", n, m, 2)
    X, y = synthetic.generate(spec, seed=seed)
    parts = partition.iid(X, y, P, seed=seed)
    return ([p[0] for p in parts],
            [np.asarray(acts.encode_labels(p[1], 2)) for p in parts])


def _eval_set(n=120, m=6, seed=99):
    return synthetic.generate(synthetic.DatasetSpec("toy", n, m, 2),
                              seed=seed)


# ------------------------------------------------------- golden schema
def test_span_taxonomy_pinned():
    """The closed span vocabulary — exporters and dashboards key on
    these exact names; extending it is a deliberate schema change."""
    assert SPAN_NAMES == (
        "round", "client.stats", "bucket.dispatch", "mask.encode",
        "collective", "tier.fold", "merge", "solve", "score.pass",
        "ledger.apply", "round.prep", "bucket.stack", "client.wait",
        "ledger.snapshot", "gc")


def test_event_taxonomy_pinned():
    assert EVENT_NAMES == (
        "fault.retry", "fault.quarantine", "fault.failover",
        "fault.recovered", "quorum.commit", "journal.commit",
        "ledger.join", "ledger.leave", "ledger.revise", "ledger.evict",
        "score.client")


def test_span_required_fields_pinned():
    assert SPAN_REQUIRED_FIELDS == ("name", "track", "t0", "dur_s",
                                    "cpu_s")
    with Tracer().span("solve") as _:
        pass


def test_prom_metric_names_pinned():
    assert PROM_METRICS == (
        "fed_round_dispatches_total", "fed_round_wire_bytes_total",
        "fed_round_retry_bytes_total", "fed_round_retry_joules_total",
        "fed_round_energy_joules_total", "fed_round_cpu_seconds_total",
        "fed_round_quarantined_total", "fed_round_tier_peak_bytes",
        "fed_round_span_seconds")


def test_energy_categories_pinned():
    assert CATEGORIES == ("compute", "uplink", "retry", "scoring")


def test_span_to_dict_carries_required_fields():
    tr = Tracer()
    with tr.span("merge", n_uploads=3):
        pass
    d = tr.spans[0].to_dict()
    for field in SPAN_REQUIRED_FIELDS:
        assert field in d, field
    json.dumps(d)


# ----------------------------------------------------- tracer mechanics
def test_tracer_records_span_timing_and_attrs():
    tr = Tracer()
    with tr.span("solve", first=True) as sp:
        sp.set(extra=7)
    (span,) = tr.spans
    assert span.name == "solve" and span.track == "coordinator"
    assert span.dur_s >= 0.0 and span.cpu_s >= 0.0
    assert span.attrs == {"first": True, "extra": 7}


def test_tracer_strict_rejects_unknown_names():
    tr = Tracer()
    with pytest.raises(ValueError, match="unknown span name"):
        tr.span("dinner")
    with pytest.raises(ValueError, match="unknown event name"):
        tr.event("dinner.ready")


def test_tracer_depth_tracks_nesting_and_survives_exceptions():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("round"):
            with tr.span("merge"):
                raise RuntimeError("boom")
    round_sp, merge_sp = tr.spans
    assert (round_sp.depth, merge_sp.depth) == (0, 1)
    # depth counters unwound: a new span starts at depth 0 again
    with tr.span("solve"):
        pass
    assert tr.spans[-1].depth == 0


def test_null_tracer_is_shared_constant_noop():
    assert NULL_TRACER.enabled is False
    ctx1 = NULL_TRACER.span("round", anything="goes")
    ctx2 = NullTracer().span("solve")
    assert ctx1 is ctx2  # one shared context object, no allocation
    with ctx1 as sp:
        sp.set(bytes=12)  # same late-attr interface as a live span
    assert NULL_TRACER.spans == () and NULL_TRACER.events == ()


def test_sanitize_attrs_scalars_pass_arrays_raise():
    ok = sanitize_attrs({"n": 3, "frac": 0.5, "tag": "x", "flag": True,
                         "np_scalar": np.float64(2.0),
                         "small_list": [1, 2, 3]})
    assert ok["np_scalar"] == 2.0 and ok["small_list"] == [1, 2, 3]
    with pytest.raises(TypeError, match="not a scalar"):
        sanitize_attrs({"payload": np.zeros((4, 4))})
    with pytest.raises(TypeError, match="not a scalar"):
        sanitize_attrs({"payload": np.zeros(3)})
    with pytest.raises(TypeError, match="sequence"):
        sanitize_attrs({"long": list(range(17))})
    import jax.numpy as jnp
    with pytest.raises(TypeError, match="not a scalar"):
        sanitize_attrs({"payload": jnp.zeros((2, 2))})


# -------------------------------------------------- off = bit-identical
@pytest.mark.parametrize("kw", [
    {},  # per-client loop
    {"fused": True},
    {"wire": "gram", "topology": "fanout=4,tiers=2"},  # tiered
    # ragged shards: several buckets, then a merge that ends on its
    # aggregate while traced
    {"wire": "gram", "fused": True, "ragged": True},
    {"wire": "gram", "batch_clients": True, "ragged": True},
    # late joiners: W_first, then the late merge
    {"wire": "gram", "scenario": Scenario.parse("late_join=0.25")},
], ids=["loop", "fused", "tiered", "fused-ragged", "batched-ragged",
        "loop-late"])
def test_tracing_off_and_on_are_bit_identical(kw):
    """trace=None (the pre-PR default) and a live tracer produce the
    bitwise-same W and the same dispatch count: observation never
    touches arrays, RNG state, or dispatch structure (a traced merge
    only waits for its aggregate)."""
    kw = dict(kw)
    pX, pD = _parts(P=8)
    if kw.pop("ragged", False):
        pX = [X[:12 + 7 * i] for i, X in enumerate(pX)]
        pD = [d[:12 + 7 * i] for i, d in enumerate(pD)]
    got = {}
    for traced in (False, True):
        eng = FederationEngine(trace=Tracer() if traced else None, **kw)
        r = eng.run(pX, pD)
        got[traced] = (np.asarray(r.W).copy(), r.dispatches,
                       None if r.W_first is None
                       else np.asarray(r.W_first).copy())
    assert np.array_equal(got[False][0], got[True][0])
    assert got[False][1] == got[True][1]
    if got[False][2] is not None:
        assert np.array_equal(got[False][2], got[True][2])


# ------------------------------------------------- acceptance: P = 10³
@pytest.fixture(scope="module")
def traced_p1000(tmp_path_factory):
    """One traced tiered+faulted P=10³ round (the ISSUE acceptance
    round), shared across the assertions below."""
    P = 1000
    spec = synthetic.DatasetSpec("toy", 2 * P, 6, 2)
    X, y = synthetic.generate(spec, seed=0)
    parts = partition.iid(X, y, P, seed=0)
    pX = [p[0] for p in parts]
    pD = [np.asarray(acts.encode_labels(p[1], 2)) for p in parts]
    tr = Tracer()
    eng = FederationEngine(wire="gram", topology="fanout=64,tiers=3",
                           faults="flaky=0.05,maxretries=2,seed=0",
                           trace=tr)
    report = eng.run(pX, pD)
    out = tmp_path_factory.mktemp("obs")
    return tr, report, out


def test_p1000_perfetto_trace_is_valid(traced_p1000):
    tr, report, out = traced_p1000
    path = write_perfetto(tr, str(out / "round.json"))
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    assert doc["otherData"]["span_names"] == list(SPAN_NAMES)
    phases = {e["ph"] for e in evs}
    assert phases <= {"X", "i", "M"} and "X" in phases
    for e in evs:
        if e["ph"] == "X":
            assert e["name"] in SPAN_NAMES
            assert e["ts"] >= 0 and e["dur"] >= 0
        elif e["ph"] == "i":
            assert e["name"] in EVENT_NAMES
    # the faulted round really recorded fault instants
    assert any(e["ph"] == "i" and e["name"].startswith("fault.")
               for e in evs)


def test_p1000_prometheus_exposes_contract_names(traced_p1000):
    tr, report, out = traced_p1000
    path = write_prometheus(tr, str(out / "round.prom"), report=report)
    with open(path) as f:
        text = f.read()
    for name in PROM_METRICS:
        assert name in text, f"metric {name} missing from textfile"
    # report-side totals reconcile exactly
    assert f"fed_round_dispatches_total {report.dispatches}" in text
    assert f"fed_round_wire_bytes_total {report.wire_bytes}" in text
    # the tiered round exposes a real per-tier peak
    assert 'fed_round_tier_peak_bytes{tier="1"}' in text


def test_p1000_energy_reconciles_with_report(traced_p1000):
    tr, report, _ = traced_p1000
    led = EnergyLedger.from_report(report)
    got_s = led.seconds("compute") + led.seconds("scoring")
    assert got_s == pytest.approx(report.cpu_time, rel=1e-12)
    hier = report.hierarchy
    assert led.bytes("uplink") == int(hier["bytes_tiered"])
    f = report.faults
    assert led.bytes("retry") == int(f["retry_bytes"])
    cats = led.by_category()
    assert cats["uplink"] == pytest.approx(hier["uplink_j_tiered"])
    assert cats["retry"] == pytest.approx(f["retry_j"])
    assert led.total_j() == pytest.approx(sum(cats.values()))
    json.dumps(led.summary())


def test_p1000_console_summary_renders(traced_p1000):
    tr, report, _ = traced_p1000
    text = console_summary(tr, report)
    assert "tier.fold" in text and "energy:" in text
    assert "fault." in text  # event counts rendered


# ------------------------------------------------------- privacy: spy
def test_secagg_trace_carries_no_statistic_values():
    """A traced masked round's exported JSON contains sizes and
    timings only — none of the wire's actual statistic values."""
    pX, pD = _parts(P=6)
    tr = Tracer()
    eng = FederationEngine(wire="gram", privacy="secagg", trace=tr)
    eng.run(pX, pD)
    doc = json.dumps(to_perfetto(tr))
    wire = get_wire("gram")
    stats = wire.local_stats(pX[0], pD[0])
    leaves = [np.asarray(x).ravel() for x in
              (stats if isinstance(stats, (tuple, list)) else [stats])]
    probed = 0
    for leaf in leaves:
        for v in leaf[:8]:
            s = repr(float(v))
            if len(s) >= 8:  # full-precision floats only: no "0.0"s
                probed += 1
                assert s not in doc, f"statistic value {s} leaked"
    assert probed > 0
    # and structurally: an array physically cannot ride an attribute
    with pytest.raises(TypeError, match="not a scalar"):
        tr.span("mask.encode", payload=np.asarray(leaves[0]))


def test_all_span_attrs_are_json_scalars():
    pX, pD = _parts(P=8)
    tr = Tracer()
    FederationEngine(wire="gram", fused=True,
                     faults="flaky=0.2,seed=1", trace=tr).run(pX, pD)
    for sp in tr.spans:
        for k, v in sp.attrs.items():
            assert isinstance(v, (bool, int, float, str, type(None),
                                  list)), (sp.name, k, type(v))
    for ev in tr.events:
        for k, v in ev.attrs.items():
            assert isinstance(v, (bool, int, float, str, type(None),
                                  list)), (ev.name, k, type(v))


# ------------------------------------------- RoundReport.to_dict audit
def test_report_to_dict_round_trips_faulted_tiered():
    pX, pD = _parts(P=16, n=640)
    eng = FederationEngine(wire="gram",
                           topology="fanout=4,tiers=2",
                           faults="crash@upload:p3,flaky=0.1,seed=1",
                           quorum=0.5)
    r = eng.run(pX, pD)
    d = r.to_dict()
    assert json.loads(json.dumps(d)) == d
    assert d["wire_bytes"] == r.wire_bytes
    assert d["hierarchy"]["bytes_tiered"] == r.hierarchy["bytes_tiered"]
    assert "W" not in d  # model excluded by default
    dm = r.to_dict(include_model=True)
    assert np.asarray(dm["W"]).shape == np.asarray(r.W).shape
    json.dumps(dm)


def test_report_to_dict_round_trips_selection_and_privacy():
    pX, pD = _parts(P=8)
    Xe, ye = _eval_set()
    r = FederationEngine(
        wire="gram", scenario=Scenario.parse("select=topk:3"),
        select_eval=(Xe, ye)).run(pX, pD)
    d = r.to_dict()
    assert json.loads(json.dumps(d)) == d
    assert d["contribution"]["n_selected"] == 3
    rp = FederationEngine(wire="gram", privacy="secagg").run(pX, pD)
    dp = rp.to_dict()
    assert json.loads(json.dumps(dp)) == dp
    assert dp["privacy"]["mode"] == "secagg"


# --------------------------------------------------- the energy ledger
def test_energy_ledger_add_and_aggregate():
    led = EnergyLedger(watts=10.0, j_per_byte=1e-6)
    led.add("compute", "client:0", seconds=2.0)
    led.add("compute", "client:0", seconds=1.0)
    led.add("uplink", "fleet", nbytes=1_000_000)
    led.add("retry", "fleet", nbytes=100, joules=42.0)
    assert led.seconds("compute") == pytest.approx(3.0)
    assert led.by_client()["client:0"]["compute"] == pytest.approx(30.0)
    assert led.by_category()["uplink"] == pytest.approx(1.0)
    assert led.by_category()["retry"] == 42.0  # explicit price wins
    assert led.total_j() == pytest.approx(73.0)
    with pytest.raises(ValueError, match="unknown energy category"):
        led.add("gravity", "fleet", seconds=1.0)


def test_energy_from_report_selection_covers_scoring_clients():
    """Selection rounds: unselected clients' scoring compute is real
    energy — attributed under 'scoring', on top of report.cpu_time
    (which only covers committed participants)."""
    pX, pD = _parts(P=8)
    Xe, ye = _eval_set()
    r = FederationEngine(
        wire="gram", scenario=Scenario.parse("select=topk:3"),
        select_eval=(Xe, ye)).run(pX, pD)
    led = EnergyLedger.from_report(r)
    extra = float(r.contribution["scoring_client_s"])
    got = led.seconds("compute") + led.seconds("scoring")
    assert got == pytest.approx(r.cpu_time + extra, rel=1e-12)
    assert led.seconds("scoring") > 0.0


def test_energy_from_trace_attributes_by_scope():
    tr = Tracer()
    with tr.span("tier.fold", tier=1, bytes=100):
        pass
    with tr.span("client.stats", track="client", cid=4):
        pass
    with tr.span("solve"):
        pass
    with tr.span("score.pass", n_clients=3):
        pass
    led = EnergyLedger.from_trace(tr)
    scopes = {e.scope for e in led.entries}
    assert {"tier:1", "client:4", "coordinator"} <= scopes
    assert led.by_tier().keys() == {"tier:1"}
    assert led.by_client().keys() == {"client:4"}
    assert set(led.by_category()) == set(CATEGORIES)


# ---------------------------------------------------------- bench_diff
def _bench_diff():
    spec = importlib.util.spec_from_file_location(
        "bench_diff", os.path.join(REPO, "scripts", "bench_diff.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _payload(**over):
    row = {"transport": "local", "wire": "gram", "P": 10,
           "mode": "loop", "dispatches": 10, "wire_bytes": 1000,
           "compiles": 1, "cpu_time": 1.0}
    row.update(over)
    return {"rows": [row],
            "faults": {"rows": [{"flaky": 0.2, "availability": 1.0,
                                 "retries": 1, "retry_bytes": 10,
                                 "retry_j": 0.1}]}}


def test_bench_diff_passes_identical_payloads():
    bd = _bench_diff()
    base = _payload()
    _, failures = bd.diff(base, base, 0.25, 3.0)
    assert failures == 0


def test_bench_diff_gates_deterministic_regressions():
    bd = _bench_diff()
    table, failures = bd.diff(_payload(dispatches=20), _payload(),
                              0.25, 3.0)
    assert failures == 1
    assert any(r[2] == "dispatches" and r[-1] == "FAIL" for r in table)


def test_bench_diff_availability_down_is_a_regression():
    bd = _bench_diff()
    cur = _payload()
    cur["faults"]["rows"][0]["availability"] = 0.5
    _, failures = bd.diff(cur, _payload(), 0.25, 3.0)
    assert failures == 1
    # and an improvement the other way never gates
    cur["faults"]["rows"][0]["availability"] = 1.0
    base = _payload()
    base["faults"]["rows"][0]["availability"] = 0.5
    _, failures = bd.diff(cur, base, 0.25, 3.0)
    assert failures == 0


def test_bench_diff_timing_gated_loosely():
    bd = _bench_diff()
    _, failures = bd.diff(_payload(cpu_time=2.0), _payload(), 0.25, 3.0)
    assert failures == 0  # 2x ΣCPU: within the noisy-timing gate
    _, failures = bd.diff(_payload(cpu_time=9.0), _payload(), 0.25, 3.0)
    assert failures == 1  # 8x is catastrophic on any box


def test_bench_diff_grid_changes_are_not_failures():
    bd = _bench_diff()
    cur = _payload()
    cur["rows"] = []  # quick lane ran a smaller grid
    table, failures = bd.diff(cur, _payload(), 0.25, 3.0)
    assert failures == 0
    assert any(r[5] == "missing" for r in table)


def test_bench_diff_cli_ok_against_committed_baseline():
    """The committed baseline must accept itself (the ci_smoke path)."""
    bd = _bench_diff()
    baseline = os.path.join(REPO, "benchmarks", "baselines",
                            "BENCH_fedround.baseline.json")
    assert os.path.exists(baseline)
    rc = bd.main(["--bench", baseline, "--baseline", baseline])
    assert rc == 0
