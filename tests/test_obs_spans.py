"""The flight recorder's linked spans (DESIGN.md §14): parent links on
the loop, batched, fused and event-driven paths, the host-time spans
(``round.prep``, ``bucket.stack``, ``client.wait``, ``ledger.snapshot``,
``gc``), the collector hook's lifetime, and the profiler annotations
that put every span on the device trace's clock."""
import gc
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import activations as acts
from repro.core.engine import FederationEngine, _bucket_bound
from repro.data import synthetic
from repro.obs import Tracer


def _parts(sizes, m=6, seed=5):
    """Shards of the given sizes, device-resident (as a deployment
    holds them)."""
    spec = synthetic.DatasetSpec("toy", sum(sizes), m, 2)
    X, y = synthetic.generate(spec, seed=seed)
    D = np.asarray(acts.encode_labels(y, 2))
    cuts = np.cumsum([0] + list(sizes))
    return ([jnp.asarray(X[a:b]) for a, b in zip(cuts, cuts[1:])],
            [jnp.asarray(D[a:b]) for a, b in zip(cuts, cuts[1:])])


def _by_id(tr):
    return {s.id: s for s in tr.spans}


def _parent(tr, sp):
    return _by_id(tr).get(sp.parent)


def _work(tr):
    return [s for s in tr.spans if s.name != "gc"]


def test_parents_follow_nesting_across_tracks_and_exceptions():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("round"):
            with tr.span("client.stats", track="client"):
                with tr.span("client.wait", track="client"):
                    raise RuntimeError("boom")
    rnd, stats, wait = _work(tr)
    assert rnd.parent is None
    assert stats.parent == rnd.id and wait.parent == stats.id
    assert len({rnd.id, stats.id, wait.id}) == 3
    # the open stack unwound with the exception: a new span is top-level
    with tr.span("solve"):
        pass
    assert _work(tr)[-1].parent is None
    assert _work(tr)[-1].to_dict()["parent"] is None


def test_loop_round_links_prep_stats_wait_merge_solve():
    pX, pD = _parts([40, 40, 40])
    tr = Tracer()
    FederationEngine(wire="gram", trace=tr).run(pX, pD)
    (rnd,) = tr.spans_named("round")
    (prep,) = tr.spans_named("round.prep")
    assert prep.parent == rnd.id
    stats = tr.spans_named("client.stats")
    waits = tr.spans_named("client.wait")
    assert len(stats) == len(waits) == 3
    assert all(s.parent == rnd.id for s in stats)
    # each wait is its client pass's child, on the parent's track
    assert sorted(w.parent for w in waits) == sorted(s.id for s in stats)
    assert all(w.track == "client" for w in waits)
    for name in ("merge", "solve"):
        (sp,) = tr.spans_named(name)
        assert sp.parent == rnd.id
    # the round opens first and holds every other span in time
    assert rnd.t0 <= prep.t0
    assert all(s.t0 + s.dur_s <= rnd.t0 + rnd.dur_s
               for s in _work(tr))


@pytest.mark.parametrize("kw", [{"batch_clients": True}, {"fused": True}],
                         ids=["batched", "fused"])
def test_bucket_paths_stack_then_dispatch_then_wait(kw):
    sizes = [5, 30, 31, 70]
    pX, pD = _parts(sizes)
    tr = Tracer()
    eng = FederationEngine(wire="gram", trace=tr, **kw)
    eng.run(pX, pD)
    (rnd,) = tr.spans_named("round")
    stacks = tr.spans_named("bucket.stack")
    dispatches = tr.spans_named("bucket.dispatch")
    assert len(stacks) == len(dispatches) == 3     # bounds 8, 32, 128
    assert all(s.parent == rnd.id for s in stacks + dispatches)
    waits = tr.spans_named("client.wait")
    assert sorted(w.parent for w in waits) == \
        sorted(d.id for d in dispatches)
    assert all(w.track == "coordinator" for w in waits)
    (merge,) = tr.spans_named("merge")
    assert merge.parent == rnd.id


def test_bucket_stack_bytes_count_pulls_and_stacks():
    sizes = [5, 30, 31, 70]
    pX, pD = _parts(sizes)
    m, c = pX[0].shape[1], pD[0].shape[1]
    tr = Tracer()
    FederationEngine(wire="gram", fused=True, trace=tr).run(pX, pD)
    want = {}
    for n in sizes:
        b = _bucket_bound(n)
        got = want.setdefault(b, {"pulled": 0, "P": 0})
        got["pulled"] += 4 * n * (m + c)          # float32 X and D shards
        got["P"] += 1
    for sp in tr.spans_named("bucket.stack"):
        b, w = sp.attrs["bound"], want[sp.attrs["bound"]]
        stacks = 4 * w["P"] * b * (m + c) + 4 * w["P"]   # Xs, Ds, ns
        assert sp.attrs["bytes"] == w["pulled"] + stacks
    # host shards are not pulled: only the stacks (and the targets,
    # which the round places on the device) count
    hX = [np.asarray(X) for X in pX]
    tr2 = Tracer()
    FederationEngine(wire="gram", fused=True, trace=tr2).run(hX, pD)
    total = sum(s.attrs["bytes"] for s in tr.spans_named("bucket.stack"))
    total2 = sum(s.attrs["bytes"] for s in tr2.spans_named("bucket.stack"))
    assert total - total2 == 4 * sum(sizes) * m


def test_event_path_links_prep_snapshot_and_waits():
    pX, pD = _parts([40] * 4)
    tr = Tracer()
    eng = FederationEngine(wire="gram", trace=tr)
    eng.run_events(pX, pD, "leave@t1:p2,revise@t2:p1")
    preps = tr.spans_named("round.prep")
    rounds = tr.spans_named("round")
    assert len(preps) == 1 and preps[0].parent is None
    assert len(rounds) == 3 and all(r.parent is None for r in rounds)
    solves = tr.spans_named("solve")
    snaps = tr.spans_named("ledger.snapshot")
    assert len(snaps) == len(solves) == 3
    assert sorted(s.parent for s in snaps) == sorted(s.id for s in solves)
    by_id = _by_id(tr)
    assert all(by_id[s.parent].name == "round" for s in solves)
    waits = tr.spans_named("client.wait")
    assert len(waits) == len(tr.spans_named("client.stats")) == 5
    assert all(by_id[w.parent].name == "client.stats" for w in waits)


def test_gc_pass_in_a_traced_round_is_a_span_and_the_hook_leaves():
    before = list(gc.callbacks)
    pX, pD = _parts([40] * 3)
    tr = Tracer()
    assert len(gc.callbacks) == len(before) + 1

    def revise(X, d, tick):
        gc.collect()
        return X, d

    eng = FederationEngine(wire="gram", trace=tr)
    eng.run_events(pX, pD, "revise@t1:p0", revise_fn=revise)
    full = [s for s in tr.spans_named("gc")
            if s.attrs["generation"] == 2]
    assert full, "gc.collect() inside the round left no gc span"
    sp = full[-1]
    assert sp.track == "host" and sp.attrs["collected"] >= 0
    assert _parent(tr, sp).name == "round"
    assert _parent(tr, sp).attrs["tick"] == 1
    del eng, tr
    assert gc.callbacks == before


def test_profiler_trace_holds_the_program_spans(tmp_path):
    pX, pD = _parts([40, 40])
    tr = Tracer()
    eng = FederationEngine(wire="gram", trace=tr)
    eng.run(pX, pD)                        # compile outside the capture
    log_dir = str(tmp_path)
    jax.profiler.start_trace(log_dir)
    try:
        eng.run(pX, pD)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in data.planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"round", "round.prep", "client.stats", "client.wait", "merge",
            "solve"} <= names


def test_float_tier_fold_span_ends_on_its_aggregate():
    """Traced, each ``tier.fold`` of the float fold waits for its device
    add: the span holds one block on the merged statistics and ends after
    it, not on the add's enqueue."""
    from repro.core.solver import GramStats
    pX, pD = _parts([40, 9, 70, 33, 120, 5])
    tr = Tracer()
    blocked = []
    block = tr._block

    def recording(x):
        out = block(x)
        blocked.append((time.perf_counter() - tr.t_origin, x))
        return out
    tr._block = recording
    FederationEngine(wire="gram", trace=tr,
                     topology="fanout=2,tiers=3,exact=off").run(pX, pD)
    folds = tr.spans_named("tier.fold")
    assert {s.attrs["tier"] for s in folds} == {0, 1, 2}
    for sp in folds:
        inside = [x for t, x in blocked if sp.t0 <= t <= sp.t0 + sp.dur_s]
        assert len(inside) == 1 and isinstance(inside[0], GramStats)


@pytest.mark.parametrize("exact", ["off", "on"])
def test_tier_bucket_dispatch_counts_the_bytes_it_writes(exact):
    """``bucket.dispatch``'s ``bytes_out``: the float fold's bucket
    program writes one folded block of statistics, the exact codec's one
    per member (each is encoded before the ring sum)."""
    pX, pD = _parts([40, 9, 70, 33, 120, 5])
    m, c = pX[0].shape[1] + 1, pD[0].shape[1]
    tr = Tracer()
    FederationEngine(wire="gram", trace=tr,
                     topology=f"fanout=3,tiers=2,exact={exact}").run(pX, pD)
    one = 4 * (c * m * m + m * c)         # XLA backend: unpadded float32
    spans = tr.spans_named("bucket.dispatch")
    assert spans
    for sp in spans:
        per = 1 if exact == "off" else sp.attrs["n_clients"]
        assert sp.attrs["bytes_out"] == per * one
