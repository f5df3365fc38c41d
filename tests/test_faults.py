"""Fault-tolerance suite (ISSUE 8): injection, quarantine, quorum,
failover, and journaled recovery — with bit-exactness as the bar.

What "recovered" means here is never "close": every recovery path must
produce the SAME bits as a round that never failed over the same
cohort. The references are the exact surfaces of PRs 4-7:

* flat faulted rounds vs a clean engine run over the surviving shards
  (same gear, same fold order → bitwise),
* hierarchical faulted rounds vs the ledger's ``ExactAccumulator``
  over the committed clients' local statistics (the tiered exact fold
  bit-equals it regardless of tree shape — PR 7),
* masked rounds vs their exact twins, with the PR 5 spy harness
  asserting the coordinator still never sees plaintext while failing
  over and resuming from the journal.

Hypothesis is optional (guarded import, the test_wire_algebra idiom):
the deterministic versions always run; the fuzzing version randomizes
the quarantined subset, dtype, and wire.
"""
import os

import numpy as np
from repro.core.util import enable_x64 as jax_enable_x64
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dependency (pip install hypothesis)
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="optional dependency: property fuzzing "
    "needs hypothesis (pip install hypothesis)")

from contextlib import nullcontext

from repro.core import activations as acts
from repro.core.engine import FederationEngine
from repro.core.faults import (CoordinatorKilled, FaultPlan,
                               RoundJournal, UploadRejected,
                               empty_faults_report, inject_corrupt,
                               validate_upload)
from repro.core.ledger import ExactAccumulator, FederationLedger
from repro.core.scenario import Scenario
from repro.core.topology import (TierTree, Topology, failover,
                                 simulate_round)
from repro.core.wire import GramWire, get_wire
from repro.data import partition, synthetic
from repro.privacy.secagg import SecAggSession


def _parts(P=6, n=360, m=8, seed=1):
    spec = synthetic.DatasetSpec("toy", n, m, 2)
    X, y = synthetic.generate(spec, seed=seed)
    parts = partition.iid(X, y, P, seed=seed)
    return ([p[0] for p in parts],
            [np.asarray(acts.encode_labels(p[1], 2)) for p in parts])


def _x64(dtype):
    return jax_enable_x64() if jnp.dtype(dtype) == jnp.float64 \
        else nullcontext()


def _bit_equal(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _exact_ref_W(wire, pX, pD, ids, lam=1e-3):
    """From-scratch exact solve over exactly ``ids`` — the ledger's
    accumulator over their local statistics (what a hierarchical exact
    fold bit-equals for ANY tree shape; tests/test_topology.py)."""
    ids = sorted(ids)
    acc = ExactAccumulator(wire.local_stats(pX[ids[0]], pD[ids[0]]))
    for i in ids:
        acc.add(wire.local_stats(pX[i], pD[i]))
    return wire.solve(acc.snapshot(), lam)


# =================================================================
# FaultPlan grammar
# =================================================================
def test_plan_parse_roundtrip():
    p = FaultPlan.parse("faults=crash@upload:p3,corrupt@wire:p7,"
                        "aggfail@tier1:g0,timeout:p5,replay:p4,"
                        "flaky=0.1,seed=2")
    assert p.crash == (3,) and p.corrupt == (7,)
    assert p.timeout == (5,) and p.replay == (4,)
    assert p.aggfail == ((1, 0),)
    assert p.flaky == 0.1 and p.seed == 2
    assert p.active
    assert FaultPlan.parse(p) is p           # idempotent
    assert FaultPlan.parse(None) is None
    assert FaultPlan.parse("") is None
    assert FaultPlan.parse("none") is None


def test_plan_parse_ranges_and_defaults():
    p = FaultPlan.parse("crash@upload:p2-p4,timeout:0-1")
    assert p.crash == (2, 3, 4) and p.timeout == (0, 1)
    assert (p.maxretries, p.die) == (3, 0)
    assert not FaultPlan.parse("seed=7").active   # kv-only, no events


def test_plan_parse_names_offending_token():
    with pytest.raises(ValueError, match="bad faults item 'zap:p3'"):
        FaultPlan.parse("crash@upload:p1,zap:p3")
    with pytest.raises(ValueError, match="bad faults item 'fanout=4'"):
        FaultPlan.parse("fanout=4")             # topology key, not ours
    with pytest.raises(ValueError, match="bad faults value in 'flaky=x'"):
        FaultPlan.parse("flaky=x")
    with pytest.raises(ValueError, match="flaky=1.5"):
        FaultPlan.parse("flaky=1.5")
    with pytest.raises(ValueError, match="p4-p2"):
        FaultPlan.parse("crash@upload:p4-p2")


def test_plan_attempts_deterministic():
    p = FaultPlan.parse("crash@upload:p0,timeout:p1,flaky=0.3,"
                        "maxretries=2,seed=5")
    assert p.attempts(0) == (3, False)        # crash burns every retry
    n1, ok1 = p.attempts(1)
    assert n1 >= 2 and isinstance(ok1, bool)  # timeout forces a retry
    for cid in range(8):                      # draws are reproducible
        assert p.attempts(cid) == p.attempts(cid)
        assert p.backoff_delay(cid, 3) == p.backoff_delay(cid, 3)
    assert p.backoff_delay(2, 1) == 0.0       # first try free


# =================================================================
# Upload admission
# =================================================================
def test_validate_upload_rejects_each_class():
    w = GramWire()
    pX, pD = _parts(P=2)
    good = w.local_stats(pX[0], pD[0])
    seen = set()
    validate_upload(0, good, seen=seen)
    with pytest.raises(UploadRejected, match="client 0 rejected "
                       r"\(duplicate\)"):
        validate_upload(0, good, seen=seen)
    bad = inject_corrupt(good, seed=0)
    with pytest.raises(UploadRejected, match=r"\(non-finite\)"):
        validate_upload(1, bad, template=good)
    with pytest.raises(UploadRejected, match=r"\(dtype\)"):
        validate_upload(1, type(good)(
            G=np.asarray(good.G, np.float64), m_vec=good.m_vec,
            n=good.n), template=good)
    with pytest.raises(UploadRejected, match=r"\(shape\)"):
        validate_upload(1, type(good)(
            G=np.asarray(good.G)[0], m_vec=good.m_vec, n=good.n),
            template=good)
    huge = np.full((3, 2), np.int64(1) << 62, np.int64)
    with pytest.raises(UploadRejected, match=r"\(limb-headroom\)"):
        validate_upload(1, (huge,))
    err = UploadRejected(7, "non-finite", "leaf 0")
    assert (err.cid, err.reason) == (7, "non-finite")


def test_inject_corrupt_is_deterministic_nan():
    w = GramWire()
    pX, pD = _parts(P=1)
    stats = w.local_stats(pX[0], pD[0])
    a, b = inject_corrupt(stats, seed=3), inject_corrupt(stats, seed=3)
    assert any(not np.all(np.isfinite(np.asarray(lf)))
               for lf in a if np.issubdtype(
                   np.asarray(lf).dtype, np.floating))
    assert all(np.array_equal(np.asarray(x), np.asarray(y),
                              equal_nan=np.issubdtype(
                                  np.asarray(x).dtype, np.floating))
               for x, y in zip(a, b))


# =================================================================
# Layer 1: quarantine removes clients with NO trace in the fold
# =================================================================
@pytest.mark.parametrize("gear", ["loop", "batched"])
def test_quarantined_round_bitmatches_survivor_round(gear):
    """Acceptance core: under crash + corrupt + timeout + replay, the
    solved W bit-equals a clean run whose cohort never contained the
    quarantined clients."""
    pX, pD = _parts(P=6)
    kw = dict(batch_clients=True) if gear == "batched" else {}
    eng = FederationEngine(
        wire="gram",
        faults="crash@upload:p3,corrupt@wire:p1,timeout:p5,replay:p4",
        **kw)
    rep = eng.run(pX, pD)
    f = rep.faults
    assert f["quarantined"] == {1: "non-finite", 3: "crash"}
    assert f["replays_rejected"] == [4]
    assert 3 in f["retried"] and 5 in f["retried"]
    assert f["retry_s"] > 0 and f["retry_bytes"] > 0
    assert f["retry_j"] > 0
    survivors = [i for i in range(6) if i not in (1, 3)]
    clean = FederationEngine(wire="gram", **kw).run(
        [pX[i] for i in survivors], [pD[i] for i in survivors])
    assert _bit_equal(rep.W, clean.W)
    assert len(rep.roles.participants) == 4
    assert set(rep.roles.dropped) == {1, 3}


def test_fault_free_report_is_empty_but_present():
    pX, pD = _parts(P=3)
    rep = FederationEngine(wire="gram").run(pX, pD)
    assert rep.faults == empty_faults_report()
    # same stable schema even when the fault machinery DID engage
    rep2 = FederationEngine(wire="gram", faults="timeout:p1").run(pX, pD)
    assert set(rep2.faults) == set(empty_faults_report())
    assert set(rep2.faults["quorum"]) == \
        set(empty_faults_report()["quorum"])
    # the membership-fallout buckets are schema-stable AND distinct:
    # graceful departures (a list of ids) never alias post-hoc
    # evictions (a dict id -> reason) — regression for evict routing
    # through leave, which collapsed the two
    schema = empty_faults_report()
    assert "departed" in schema and "evicted" in schema
    assert schema["departed"] == [] and schema["evicted"] == {}


def test_report_departed_vs_evicted_distinct_on_ticks():
    """An event-driven tick's faults report files a graceful leave and
    a post-hoc eviction under different buckets."""
    pX, pD = _parts(P=4)
    eng = FederationEngine(wire="gram")
    led = FederationLedger("gram")
    reps = eng.run_events(pX, pD, "leave@t2:p2", ledger=led)
    assert reps[-1].faults["departed"] == [2]
    assert reps[-1].faults["evicted"] == {}
    led.evict(1, reason="non-finite")
    reps2 = eng.run_events(pX, pD, "join@t4:p2", ledger=led)
    assert reps2[-1].faults["evicted"] == {1: "non-finite"}
    # rejoin cleared client 2's departure; eviction of 1 still stands
    assert reps2[-1].faults["departed"] == []
    assert 1 not in led.departed and 2 not in led.evicted


def test_fault_determinism_same_plan_same_round():
    pX, pD = _parts(P=6)
    mk = lambda: FederationEngine(
        wire="gram", faults="flaky=0.4,maxretries=2,seed=11")
    a, b = mk().run(pX, pD), mk().run(pX, pD)
    assert a.faults == b.faults
    assert _bit_equal(a.W, b.W)


def test_quarantine_everyone_raises():
    pX, pD = _parts(P=2)
    eng = FederationEngine(wire="gram", faults="crash@upload:p0-p1")
    with pytest.raises(ValueError, match="quarantined every on-time"):
        eng.run(pX, pD)


# -------------------------------------- post-hoc eviction (ledger)
def test_ledger_evict_bitmatches_never_joined():
    """A client whose upload turned out bad AFTER folding is evicted by
    exact subtract: next solve bit-equals a ledger that never saw it."""
    pX, pD = _parts(P=5)
    led = FederationLedger("gram")
    stats = [led.wire.local_stats(pX[i], pD[i]) for i in range(5)]
    for i, st_ in enumerate(stats):
        led.join(i, st_)
    led.evict(2, reason="non-finite")
    assert led.evicted == {2: "non-finite"}
    # eviction is NOT a graceful departure: the evicted client must
    # never land in `departed` (downstream timeline/fault accounting
    # tells a quarantine from a deletion request by exactly this)
    assert 2 not in led.departed
    led.leave(1)
    assert led.departed == {1} and 1 not in led.evicted
    # both standing decisions still block auto-admission
    assert set(led.seen) == {0, 1, 2, 3, 4}
    clean = FederationLedger("gram")
    for i in (0, 1, 3, 4):
        clean.join(i, stats[i])
    clean.leave(1)
    assert _bit_equal(led.solve(), clean.solve())
    with pytest.raises(ValueError, match="evict of client 2"):
        led.evict(2)                        # can't evict twice


@pytest.mark.parametrize("wire_name", ["gram", "svd"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_ledger_evict_subset_bitmatch_all_wires(wire_name, dtype):
    """Quarantine-then-subtract of a fixed subset bit-matches a solve
    that never included them — exact path (gram) and re-merge fallback
    (svd, sorted-order merge_tree) alike, on both dtypes."""
    with _x64(dtype):
        led = FederationLedger(wire_name, dtype=dtype)
        pX, pD = _parts(P=5)
        stats = [led.wire.local_stats(pX[i], pD[i]) for i in range(5)]
        for i, st_ in enumerate(stats):
            led.join(i, st_)
        for i in (1, 4):
            led.evict(i)
        clean = FederationLedger(wire_name, dtype=dtype)
        for i in (0, 2, 3):
            clean.join(i, stats[i])
        assert _bit_equal(led.solve(), clean.solve())


if HAVE_HYPOTHESIS:

    @needs_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(P=st.integers(3, 6), bits=st.integers(1, 30),
           seed=st.integers(0, 1000), f64=st.booleans(),
           wire_name=st.sampled_from(["gram", "svd"]))
    def test_property_evict_any_subset_bitmatch(P, bits, seed, f64,
                                                wire_name):
        """ANY proper quarantined subset, any dtype, both wires: the
        post-eviction solve bit-equals never-having-folded them."""
        evictees = {i for i in range(P) if bits >> i & 1}
        survivors = [i for i in range(P) if i not in evictees]
        if not survivors or not evictees:
            return
        dtype = jnp.float64 if f64 else jnp.float32
        with _x64(dtype):
            led = FederationLedger(wire_name, dtype=dtype)
            pX, pD = _parts(P=P, n=60 * P, seed=seed)
            stats = [led.wire.local_stats(pX[i], pD[i])
                     for i in range(P)]
            for i, st_ in enumerate(stats):
                led.join(i, st_)
            for i in sorted(evictees):
                led.evict(i)
            clean = FederationLedger(wire_name, dtype=dtype)
            for i in survivors:
                clean.join(i, stats[i])
            assert _bit_equal(led.solve(), clean.solve())


# =================================================================
# Layer 2: quorum commit
# =================================================================
@pytest.mark.parametrize("gear", ["loop", "batched"])
def test_quorum_commit_bitmatches_committed_cohort(gear):
    """quorum=0.6: W_first (the committed model) bit-equals a clean run
    whose cohort is exactly the committed prefix; the deferred tail
    still reaches the final W."""
    pX, pD = _parts(P=6)
    kw = dict(batch_clients=True) if gear == "batched" else {}
    sc = Scenario(straggler_frac=0.34, straggler_delay=5.0, seed=0)
    eng = FederationEngine(wire="gram", quorum=0.6, scenario=sc, **kw)
    rep = eng.run(pX, pD)
    qr = rep.faults["quorum"]
    assert qr["target"] == 0.6
    assert qr["committed_frac"] >= 0.6
    assert qr["n_deferred"] > 0 and rep.W_first is not None
    assert sorted(qr["committed"] + qr["deferred"]) == list(range(6))
    clean = FederationEngine(wire="gram", scenario=sc, **kw).run(
        [pX[i] for i in qr["committed"]],
        [pD[i] for i in qr["committed"]])
    assert _bit_equal(rep.W_first, clean.W)


def test_quorum_one_commits_everyone():
    pX, pD = _parts(P=4)
    rep = FederationEngine(wire="gram", quorum=1.0,
                           faults="timeout:p0").run(pX, pD)
    qr = rep.faults["quorum"]
    assert qr["n_deferred"] == 0 and qr["committed_frac"] == 1.0


def test_quorum_out_of_range_rejected():
    for q in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError, match="quorum"):
            FederationEngine(wire="gram", quorum=q)


# =================================================================
# Layer 3: retry pricing + tier-aggregator failover
# =================================================================
def test_simulate_round_prices_retries_and_refolds():
    tree = TierTree.build(4, fanout=2, tiers=2)
    topo = Topology(fanout=2, tiers=2, jitter=0.0)
    ready = {i: 0.0 for i in range(4)}
    sizes = {i: 1000 for i in range(4)}
    base = simulate_round(tree, topo, client_ready=ready,
                          client_bytes=sizes, agg_bytes=500)
    hot = simulate_round(tree, topo, client_ready=ready,
                         client_bytes=sizes, agg_bytes=500,
                         retries={0: 2}, refolds=1)
    assert base["retry_bytes"] == 0 and base["retry_j"] == 0.0
    # 2 client resends on the LAN tier + 1 refolded WAN aggregate
    assert hot["retry_bytes"] == 2 * 1000 + 500
    assert hot["bytes_tiered"] == base["bytes_tiered"] + 2500
    assert hot["retry_j"] > 0
    assert hot["sim_wall_tiered"] > base["sim_wall_tiered"]
    assert hot["bytes_flat"] == base["bytes_flat"] + 2000


def test_failover_rebuilds_valid_tree():
    tree = TierTree.build(9, fanout=3, tiers=2)
    new, moved = failover(tree, 0, 1)
    assert moved == 3
    assert new.levels[0][1] == ()
    assert set(new.levels[0][2]) == {6, 7, 8, 3, 4, 5}
    assert new.n_clients == 9
    with pytest.raises(ValueError, match="aggfail@tier0:g9"):
        failover(tree, 0, 9)
    with pytest.raises(ValueError, match="aggfail@tier5:g0"):
        failover(tree, 5, 0)
    root_only = TierTree.build(3, fanout=4, tiers=1)
    with pytest.raises(ValueError, match="no[\\s\\S]*sibling"):
        failover(root_only, 0, 0)


def test_aggfail_failover_bitmatches_clean_topology():
    """A dead tier-0 aggregator's children are adopted by a sibling;
    the re-tiered exact fold solves to the bit-identical W."""
    pX, pD = _parts(P=9)
    topo = "fanout=3,tiers=2"
    rep = FederationEngine(wire="gram", topology=topo,
                           faults="aggfail@tier0:g1").run(pX, pD)
    clean = FederationEngine(wire="gram", topology=topo).run(pX, pD)
    assert rep.faults["failed_over"] == ["tier0:g1"]
    assert _bit_equal(rep.W, clean.W)
    assert _bit_equal(rep.W, _exact_ref_W(clean.wire if hasattr(
        clean, "wire") else get_wire("gram"), pX, pD, range(9)))
    # refolded uplinks are priced
    assert rep.faults["retry_bytes"] > 0


def test_aggfail_masked_bitmatches_and_spy(monkeypatch):
    """Failover under secagg: bit-identical to the exact clean round
    AND the coordinator still never merges/solves plaintext uploads."""
    pX, pD = _parts(P=9)
    total_n = sum(x.shape[0] for x in pX)
    merges, solves = [], []
    real_merge, real_solve = GramWire.merge, GramWire.solve
    monkeypatch.setattr(
        GramWire, "merge",
        lambda self, a, b: (merges.append((a, b)),
                            real_merge(self, a, b))[1])
    monkeypatch.setattr(
        GramWire, "solve",
        lambda self, stats, lam=1e-3: (solves.append(stats),
                                       real_solve(self, stats, lam))[1])
    rep = FederationEngine(wire="gram", privacy="secagg",
                           topology="fanout=3,tiers=2",
                           faults="aggfail@tier0:g0").run(pX, pD)
    assert not merges, "coordinator merged unmasked client statistics"
    assert len(solves) == 1
    assert int(np.asarray(solves[0].n)) == total_n
    monkeypatch.undo()
    clean = FederationEngine(wire="gram",
                             topology="fanout=3,tiers=2").run(pX, pD)
    assert _bit_equal(rep.W, clean.W)


def test_aggfail_without_topology_rejected():
    with pytest.raises(ValueError, match="aggfail@tier"):
        FederationEngine(wire="gram", faults="aggfail@tier0:g1")


def test_masked_replay_rejected_structurally():
    """The masked path's replay defence is in the ring algebra itself:
    merging an aggregate with an upload whose id it already contains
    refuses — a replayed masked packet cannot double-fold."""
    pX, pD = _parts(P=3)
    w = GramWire()
    sess = SecAggSession(3, seed=0)
    ups = [sess.mask_upload(p, w.local_stats(pX[p], pD[p]))
           for p in range(3)]
    agg = sess.merge_signed(ups[0], ups[1])
    with pytest.raises(ValueError, match=r"overlapping client sets \[1\]"):
        sess.merge_signed(agg, ups[1])       # replayed packet


# =================================================================
# Layer 4: round journal (WAL) + bit-exact resume
# =================================================================
def test_journal_unit_roundtrip(tmp_path):
    path = str(tmp_path / "wal.npz")
    j = RoundJournal(path, mode="exact")
    assert j.lookup("on-e0") is None
    limbs = np.arange(12, dtype=np.int64).reshape(6, 2)
    j.commit("on-e0", limbs)
    j.commit("on-e1", limbs * 2, ids=frozenset((3, 1)))
    assert j.commits == 2 and len(j) == 2
    j2 = RoundJournal(path, mode="exact")
    assert j2.commits == 0                   # resumed commits are free
    got, ids = j2.lookup("on-e0")
    assert _bit_equal(got, limbs) and ids is None
    got2, ids2 = j2.lookup("on-e1")
    assert _bit_equal(got2, limbs * 2) and ids2 == frozenset((1, 3))
    with pytest.raises(ValueError, match="refusing to mix digit"):
        RoundJournal(path, mode="masked")
    with pytest.raises(ValueError, match="may not contain"):
        j.commit("a/b", limbs)


@pytest.mark.parametrize("privacy", [None, "secagg"])
def test_journal_kill_and_resume_bitmatch(tmp_path, privacy):
    """Coordinator killed after the first journal commit resumes from
    the WAL and finishes bit-identically to an uninterrupted round —
    on the exact codec and the masked codec alike."""
    pX, pD = _parts(P=9)
    path = str(tmp_path / f"wal_{privacy}.npz")
    topo = "fanout=3,tiers=2"
    kw = dict(wire="gram", topology=topo, privacy=privacy)
    with pytest.raises(CoordinatorKilled) as exc:
        FederationEngine(journal=path, faults="die=1", **kw).run(pX, pD)
    assert exc.value.commits == 1 and exc.value.path == path
    assert os.path.exists(path)              # the commit is durable
    rep = FederationEngine(journal=path, **kw).run(pX, pD)
    assert rep.faults["recovered"] >= 1
    clean = FederationEngine(**kw).run(pX, pD)
    assert _bit_equal(rep.W, clean.W)


def test_journal_guard_rails(tmp_path):
    path = str(tmp_path / "wal.npz")
    with pytest.raises(ValueError, match="needs a hierarchical round"):
        FederationEngine(wire="gram", journal=path)
    with pytest.raises(ValueError, match="no per-tier commit point"):
        FederationEngine(wire="gram", transport="mesh",
                         topology="fanout=4,tiers=2", journal=path)
    eng = FederationEngine(wire="svd", journal=path,
                           topology="fanout=4,tiers=2,exact=off")
    pX, pD = _parts(P=4)
    with pytest.raises(ValueError, match="no bit-stable digits"):
        eng.run(pX, pD)


def test_mesh_flat_faults_rejected():
    with pytest.raises(ValueError, match="all-or-nothing"):
        FederationEngine(wire="gram", transport="mesh",
                         faults="timeout:p0")
    with pytest.raises(ValueError, match="all-or-nothing"):
        FederationEngine(wire="gram", transport="mesh", quorum=0.5)


def test_run_events_rejects_fault_machinery():
    pX, pD = _parts(P=3)
    eng = FederationEngine(wire="gram", faults="timeout:p0")
    with pytest.raises(ValueError, match="one-shot rounds"):
        eng.run_events(pX, pD, "join@t1:p0")


# ------------------------------------------- satellite (b): run() errors
def test_run_names_shard_count_mismatch():
    pX, pD = _parts(P=3)
    with pytest.raises(ValueError, match="parts_X has 3 client shards "
                       "but parts_d has 2"):
        FederationEngine(wire="gram").run(pX, pD[:2])


def test_run_names_rowcount_mismatch():
    pX, pD = _parts(P=3)
    pD[1] = pD[1][:-5]
    with pytest.raises(ValueError,
                       match="client 1: X has .* rows but d has"):
        FederationEngine(wire="gram").run(pX, pD)


# =================================================================
# Acceptance: the whole plan at once, kill included
# =================================================================
@pytest.mark.parametrize("privacy", [None, "secagg"])
def test_acceptance_full_plan_kill_resume_bitmatch(tmp_path, privacy):
    """ISSUE 8 acceptance: crash + corrupt + timeout + aggfail + quorum
    + journaled kill/resume in ONE round; the quorum-committed W
    bit-equals the from-scratch exact solve over exactly the committed
    cohort — on the plain and masked paths."""
    P = 9
    pX, pD = _parts(P=P)
    path = str(tmp_path / f"wal_{privacy}.npz")
    plan = ("crash@upload:p3,corrupt@wire:p1,timeout:p5,"
            "aggfail@tier0:g2,seed=0")
    kw = dict(wire="gram", topology="fanout=3,tiers=2",
              quorum=0.7, journal=path, privacy=privacy)
    with pytest.raises(CoordinatorKilled):
        FederationEngine(faults=plan + ",die=1", **kw).run(pX, pD)
    rep = FederationEngine(faults=plan, **kw).run(pX, pD)
    f = rep.faults
    assert f["quarantined"] == {1: "non-finite", 3: "crash"}
    assert f["failed_over"] == ["tier0:g2"]
    assert f["recovered"] >= 1
    committed = f["quorum"]["committed"]
    assert 0 < len(committed) <= P - 2
    assert not {1, 3} & set(committed)
    wire = get_wire("gram")
    W_committed = rep.W_first if f["quorum"]["n_deferred"] else rep.W
    assert _bit_equal(W_committed,
                      _exact_ref_W(wire, pX, pD, committed))
    # the final W folds committed + deferred — everyone but quarantined
    assert _bit_equal(
        rep.W, _exact_ref_W(wire, pX, pD,
                            [i for i in range(P) if i not in (1, 3)]))
