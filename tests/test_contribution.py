"""Contribution-scored selection suite (ISSUE 9): exact LOO scores,
exact small-coalition Shapley, and budget-greedy client selection.

The bar is the repo's usual one — bitwise, not close:

* the leave-one-out model ``W_{-i}`` must bit-match a from-scratch
  solve over the cohort minus ``i`` (gram wire, f32 and f64, under
  dropout and under secure aggregation),
* scoring must leave the ledger bit-identical (score-then-restore
  round-trip; the hypothesis fuzz randomizes cohort/dtype/wire),
* a ``budget:inf`` selection round must bit-match the unselected
  round's ``W``, and a ``topk`` round's committed ``W`` must bit-match
  a from-scratch engine run over exactly the selected shards,
* under secagg the spy asserts the base wire still never merges
  host-side and never solves a decoded singleton aggregate.

Hypothesis is optional (guarded import, the test_faults idiom): the
deterministic versions always run.
"""
import math
from contextlib import nullcontext

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

from repro.core import activations as acts
from repro.core.contribution import (SHAPLEY_MAX_CLIENTS, SelectSpec,
                                     accuracy_frontier, greedy_select,
                                     loo_scores, shapley_scores)
from repro.core.engine import FederationEngine
from repro.core.ledger import FederationLedger
from repro.core.scenario import Scenario
from repro.core.wire import GramWire, get_wire
from repro.data import partition, synthetic
from repro.privacy import MaskedWire
from repro.privacy.secagg import SecAggSession


def _parts(P=5, n=300, m=6, seed=3):
    spec = synthetic.DatasetSpec("toy", n, m, 2)
    X, y = synthetic.generate(spec, seed=seed)
    parts = partition.iid(X, y, P, seed=seed)
    return ([p[0] for p in parts],
            [np.asarray(acts.encode_labels(p[1], 2)) for p in parts])


def _eval_set(n=120, m=6, seed=99):
    spec = synthetic.DatasetSpec("toy", n, m, 2)
    return synthetic.generate(spec, seed=seed)


def _x64(dtype):
    return jax_enable_x64() if jnp.dtype(dtype) == jnp.float64 \
        else nullcontext()


def _bit_equal(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


def _ledger(pX, pD, wire="gram", skip=(), dtype=jnp.float32):
    w = get_wire(wire, dtype=dtype)
    led = FederationLedger(w)
    for i in range(len(pX)):
        if i not in skip:
            led.join(i, w.local_stats(pX[i], pD[i]))
    return led


# ------------------------------------------------------------ spec parse
def test_selectspec_parse_valid():
    assert SelectSpec.parse(None) is None
    assert SelectSpec.parse("") is None
    assert SelectSpec.parse("none") is None
    s = SelectSpec.parse("topk:10")
    assert (s.kind, s.k) == ("topk", 10)
    s = SelectSpec.parse("budget:0.05")
    assert (s.kind, s.budget_j, s.budget_bytes) == ("budget", 0.05, None)
    s = SelectSpec.parse("budget:4096B")
    assert (s.kind, s.budget_j, s.budget_bytes) == ("budget", None, 4096)
    s = SelectSpec.parse("budget:inf")
    assert s.kind == "budget" and math.isinf(s.budget_j)
    assert SelectSpec.parse("frontier").kind == "frontier"
    # idempotent: an already-parsed spec passes through
    assert SelectSpec.parse(s) is s


@pytest.mark.parametrize("bad,msg", [
    ("topk:x", "topk:x"), ("topk:0", "K must be >= 1"),
    ("topk", "needs a value"), ("budget:", "needs a value"),
    ("budget:-1", "must be > 0"), ("budget:abcB", "needs a number"),
    ("frontier:3", "takes no value"), ("karma:2", "karma:2"),
])
def test_selectspec_parse_errors_quote_token(bad, msg):
    with pytest.raises(ValueError, match="bad select spec") as ei:
        SelectSpec.parse(bad)
    assert msg in str(ei.value)


def test_scenario_select_axis_validates_eagerly():
    sc = Scenario.parse("dropout=0.2,select=topk:3")
    assert sc.select == "topk:3" and sc.dropout == 0.2
    with pytest.raises(ValueError, match="bad select spec 'topk:'"):
        Scenario.parse("select=topk:")


# ------------------------------------------------------------ LOO exact
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_loo_bitmatches_scratch(dtype):
    """Acceptance: W_{-i} from the ledger downdate bit-equals a
    from-scratch fold over the cohort minus i — every client, gram
    wire, f32 and f64 — and scoring leaves the ledger bit-identical."""
    with _x64(dtype):
        pX, pD = _parts()
        Xe, ye = _eval_set()
        led = _ledger(pX, pD, dtype=dtype)
        W_before = np.asarray(led.solve())
        for i in range(len(pX)):
            W_loo = led.wire.solve(led.peek_without(i), led.lam)
            scratch = _ledger(pX, pD, skip={i}, dtype=dtype)
            assert _bit_equal(W_loo, scratch.solve()), f"client {i}"
        rep = loo_scores(led, Xe, ye)
        assert len(rep.scores) == len(pX)
        # score-then-restore round-trip: state bit-identical
        assert _bit_equal(led.solve(), W_before)
        assert all(s.d_joules > 0 and s.upload_bytes > 0
                   for s in rep.scores)


def test_loo_exact_under_dropout_and_secagg():
    """Acceptance: the masked ring downdate yields the SAME LOO
    accuracies as an exact plaintext ledger over the same surviving
    cohort (client 1 dropped)."""
    P = 4
    pX, pD = _parts(P=P)
    Xe, ye = _eval_set()
    survivors = [i for i in range(P) if i != 1]
    sess = SecAggSession(P, seed=0)
    mled = FederationLedger(MaskedWire(GramWire(), sess))
    for i in survivors:
        mled.join(i, mled.wire.upload(i, pX[i], pD[i]))
    exact = _ledger(pX, pD, skip={1})
    mrep = loo_scores(mled, Xe, ye)
    erep = loo_scores(exact, Xe, ye)
    assert mrep.acc_full == erep.acc_full
    for ms, es in zip(mrep.scores, erep.scores):
        assert ms.cid == es.cid
        assert ms.acc_loo == es.acc_loo and ms.d_acc == es.d_acc


@pytest.mark.parametrize("wire", ["gram", "svd"])
def test_score_then_restore_roundtrip(wire):
    """Deterministic round-trip on both wires: a full scoring pass is
    an exact no-op on ledger state (the svd wire exercises the
    non-subtractable re-merge path of peek_without)."""
    pX, pD = _parts()
    Xe, ye = _eval_set()
    led = _ledger(pX, pD, wire=wire)
    W_before = np.asarray(led.solve())
    n_events = led.n_events
    loo_scores(led, Xe, ye)
    assert led.n_events == n_events
    assert _bit_equal(led.solve(), W_before)


if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=12, deadline=None)
    @given(P=st.integers(2, 7), seed=st.integers(0, 50),
           wire=st.sampled_from(["gram", "svd"]),
           f64=st.booleans())
    def test_property_scoring_is_exact_noop(P, seed, wire, f64):
        """Property (hypothesis): for any cohort size, seed, wire, and
        dtype, score-then-restore leaves the ledger bit-identical AND
        greedy selection under budget=inf keeps everyone."""
        dtype = jnp.float64 if f64 else jnp.float32
        with _x64(dtype):
            pX, pD = _parts(P=P, n=60 * P, seed=seed)
            Xe, ye = _eval_set()
            led = _ledger(pX, pD, wire=wire, dtype=dtype)
            W_before = np.asarray(led.solve())
            rep = loo_scores(led, Xe, ye)
            assert _bit_equal(led.solve(), W_before)
            sel = greedy_select(rep, SelectSpec.parse("budget:inf"))
            assert sel.selected == tuple(range(P))


# ----------------------------------------------------------- selection
def test_budget_inf_bitmatches_unselected_round():
    """Acceptance: selection with an infinite budget admits everyone
    and the committed W bit-matches the round with no select axis."""
    pX, pD = _parts()
    Xe, ye = _eval_set()
    plain = FederationEngine(wire="gram").run(pX, pD)
    sel = FederationEngine(
        wire="gram", scenario=Scenario.parse("select=budget:inf"),
        select_eval=(Xe, ye)).run(pX, pD)
    assert _bit_equal(plain.W, sel.W)
    c = sel.contribution
    assert c["n_selected"] == len(pX) and c["budget_j"] is None
    assert plain.contribution is None


@pytest.mark.parametrize("gear", ["loop", "batched", "fused"])
def test_topk_commit_bitmatches_scratch(gear):
    """Acceptance: the selected-cohort committed W bit-matches a
    from-scratch engine run over exactly the selected shards (every
    in-process gear; fused degrades to the stats-materializing path)."""
    pX, pD = _parts()
    Xe, ye = _eval_set()
    kw = {"batched": dict(batch_clients=True),
          "fused": dict(fused=True)}.get(gear, {})
    eng = FederationEngine(
        wire="gram", scenario=Scenario.parse("select=topk:3"),
        select_eval=(Xe, ye), **kw)
    rep = eng.run(pX, pD)
    picked = rep.contribution["selected"]
    assert len(picked) == 3
    # the fused gear degrades to the stats-materializing (batched)
    # commit path when selection is active — per-client statistics
    # must exist to be scored — so its reference is the batched run
    ref_kw = dict(batch_clients=True) if gear == "fused" else kw
    scratch = FederationEngine(wire="gram", **ref_kw).run(
        [pX[i] for i in picked], [pD[i] for i in picked])
    assert _bit_equal(rep.W, scratch.W)
    # unselected clients moved to dropped, selection order is recorded
    assert set(rep.roles.dropped) == set(range(len(pX))) - set(picked)
    assert sorted(rep.contribution["order"]) == list(range(len(pX)))


def test_byte_budget_bounds_spend():
    pX, pD = _parts()
    Xe, ye = _eval_set()
    led = _ledger(pX, pD)
    rep = loo_scores(led, Xe, ye)
    one = rep.scores[0].upload_bytes     # homogeneous shards
    sel = greedy_select(rep, SelectSpec.parse(f"budget:{2 * one}B"))
    assert len(sel.selected) == 2 and sel.spent_bytes <= 2 * one
    # the floor admits the top-ranked client even over budget
    tiny = greedy_select(rep, SelectSpec.parse("budget:1B"))
    assert len(tiny.selected) == 1
    assert tiny.selected == (rep.ranked()[0].cid,)
    assert tiny.spent_bytes > 1          # overrun is visible


def test_frontier_monotone_and_commits_everyone():
    pX, pD = _parts()
    Xe, ye = _eval_set()
    eng = FederationEngine(
        wire="gram", scenario=Scenario.parse("select=frontier"),
        select_eval=(Xe, ye))
    rep = eng.run(pX, pD)
    fr = rep.contribution["frontier"]
    assert [p["k"] for p in fr] == list(range(1, len(pX) + 1))
    for a, b in zip(fr, fr[1:]):
        assert b["cum_j"] >= a["cum_j"]
        assert b["cum_bytes"] >= a["cum_bytes"]
    # the full-prefix point IS the committed full-cohort model
    assert fr[-1]["accuracy"] == rep.contribution["acc_full"]
    assert _bit_equal(rep.W, FederationEngine(wire="gram").run(pX, pD).W)


def test_selection_composes_with_dropout_and_topology():
    """Tiered fold over the selected cohort still bit-matches an exact
    flat ledger over exactly those clients' statistics."""
    P = 8
    pX, pD = _parts(P=P, seed=7)
    Xe, ye = _eval_set()
    eng = FederationEngine(
        wire="gram", topology="tiers=2,fanout=3",
        scenario=Scenario.parse("dropout=0.25,select=topk:4"),
        select_eval=(Xe, ye))
    rep = eng.run(pX, pD)
    picked = rep.contribution["selected"]
    assert len(picked) == 4
    assert not set(picked) & set(rep.roles.dropped)
    ref = _ledger(pX, pD, skip=set(range(P)) - set(picked))
    assert _bit_equal(rep.W, ref.solve())


def test_selection_composes_with_faults_and_quorum():
    pX, pD = _parts(P=6)
    Xe, ye = _eval_set()
    rep = FederationEngine(
        wire="gram", faults="crash@upload:p0", quorum=0.5,
        scenario=Scenario.parse("select=topk:3"),
        select_eval=(Xe, ye)).run(pX, pD)
    # the crashed client was quarantined before scoring: it is neither
    # scored nor selectable
    scored = {s["cid"] for s in rep.contribution["scores"]}
    assert 0 not in scored and 0 in rep.faults["quarantined"]
    assert len(rep.contribution["selected"]) == 3


# ------------------------------------------------------------- privacy
def test_select_secagg_spy_no_plaintext(monkeypatch):
    """Acceptance (spy): during a masked selection round the base
    wire's merge is never called host-side and every solve receives a
    decoded aggregate of >= 2 clients — never a singleton (which would
    be one client's plaintext statistics)."""
    pX, pD = _parts()
    shard_n = sorted(int(x.shape[0]) for x in pX)
    min_pair = shard_n[0] + shard_n[1]
    Xe, ye = _eval_set()
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
    rep = FederationEngine(
        wire="gram", privacy="secagg",
        scenario=Scenario.parse("select=budget:inf"),
        select_eval=(Xe, ye)).run(pX, pD)
    assert not merges, "coordinator merged unmasked client statistics"
    # full solve + one LOO solve per client (+ the committed solve) —
    # all on aggregates of >= 2 clients' samples
    assert len(solves) >= len(pX) + 1
    for st_ in solves:
        assert int(np.asarray(st_.n)) >= min_pair
    assert rep.W is not None
    assert rep.contribution["n_selected"] == len(pX)


def test_select_secagg_floor_is_two():
    """Under secagg even a starvation budget keeps >= 2 clients: a
    1-client commit would decode that client's plaintext."""
    pX, pD = _parts()
    Xe, ye = _eval_set()
    rep = FederationEngine(
        wire="gram", privacy="secagg",
        scenario=Scenario.parse("select=budget:1B"),
        select_eval=(Xe, ye)).run(pX, pD)
    assert rep.contribution["n_selected"] == 2
    # frontier under secagg never solves the k=1 prefix
    rep2 = FederationEngine(
        wire="gram", privacy="secagg",
        scenario=Scenario.parse("select=frontier"),
        select_eval=(Xe, ye)).run(pX, pD)
    assert rep2.contribution["frontier"][0]["k"] == 2


# -------------------------------------------------------------- Shapley
def test_shapley_efficiency_and_loo_consistency():
    """Exact Shapley values satisfy efficiency: Σφ_i = v(N) − v(∅).
    On a 2-client cohort the marginals reduce to LOO quantities."""
    pX, pD = _parts(P=4)
    Xe, ye = _eval_set()
    led = _ledger(pX, pD)
    phi = shapley_scores(led, Xe, ye)
    assert sorted(phi) == [0, 1, 2, 3]
    W0 = np.zeros_like(np.asarray(led.solve()))
    from repro.core.contribution import _accuracy
    v_empty = _accuracy(led.wire, W0, Xe, ye)
    v_full = loo_scores(led, Xe, ye).acc_full
    assert math.isclose(sum(phi.values()), v_full - v_empty,
                        abs_tol=1e-12)
    # scoring left the ledger intact
    assert led.clients == (0, 1, 2, 3)


def test_shapley_tractability_bound_and_masked_refusal():
    pX, pD = _parts(P=2)
    Xe, ye = _eval_set()
    led = _ledger(pX, pD)
    with pytest.raises(ValueError, match="tractability bound"):
        shapley_scores(led, Xe, ye, max_clients=1)
    assert SHAPLEY_MAX_CLIENTS == 16
    sess = SecAggSession(2, seed=0)
    mled = FederationLedger(MaskedWire(GramWire(), sess))
    for i in range(2):
        mled.join(i, mled.wire.upload(i, pX[i], pD[i]))
    with pytest.raises(NotImplementedError, match="plaintext"):
        shapley_scores(mled, Xe, ye)


# -------------------------------------------------------------- errors
def test_select_without_eval_data_raises():
    pX, pD = _parts(P=2)
    eng = FederationEngine(wire="gram",
                           scenario=Scenario.parse("select=topk:1"))
    with pytest.raises(ValueError, match="select_eval"):
        eng.run(pX, pD)


def test_select_flat_mesh_refused():
    with pytest.raises(ValueError, match="per-client upload"):
        FederationEngine(wire="gram", transport="mesh",
                         scenario=Scenario.parse("select=topk:1"),
                         select_eval=_eval_set())


def test_select_run_events_refused():
    pX, pD = _parts(P=2)
    eng = FederationEngine(wire="gram",
                           scenario=Scenario.parse("select=topk:1"),
                           select_eval=_eval_set())
    with pytest.raises(ValueError, match="one-shot rounds"):
        eng.run_events(pX, pD, "none")
