"""Privacy subsystem coverage (ISSUE 5 acceptance).

* mask cancellation is BITWISE for any client order/permutation and any
  dropout subset — the decoded masked aggregate equals the
  ExactAccumulator snapshot of the same unmasked statistics
  (hypothesis-fuzzed when installed, deterministic fallback always),
* with ``privacy=secagg`` the engine's solved ``W`` bit-matches the
  unmasked exact-aggregation (ledger) solve — one-shot, under a
  dropout+late-join scenario, and through ``run_events`` leave ticks
  (exact unlearning under masking) — and a spy on the base wire
  asserts no single client's unmasked statistics ever reach a
  coordinator-side merge/solve,
* DP: noise is zero-mean with the calibrated σ, the exact Gaussian
  calibration is sufficient AND tight, the accountant rejects invalid
  (ε, δ), and ε=∞ bit-matches the clipped non-noised baseline,
* the svd wire refuses masking with a real NotImplementedError (the
  full 24-cell wire × transport × privacy conformance matrix lives in
  tests/test_privacy_matrix.py; the jitted limb-algebra properties in
  tests/test_limbs.py),
* the communication-energy satellite: ``CostModel`` uplink term
  monotonicity in P, and federated-vs-centralized crossover under it.
"""
import math
from contextlib import nullcontext

import numpy as np
import pytest
from repro.core.util import enable_x64 as jax_enable_x64

from repro.core import activations as acts
from repro.core.engine import FederationEngine
from repro.core.ledger import ExactAccumulator, FederationLedger
from repro.core.scenario import Scenario
from repro.core.wire import GramWire, SvdWire
from repro.energy import CostModel, J_PER_BYTE, uplink_joules
from repro.privacy import (DPAccountant, MaskedWire, PrivacyPolicy,
                           SecAggSession, calibrate_sigma, clip_rows,
                           gaussian_delta, noise_stats, sensitivity,
                           validate_budget)
from repro.privacy.secagg import MaskedStats

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dependency (pip install hypothesis)
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="optional dependency: property fuzzing "
    "needs hypothesis (pip install hypothesis)")


def _client_stats(P=5, n=40, m=5, c=2, seed=0, dtype=np.float32,
                  scale=1.0):
    rng = np.random.default_rng(seed)
    wire = GramWire(dtype=dtype)
    out = []
    for p in range(P):
        X = rng.normal(size=(n + 3 * p, m)).astype(dtype) * scale
        D = np.asarray(acts.encode_labels(
            rng.integers(0, c, size=n + 3 * p), c), dtype)
        out.append(wire.local_stats(X, D))
    return wire, out


def _exact_ref(stats_list):
    acc = ExactAccumulator(stats_list[0])
    for st in stats_list:
        acc.add(st)
    return acc.snapshot()


def _assert_tree_equal(a, b, msg=""):
    import jax
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), msg


def _parts(P=8, n=600, m=12, seed=0):
    from repro.data import partition, synthetic
    spec = synthetic.DatasetSpec("toy", n, m, 2)
    X, y = synthetic.generate(spec, seed=seed)
    parts = partition.iid(X, y, P, seed=seed)
    return ([p[0] for p in parts],
            [np.asarray(acts.encode_labels(p[1], 2)) for p in parts])


# ----------------------------------------------- mask cancellation
def test_mask_cancellation_bitwise_any_order():
    """Acceptance: the decoded masked sum over ALL clients, merged in
    any order, bit-equals the exact unmasked aggregate."""
    wire, stats = _client_stats(P=5)
    sess = SecAggSession(5, seed=3)
    ups = [sess.mask_upload(p, stats[p]) for p in range(5)]
    ref = _exact_ref(stats)
    rng = np.random.default_rng(0)
    for _ in range(4):
        order = rng.permutation(5)
        agg = ups[order[0]]
        for i in order[1:]:
            agg = sess.merge_signed(agg, ups[i])
        _assert_tree_equal(sess.unmask(agg), ref, f"order {order}")


def test_mask_cancellation_bitwise_any_dropout_subset():
    """Every nonempty participant subset decodes (after boundary-pad
    recovery) to the exact sum of exactly its members' statistics."""
    P = 4
    wire, stats = _client_stats(P=P, seed=1)
    sess = SecAggSession(P, seed=9)
    ups = [sess.mask_upload(p, stats[p]) for p in range(P)]
    for bits in range(1, 1 << P):
        S = [i for i in range(P) if bits >> i & 1]
        agg = ups[S[0]]
        for i in S[1:]:
            agg = sess.merge_signed(agg, ups[i])
        _assert_tree_equal(sess.unmask(agg),
                           _exact_ref([stats[i] for i in S]),
                           f"subset {S}")


def test_leave_downdate_equals_survivor_sum():
    """Ring subtract of a departed client's upload + boundary recovery
    == the survivors-only aggregate, bit for bit."""
    wire, stats = _client_stats(P=5, seed=2)
    sess = SecAggSession(5, seed=5)
    ups = [sess.mask_upload(p, stats[p]) for p in range(5)]
    agg = ups[0]
    for u in ups[1:]:
        agg = sess.merge_signed(agg, u)
    agg = sess.merge_signed(agg, ups[2], -1)        # client 2 leaves
    _assert_tree_equal(sess.unmask(agg),
                       _exact_ref([stats[i] for i in (0, 1, 3, 4)]))


def test_single_upload_is_masked_and_roundtrips():
    wire, stats = _client_stats(P=3)
    sess = SecAggSession(3, seed=0)
    up = sess.mask_upload(0, stats[0])
    enc = sess.encode(stats[0], 0)
    # the published limbs differ from the plain encoding in (nearly)
    # every element — the upload is pad-masked
    diff = sum(int(np.any(a != b))
               for a, b in zip(up.limbs, enc.limbs))
    assert diff == len(up.limbs)
    # ...and the decoded plain encoding round-trips the floats exactly
    _assert_tree_equal(sess.decode(enc), stats[0])


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_mask_cancellation_dtypes(dtype):
    ctx = jax_enable_x64() if dtype is np.float64 else nullcontext()
    with ctx:
        wire, stats = _client_stats(P=3, dtype=dtype, scale=37.5)
        sess = SecAggSession(3, seed=1, dtype=dtype)
        ups = [sess.mask_upload(p, stats[p]) for p in range(3)]
        agg = sess.merge_signed(sess.merge_signed(ups[0], ups[1]),
                                ups[2])
        _assert_tree_equal(sess.unmask(agg), _exact_ref(stats))


def test_masked_merge_rejects_double_and_foreign_subtract():
    wire, stats = _client_stats(P=3)
    sess = SecAggSession(3, seed=0)
    u0, u1 = (sess.mask_upload(p, stats[p]) for p in (0, 1))
    with pytest.raises(ValueError, match="uploads once"):
        sess.merge_signed(u0, u0)
    with pytest.raises(ValueError, match="not in the aggregate"):
        sess.merge_signed(u0, u1, -1)
    with pytest.raises(ValueError, match="empty aggregate"):
        sess.unmask(MaskedStats(limbs=u0.limbs, ids=frozenset()))


def test_session_rejects_template_mismatch_and_nonfinite():
    wire, stats = _client_stats(P=2, m=5)
    sess = SecAggSession(2, seed=0)
    sess.mask_upload(0, stats[0])
    other = GramWire().local_stats(np.zeros((4, 9), np.float32),
                                   np.full((4, 2), 0.5, np.float32))
    with pytest.raises(ValueError, match="template"):
        sess.mask_upload(1, other)
    bad = type(stats[0])(G=stats[0].G * np.nan, m_vec=stats[0].m_vec,
                         n=stats[0].n)
    with pytest.raises(ValueError, match="non-finite"):
        sess.mask_upload(1, bad)


def test_carry_normalization_is_invisible():
    """Lazy int64 limbs far outside [0, 2^32) still decode to the same
    ring value: carry propagation is value-preserving."""
    wire, stats = _client_stats(P=2, n=16)
    sess = SecAggSession(2, seed=0)
    enc = sess.encode(stats[0], 0)
    ref = sess.decode(enc)
    # add 2^57 at limb 0 and remove the same value at limb 1
    # (2^57 = 2^25·2^32): the ring value is unchanged but limb 0 now
    # overflows the clean-digit range and must carry at decode
    messy = [l.copy() for l in enc.limbs]
    messy[0][..., 0] += np.int64(1) << 57
    messy[0][..., 1] -= np.int64(1) << 25
    dec = sess.decode(MaskedStats(limbs=tuple(messy), ids=enc.ids))
    _assert_tree_equal(dec, ref)
    # and the lazy-merge threshold path normalizes without changing it
    big = MaskedStats(limbs=tuple(messy), ids=enc.ids)
    zero = MaskedStats(limbs=tuple(np.zeros_like(l)
                                   for l in enc.limbs),
                       ids=frozenset((1,)))
    merged = sess.merge_signed(big, zero)
    assert np.abs(merged.limbs[0]).max() < np.int64(1) << 33
    _assert_tree_equal(
        sess.decode(MaskedStats(limbs=merged.limbs, ids=enc.ids)), ref)


if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 5), st.integers(1, 30), st.integers(1, 6),
           st.integers(0, 2 ** 16), st.data())
    def test_fuzz_mask_cancellation(P, n, m, seed, data):
        """Hypothesis: random shapes/seeds/subsets, still bitwise."""
        wire, stats = _client_stats(P=P, n=n, m=m, seed=seed)
        sess = SecAggSession(P, seed=seed)
        ups = [sess.mask_upload(p, stats[p]) for p in range(P)]
        S = data.draw(st.lists(st.integers(0, P - 1), min_size=1,
                               max_size=P, unique=True))
        agg = ups[S[0]]
        for i in S[1:]:
            agg = sess.merge_signed(agg, ups[i])
        _assert_tree_equal(sess.unmask(agg),
                           _exact_ref([stats[i] for i in S]))


# ------------------------------------------------ engine: secagg
def test_engine_secagg_bitmatches_unmasked_exact_solve():
    """Acceptance: privacy=secagg W ≡ the unmasked gram-wire exact-
    aggregation solve, bit for bit."""
    pX, pD = _parts()
    rep = FederationEngine(wire="gram", privacy="secagg").run(pX, pD)
    led = FederationLedger("gram")
    for i in range(8):
        led.join(i, led.wire.local_stats(pX[i], pD[i]))
    assert np.array_equal(np.asarray(rep.W), np.asarray(led.solve()))
    # overhead is visible: masked uploads dwarf the float uploads
    base = FederationEngine(wire="gram").run(pX, pD)
    assert rep.wire_bytes > 10 * base.wire_bytes
    assert rep.privacy["mode"] == "secagg"
    assert rep.privacy["upload_bytes"] * 8 == rep.wire_bytes


def test_engine_secagg_dropout_late_join_scenario():
    """Acceptance: under dropout + late join the masked W (and the
    masked W_first) still bit-match unmasked exact solves over the
    same participant sets."""
    P = 8
    pX, pD = _parts(P=P)
    sc = Scenario(dropout=0.25, late_join=0.25, seed=4)
    roles = sc.roles(P)
    rep = FederationEngine(wire="gram", scenario=sc, privacy="secagg",
                           batch_clients=True).run(pX, pD)
    w = GramWire()

    def exact(ids):
        led = FederationLedger("gram")
        for i in ids:
            led.join(i, w.local_stats(pX[i], pD[i]))
        return np.asarray(led.solve())

    assert np.array_equal(np.asarray(rep.W), exact(roles.participants))
    assert np.array_equal(np.asarray(rep.W_first), exact(roles.on_time))


def test_engine_secagg_run_events_leave_bitmatches_survivors():
    """Acceptance: exact unlearning survives masking — after a ledger
    leave event the masked W ≡ a survivors-only unmasked solve."""
    pX, pD = _parts()
    eng = FederationEngine(wire="gram", privacy="secagg",
                           batch_clients=True)
    reps = eng.run_events(pX, pD, "leave@t1:p3")
    led = FederationLedger("gram")
    for i in range(8):
        if i != 3:
            led.join(i, led.wire.local_stats(pX[i], pD[i]))
    assert np.array_equal(np.asarray(reps[-1].W), np.asarray(led.solve()))
    # delta ≡ full re-aggregation holds under masking too
    eng2 = FederationEngine(wire="gram", privacy="secagg",
                            batch_clients=True)
    full = eng2.run_events(pX, pD, "leave@t1:p3", delta=False)
    for a, b in zip(reps, full):
        assert np.array_equal(np.asarray(a.W), np.asarray(b.W))


@pytest.mark.parametrize("gear", ["loop", "batched", "fused", "mesh"])
def test_engine_secagg_coordinator_never_sees_plaintext(monkeypatch,
                                                        gear):
    """Acceptance (spy): during a masked round — on the loop, batched
    and FUSED gears and on the mesh transport alike — the base wire's
    merge is never called host-side, and its solve receives ONLY the
    decoded aggregate (never a single client's statistics). On the
    fused path per-client plaintext exists only as traced
    intermediates inside the one masked program; on the mesh it never
    leaves the owning device."""
    pX, pD = _parts()
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
    kw = {"batched": dict(batch_clients=True),
          "fused": dict(fused=True),
          "mesh": dict(transport="mesh")}.get(gear, {})
    rep = FederationEngine(wire="gram", privacy="secagg",
                           **kw).run(pX, pD)
    assert not merges, "coordinator merged unmasked client statistics"
    assert len(solves) == 1
    # the one decoded object is the aggregate over ALL participants —
    # its sample count proves it is not an individual publication
    assert int(np.asarray(solves[0].n)) == total_n
    assert rep.W is not None


def test_svd_wire_refuses_masking():
    pX, pD = _parts(P=3)
    with pytest.raises(NotImplementedError, match="Iwen-Ong"):
        SvdWire().secagg_encode()
    with pytest.raises(NotImplementedError, match="wire='gram'"):
        FederationEngine(wire="svd", privacy="secagg").run(pX, pD)


def test_privacy_composes_with_mesh_and_fused():
    """Regression of the former loud rejections: the mesh transport
    and the fused path now RUN privacy policies (the 24-cell
    conformance matrix is tests/test_privacy_matrix.py); the one
    refusal left is typed and names its cell. MaskedWire stays
    client-addressed."""
    from repro.privacy.policy import PrivacyCellUnsupported
    pX, pD = _parts(P=2)
    rep_m = FederationEngine(wire="gram", transport="mesh",
                             privacy="secagg").run(pX, pD)
    rep_f = FederationEngine(wire="gram", fused=True,
                             privacy="dp").run(pX, pD)
    assert np.isfinite(np.asarray(rep_m.W)).all()
    assert np.isfinite(np.asarray(rep_f.W)).all()
    with pytest.raises(PrivacyCellUnsupported) as ei:
        FederationEngine(wire="svd", transport="mesh",
                         privacy="secagg").run(pX, pD)
    assert ei.value.cell == ("svd", "mesh", "secagg")
    with pytest.raises(NotImplementedError, match="client-addressed"):
        sess = SecAggSession(2, seed=0)
        MaskedWire(GramWire(), sess).local_stats(pX[0], pD[0])


def test_masked_ledger_refuses_checkpoint(tmp_path):
    pX, pD = _parts(P=3)
    eng = FederationEngine(wire="gram", privacy="secagg")
    reps = eng.run_events(pX, pD, "none")
    assert reps[0].W is not None
    sess = SecAggSession(3, seed=0)
    led = FederationLedger(MaskedWire(GramWire(), sess))
    led.join(0, led.wire.upload(0, pX[0], pD[0]))
    with pytest.raises(NotImplementedError, match="checkpoint"):
        led.save(str(tmp_path / "masked.npz"))


def test_run_events_rejects_mismatched_masked_ledger():
    pX, pD = _parts(P=3)
    eng = FederationEngine(wire="gram", privacy="secagg")
    with pytest.raises(ValueError, match="masked"):
        eng.run_events(pX, pD, "none", ledger=FederationLedger("gram"))
    # a ledger on the engine's own (cached) masked wire is accepted and
    # carries state across run_events calls — masked delta federation
    eng2 = FederationEngine(wire="gram", privacy="secagg")
    led = FederationLedger(eng2._begin_privacy(3).coord_wire)
    reps = eng2.run_events(pX, pD, "none", ledger=led)
    assert reps[0].tick == 0 and led.clients == (0, 1, 2)
    reps2 = eng2.run_events(pX, pD, "leave@t1:p1", ledger=led)
    assert led.clients == (0, 2)
    ref = FederationLedger("gram")
    for i in (0, 2):
        ref.join(i, ref.wire.local_stats(pX[i], pD[i]))
    assert np.array_equal(np.asarray(reps2[-1].W),
                          np.asarray(ref.solve()))


# ------------------------------------------------------------- DP
def test_clip_rows_bounds_norms_and_is_idempotent():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(100, 7)).astype(np.float32) * 10
    Xc = clip_rows(X, 2.5)
    norms = np.linalg.norm(np.asarray(Xc, np.float64), axis=1)
    assert np.all(norms <= 2.5 * (1 + 1e-6))
    # re-clipping only nudges float32 rounding at the boundary
    np.testing.assert_allclose(clip_rows(Xc, 2.5), Xc, rtol=1e-6)
    # rows already inside the ball are untouched bit-for-bit
    small = (X * 1e-3).astype(np.float32)
    assert np.array_equal(clip_rows(small, 2.5), small)
    with pytest.raises(ValueError, match="clip"):
        clip_rows(X, 0.0)


def test_dp_noise_zero_mean_matches_sigma():
    """Satellite: the injected noise is zero-mean with the calibrated
    σ (empirically, over many draws)."""
    import jax
    sigma = calibrate_sigma(1.0, 1e-5, sensitivity(2, 1.0))
    zero = type(GramWire().local_stats(
        np.zeros((4, 6), np.float32), np.full((4, 2), 0.5, np.float32)))
    base = zero(G=np.zeros((2, 7, 7), np.float32),
                m_vec=np.zeros((7, 2), np.float32),
                n=np.float32(4))
    key = jax.random.key(0)
    samples = []
    for i in range(400):
        st = noise_stats(base, sigma, jax.random.fold_in(key, i))
        # upper triangle only: the mirrored lower half is the same draw
        iu = np.triu_indices(7)
        samples.append(np.concatenate(
            [np.asarray(st.G)[:, iu[0], iu[1]].ravel(),
             np.asarray(st.m_vec).ravel()]))
        assert np.array_equal(np.asarray(st.G),
                              np.swapaxes(np.asarray(st.G), -1, -2))
        assert float(st.n) == 4.0
    flat = np.concatenate(samples)
    assert abs(flat.mean()) < 5 * sigma / math.sqrt(flat.size)
    assert abs(flat.std() / sigma - 1.0) < 0.05


def test_calibrated_sigma_is_sufficient_and_tight():
    for eps, delta in [(0.5, 1e-5), (1.0, 1e-5), (4.0, 1e-6),
                       (10.0, 1e-4)]:
        sens = sensitivity(3, 2.0)
        sig = calibrate_sigma(eps, delta, sens)
        assert gaussian_delta(eps, sens, sig) <= delta * (1 + 1e-6)
        assert gaussian_delta(eps, sens, 0.95 * sig) > delta
    assert calibrate_sigma(math.inf, 1e-5, 1.0) == 0.0
    # regression: very large finite ε is a legal sweep value — the
    # e^ε term must be evaluated in log space, not overflow
    big = calibrate_sigma(800.0, 1e-5, 1.0)
    assert 0.0 < big < 0.1
    assert gaussian_delta(800.0, 1.0, big) <= 1e-5 * (1 + 1e-6)


def test_clip_only_works_on_svd_wire():
    """Regression: ε=∞ short-circuits σ to 0 before the sensitivity
    bound, so clip-only dp runs work on wires with no analytic Δ."""
    pX, pD = _parts(P=4)
    pol = PrivacyPolicy(mode="dp", epsilon=math.inf, clip=3.0)
    rep = FederationEngine(wire="svd", privacy=pol).run(pX, pD)
    base = FederationEngine(wire="svd").run(
        [clip_rows(X, 3.0) for X in pX], pD)
    np.testing.assert_allclose(np.asarray(rep.W), np.asarray(base.W),
                               rtol=1e-5, atol=1e-6)


def test_accountant_rejects_invalid_budgets():
    """Satellite: the ε-accountant rejects invalid (ε, δ)."""
    acc = DPAccountant()
    for eps, delta in [(0.0, 1e-5), (-1.0, 1e-5), (math.nan, 1e-5),
                       (1.0, -0.1), (1.0, 1.0), (1.0, math.nan),
                       (1.0, 0.0)]:
        with pytest.raises(ValueError):
            acc.spend(eps, delta)
        with pytest.raises(ValueError):
            validate_budget(eps, delta)
    assert acc.releases == 0
    acc.spend(1.0, 1e-5)
    # a clip-only (ε=∞) release is NOT free — an unnoised release has
    # no DP, and the honest total is ∞, never 0
    acc.spend(math.inf, 0.0)
    assert math.isinf(acc.eps_spent) and acc.releases == 2
    with pytest.raises(ValueError):
        PrivacyPolicy(mode="dp", epsilon=-2.0)
    with pytest.raises(ValueError, match="clip"):
        PrivacyPolicy(mode="dp", clip=0.0)
    with pytest.raises(ValueError, match="privacy mode"):
        PrivacyPolicy(mode="both")


def test_engine_dp_eps_inf_bitmatches_clipped_baseline():
    """Acceptance: ε=∞ (clip, no noise) ≡ manually clipped run."""
    pX, pD = _parts()
    pol = PrivacyPolicy(mode="dp", epsilon=math.inf, clip=3.0)
    rep = FederationEngine(wire="gram", privacy=pol).run(pX, pD)
    base = FederationEngine(wire="gram").run(
        [clip_rows(X, 3.0) for X in pX], pD)
    assert np.array_equal(np.asarray(rep.W), np.asarray(base.W))
    assert rep.privacy["releases"] == 1
    # the unnoised release is honestly reported as an infinite spend
    assert math.isinf(rep.privacy["eps_spent"])


def test_engine_dp_noised_solve_is_finite_and_accounted():
    pX, pD = _parts()
    pol = PrivacyPolicy(mode="dp", epsilon=1.0, clip=3.0, seed=1)
    rep = FederationEngine(wire="gram", privacy=pol).run(pX, pD)
    assert np.all(np.isfinite(np.asarray(rep.W)))
    assert rep.privacy["sigma"] > 0
    assert rep.privacy["eps_spent"] == 1.0
    # determinism: same policy/seed → same noise → same W
    rep2 = FederationEngine(wire="gram", privacy=pol).run(pX, pD)
    assert np.array_equal(np.asarray(rep.W), np.asarray(rep2.W))


def test_release_noise_is_never_reused():
    """Regression: successive releases must draw independent noise —
    identical draws would cancel under differencing, voiding the
    composition the accountant charges."""
    pX, pD = _parts()
    pol = PrivacyPolicy(mode="dp", epsilon=1.0, clip=3.0, seed=2)
    eng = FederationEngine(wire="gram", privacy=pol)
    rep1 = eng.run(pX, pD)
    rep2 = eng.run(pX, pD)          # same data, same engine: 2nd spend
    assert rep2.privacy["eps_spent"] == 2.0
    assert not np.array_equal(np.asarray(rep1.W), np.asarray(rep2.W))


def test_distributed_noise_shares_scale_to_round_cohort():
    """Regression: under dropout the surviving shares must still sum
    to the calibrated σ — shares scale by the round's participant
    count, not the universe."""
    P = 8
    pX, pD = _parts(P=P)
    pol = PrivacyPolicy(mode="secagg+dp", epsilon=1.0, clip=3.0)
    sc = Scenario(dropout=0.5, seed=1)
    eng = FederationEngine(wire="gram", scenario=sc, privacy=pol)
    rep = eng.run(pX, pD)
    n_part = len(sc.roles(P).participants)
    assert n_part < P
    assert eng._priv.cohort == n_part
    assert rep.privacy["noise_share_basis"] == n_part
    # unit check of the scaling itself: same policy/seed, first encode
    # of the same stats under two cohort sizes → the same Gaussian
    # draw scaled by exactly √(c2/c1)
    wire, stats = _client_stats(P=2)
    runs = []
    for cohort in (4, 16):
        run = PrivacyPolicy(mode="secagg+dp", epsilon=1.0,
                            clip=3.0).begin(16, GramWire())
        run.cohort = cohort
        run.session = None          # observe the noised floats
        runs.append(run.client_encode(0, stats[0]))
    d4 = np.asarray(runs[0].G) - np.asarray(stats[0].G)
    d16 = np.asarray(runs[1].G) - np.asarray(stats[0].G)
    np.testing.assert_allclose(d4, d16 * 2.0, rtol=1e-5)


def test_engine_secagg_dp_distributed_noise_is_finite():
    pX, pD = _parts()
    pol = PrivacyPolicy(mode="secagg+dp", epsilon=1.0, clip=3.0)
    rep = FederationEngine(wire="gram", privacy=pol).run(pX, pD)
    assert np.all(np.isfinite(np.asarray(rep.W)))
    assert rep.privacy["mode"] == "secagg+dp"
    assert rep.privacy["upload_bytes"] > 0


def test_sensitivity_analytic_bound_holds_empirically():
    """Adding one clipped sample never moves (G, m_vec) by more than
    the analytic Δ (checked in float64)."""
    rng = np.random.default_rng(3)
    clip = 1.5
    wire = GramWire(dtype=np.float64)
    sens = sensitivity(2, clip)
    X = clip_rows(rng.normal(size=(50, 4)) * 5, clip)
    D = np.asarray(acts.encode_labels(rng.integers(0, 2, 50), 2),
                   np.float64)
    with jax_enable_x64():
        full = wire.local_stats(X, D)
        drop = wire.local_stats(X[:-1], D[:-1])
    dG = np.asarray(full.G) - np.asarray(drop.G)
    dm = np.asarray(full.m_vec) - np.asarray(drop.m_vec)
    moved = math.sqrt(float((dG ** 2).sum() + (dm ** 2).sum()))
    assert moved <= sens * (1 + 1e-6)


# ----------------------------------------------- energy satellite
def test_comm_energy_monotone_in_clients():
    """Satellite: with the J/byte uplink term, federated energy is
    strictly increasing in P beyond the compute crossover, while
    centralized stays P-independent — and the comm term itself is
    linear in P."""
    model = CostModel()
    n, m, B = 1_000_000, 18, 24_352
    fj = [model.federated_joules(n, m, P, upload_bytes_per_client=B)
          for P in (10, 100, 1_000, 10_000, 100_000)]
    comm = [model.federated_joules(n, m, P, upload_bytes_per_client=B)
            - model.federated_joules(n, m, P)
            for P in (10, 100, 1_000)]
    assert np.allclose(comm, [P * B * model.j_per_byte
                              for P in (10, 100, 1_000)])
    central = model.centralized_joules(n, m)
    assert central == model.centralized_joules(n, m)   # P-independent
    assert fj[-1] > fj[-2] > fj[-3]          # right branch of the U
    assert fj[-1] > central                  # crossover exists
    # secagg's ring-widened uploads cost proportionally more uplink
    assert model.comm_joules(40 * B) == 40 * model.comm_joules(B)
    assert uplink_joules(B) == B * J_PER_BYTE