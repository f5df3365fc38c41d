"""Property-based merge-algebra suite (ISSUE 4 satellite).

The engine has leaned on the wires' merge algebra since PR 2 — this is
its adversarial test suite:

* commutativity (bitwise on the gram wire: IEEE addition commutes),
* associativity (to rounding in float; *bitwise* through the ledger's
  ExactAccumulator, whose integer arithmetic never rounds),
* ``merge_many`` ≡ ``merge_tree`` ≡ fleet ``merge_axis``,
* subtract∘merge round-trip identity: in float, ``(a+b)−b`` recovers
  ``a`` only to rounding (``GramWire.subtract``); through the exact
  signed algebra it bit-equals ``a`` unconditionally — on every dtype
  and on padded (fleet-stacked) and unpadded statistics alike,
* conditioning regression for ``solve_weights_gram`` (near-singular
  Gram: duplicated columns, n < m) on both the Cholesky happy path and
  the ``method="solve"`` LU fallback.

Hypothesis is optional (guarded import): the deterministic seeded
versions always run; the fuzzing versions add randomized shapes,
dtypes, and partitions when hypothesis is installed.
"""
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
from repro.core import client_gram_stats, solve_weights_gram
from repro.core.ledger import ExactAccumulator
from repro.core.wire import GramWire, SvdWire, get_wire


def _client_data(n, m, c=2, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(dtype)
    D = np.asarray(acts.encode_labels(rng.integers(0, c, size=n), c),
                   dtype)
    return X, D


def _stats_list(wire, P, n=120, m=9, seed=0, padded=False):
    """P clients' published statistics, optionally via the zero-padded
    fleet path (each slice is bitwise the per-client pass — PR 3)."""
    data = [_client_data(n + 17 * p, m, seed=seed + p) for p in range(P)]
    if not padded:
        return [wire.local_stats(X, D) for X, D in data]
    n_max = max(X.shape[0] for X, _ in data)
    Xs = np.zeros((P, n_max, m), np.float32)
    Ds = np.full((P, n_max, data[0][1].shape[1]), 0.5, np.float32)
    ns = []
    for p, (X, D) in enumerate(data):
        Xs[p, :X.shape[0]], Ds[p, :X.shape[0]] = X, D
        ns.append(X.shape[0])
    return wire.local_stats_batch(Xs, Ds, np.asarray(ns))


def _bit_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def _x64(dtype):
    """fp64 statistics need the x64 switch (fp32 is the JAX default)."""
    return jax_enable_x64() if jnp.dtype(dtype) == jnp.float64 \
        else nullcontext()


# --------------------------------------------------------- commutativity
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_gram_merge_commutes_bitwise(dtype, padded):
    """IEEE addition commutes, so the gram merge is bitwise symmetric."""
    with _x64(dtype):
        w = GramWire(dtype=dtype)
        a, b = _stats_list(w, 2, seed=3, padded=padded)
        assert _bit_equal(w.merge(a, b), w.merge(b, a))


def test_svd_merge_commutes_through_solve():
    """The SVD merge commutes up to sign/rounding of the factors — the
    solved model is the invariant surface to compare on."""
    w = SvdWire()
    a, b = _stats_list(w, 2, seed=4)
    np.testing.assert_allclose(
        np.asarray(w.solve(w.merge(a, b), 1e-3)),
        np.asarray(w.solve(w.merge(b, a), 1e-3)), rtol=1e-4, atol=1e-5)


# --------------------------------------------------------- associativity
@pytest.mark.parametrize("wire_name", ["gram", "svd"])
def test_merge_associates_through_solve(wire_name):
    w = get_wire(wire_name)
    a, b, c = _stats_list(w, 3, seed=5)
    left = w.merge(w.merge(a, b), c)
    right = w.merge(a, w.merge(b, c))
    np.testing.assert_allclose(np.asarray(w.solve(left, 1e-3)),
                               np.asarray(w.solve(right, 1e-3)),
                               rtol=1e-4, atol=1e-5)


def test_exact_algebra_associates_bitwise():
    """The ledger's signed algebra is *exactly* associative and
    commutative: any grouping/order snapshots bit-identically."""
    w = GramWire()
    a, b, c = _stats_list(w, 3, seed=6)
    orders = [(a, b, c), (c, a, b), (b, c, a)]
    snaps = []
    for order in orders:
        acc = ExactAccumulator(a)
        for s in order:
            acc.add(s)
        snaps.append(acc.snapshot())
    assert _bit_equal(snaps[0], snaps[1]) and _bit_equal(snaps[0],
                                                         snaps[2])


# ------------------------------------- merge_many ≡ merge_tree ≡ axis
@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("wire_name", ["gram", "svd"])
def test_merge_topologies_agree(wire_name, padded):
    """Sequential fold ≡ pairwise tree ≡ fleet leading-axis merge."""
    w = get_wire(wire_name)
    stats = _stats_list(w, 5, seed=7, padded=padded)
    W_many = w.solve(w.merge_many(stats), 1e-3)
    W_tree = w.solve(w.merge_tree(stats), 1e-3)
    np.testing.assert_allclose(np.asarray(W_many), np.asarray(W_tree),
                               rtol=1e-4, atol=1e-5)
    # the fused path's merge over the stacked fleet axis
    data = [_client_data(120 + 17 * p, 9, seed=7 + p) for p in range(5)]
    n_max = max(X.shape[0] for X, _ in data)
    Xs = np.zeros((5, n_max, 9), np.float32)
    Ds = np.full((5, n_max, 2), 0.5, np.float32)
    ns = np.asarray([X.shape[0] for X, _ in data])
    for p, (X, D) in enumerate(data):
        Xs[p, :X.shape[0]], Ds[p, :X.shape[0]] = X, D
    W_axis = w.solve(w.merge_axis(w.fleet_stats(Xs, Ds, ns)), 1e-3)
    np.testing.assert_allclose(np.asarray(W_axis), np.asarray(W_many),
                               rtol=1e-4, atol=1e-5)


# --------------------------------------------- subtract / merge_signed
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_gram_subtract_float_downdate(dtype):
    """Float downdate: (a+b)−b recovers a to rounding (NOT bitwise —
    that is exactly why the ledger carries an ExactAccumulator)."""
    with _x64(dtype):
        w = GramWire(dtype=dtype)
        a, b = _stats_list(w, 2, seed=8)
        back = w.subtract(w.merge(a, b), b)
        tol = dict(rtol=1e-6, atol=1e-6) if dtype == jnp.float32 else \
            dict(rtol=1e-12, atol=1e-12)
        np.testing.assert_allclose(np.asarray(back.G), np.asarray(a.G),
                                   **tol)
        np.testing.assert_allclose(np.asarray(back.m_vec),
                                   np.asarray(a.m_vec), **tol)
        assert float(back.n) == float(a.n)
        # merge_signed(+1) is merge
        assert _bit_equal(w.merge_signed(a, b, 1), w.merge(a, b))


@pytest.mark.parametrize("padded", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_subtract_merge_roundtrip_bitwise_exact(dtype, padded):
    """subtract∘merge identity, bit-exact: through the ledger's exact
    signed algebra, add(b) then subtract(b) leaves the snapshot of ``a``
    bit-identical — on every dtype, padded or not."""
    with _x64(dtype):
        w = GramWire(dtype=dtype)
        a, b = _stats_list(w, 2, seed=9, padded=padded)
        acc = ExactAccumulator(a)
        acc.add(a)
        assert _bit_equal(acc.snapshot(), a)  # snapshot of one entry = it
        acc.add(b)
        acc.subtract(b)
        assert _bit_equal(acc.snapshot(), a)


def test_exact_accumulator_multiset_invariance():
    """Snapshots depend only on the multiset of live contributions,
    never the history: join/leave churn == never-joined, bitwise."""
    w = GramWire()
    a, b, c = _stats_list(w, 3, seed=10)
    churn = ExactAccumulator(a)
    for s in (a, b, c):
        churn.add(s)
    churn.subtract(b)
    clean = ExactAccumulator(a)
    clean.add(a)
    clean.add(c)
    assert _bit_equal(churn.snapshot(), clean.snapshot())


# ------------------------------------------------ hypothesis fuzzing
if HAVE_HYPOTHESIS:

    @needs_hypothesis
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(5, 150), m=st.integers(2, 12),
           c=st.integers(1, 3), seed=st.integers(0, 10_000),
           f64=st.booleans())
    def test_fuzz_gram_commutes_bitwise(n, m, c, seed, f64):
        dtype = jnp.float64 if f64 else jnp.float32
        with _x64(dtype):
            w = GramWire(dtype=dtype)
            a_X, a_D = _client_data(n, m, c, seed)
            b_X, b_D = _client_data(n + 3, m, c, seed + 1)
            a, b = w.local_stats(a_X, a_D), w.local_stats(b_X, b_D)
            assert _bit_equal(w.merge(a, b), w.merge(b, a))

    @needs_hypothesis
    @settings(max_examples=20, deadline=None)
    @given(n=st.integers(5, 150), m=st.integers(2, 12),
           c=st.integers(1, 3), seed=st.integers(0, 10_000),
           f64=st.booleans())
    def test_fuzz_roundtrip_bitwise_exact(n, m, c, seed, f64):
        dtype = jnp.float64 if f64 else jnp.float32
        with _x64(dtype):
            w = GramWire(dtype=dtype)
            a_X, a_D = _client_data(n, m, c, seed)
            b_X, b_D = _client_data(n + 3, m, c, seed + 1)
            a, b = w.local_stats(a_X, a_D), w.local_stats(b_X, b_D)
            acc = ExactAccumulator(a)
            acc.add(a)
            acc.add(b)
            acc.subtract(b)
            assert _bit_equal(acc.snapshot(), a)

    @needs_hypothesis
    @settings(max_examples=10, deadline=None)
    @given(P=st.integers(2, 6), n=st.integers(30, 120),
           m=st.integers(2, 10), seed=st.integers(0, 10_000),
           wire_name=st.sampled_from(["gram", "svd"]))
    def test_fuzz_merge_topologies_agree(P, n, m, seed, wire_name):
        w = get_wire(wire_name)
        stats = [w.local_stats(*_client_data(n + 7 * p, m,
                                             seed=seed + p))
                 for p in range(P)]
        np.testing.assert_allclose(
            np.asarray(w.solve(w.merge_many(stats), 1e-3)),
            np.asarray(w.solve(w.merge_tree(stats), 1e-3)),
            rtol=1e-3, atol=1e-4)


# ------------------------------------------- conditioning regression
@pytest.mark.parametrize("method", ["cholesky", "solve"])
@pytest.mark.parametrize("act", ["logistic", "identity"])
def test_solve_weights_gram_near_singular(method, act):
    """Near-singular Gram (duplicated columns AND n < m): with the ridge
    λ = 1e-3 the system stays SPD, so the Cholesky happy path and the
    LU fallback must both return finite W with backward-stable residual
    (documented tolerance: relative residual ≤ 1e-5 at fp32 — see
    solve_weights_gram)."""
    rng = np.random.default_rng(11)
    n, m, c = 8, 12, 2                        # n < m: rank(G) ≤ n
    X = rng.normal(size=(n, m)).astype(np.float32)
    X[:, m // 2:] = X[:, :m - m // 2]          # duplicated columns
    if act == "logistic":
        D = np.asarray(acts.encode_labels(rng.integers(0, c, size=n), c))
    else:
        D = rng.uniform(-0.8, 0.8, size=(n, c)).astype(np.float32)
    lam = 1e-3
    st_ = client_gram_stats(X, D, act=act)
    W = solve_weights_gram(st_, lam, method=method)
    assert np.isfinite(np.asarray(W)).all()
    # documented tolerance: backward-stable relative residual
    G, m_vec = np.asarray(st_.G), np.asarray(st_.m_vec)
    eye = np.eye(G.shape[-1], dtype=G.dtype)
    for k in range(G.shape[0]):
        A = G[k] + lam * eye
        b = m_vec[:, k] if G.shape[0] > 1 else m_vec
        wk = np.asarray(W)[:, k] if G.shape[0] > 1 else np.asarray(W)
        r = A @ wk - b
        denom = np.linalg.norm(A) * np.linalg.norm(wk) + \
            np.linalg.norm(b)
        assert np.linalg.norm(r) / denom < 1e-5, (method, act, k)


def test_solve_methods_agree_near_singular():
    """Cholesky and LU agree on the near-singular ridge system."""
    rng = np.random.default_rng(12)
    X = rng.normal(size=(6, 10)).astype(np.float32)
    X[:, 5:] = X[:, :5]
    D = np.asarray(acts.encode_labels(rng.integers(0, 2, size=6), 2))
    st_ = client_gram_stats(X, D)
    W_cho = solve_weights_gram(st_, 1e-3)
    W_lu = solve_weights_gram(st_, 1e-3, method="solve")
    np.testing.assert_allclose(np.asarray(W_cho), np.asarray(W_lu),
                               rtol=1e-3, atol=1e-4)
