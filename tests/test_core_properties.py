"""Hypothesis property tests on the solver's algebraic invariants."""
import numpy as np
import jax
from repro.core.util import enable_x64 as jax_enable_x64
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="optional dependency: property tests need "
    "hypothesis (pip install hypothesis)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import (centralized_solve_gram, client_stats, merge_many,
                        merge_stats, solve_weights)
from repro.core import activations as acts


def _solve_fed(parts_X, parts_D, act, lam):
    stats = [client_stats(X, D, act=act, dtype=jnp.float64)
             for X, D in parts_X_D(parts_X, parts_D)]
    return solve_weights(merge_many(stats), lam)


def parts_X_D(Xs, Ds):
    return list(zip(Xs, Ds))


@settings(max_examples=15, deadline=None)
@given(
    n=st.integers(20, 120),
    m=st.integers(2, 12),
    c=st.integers(1, 3),
    P=st.integers(1, 5),
    lam=st.floats(1e-4, 1e-1),
    seed=st.integers(0, 10_000),
    act=st.sampled_from(["logistic", "identity", "tanh"]),
)
def test_partition_invariance(n, m, c, P, lam, seed, act):
    """∀ partitionings: federated solve == centralized solve (fp64)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m))
    lo, hi = (0.1, 0.9) if act in ("logistic",) else (-0.8, 0.8)
    D = rng.uniform(lo, hi, size=(n, c))
    with jax_enable_x64(True):
        W_cen = centralized_solve_gram(X, D, act=act, lam=lam,
                                       dtype=jnp.float64)
        cuts = np.sort(rng.choice(np.arange(1, n), size=P - 1,
                                  replace=False)) if P > 1 else []
        idx = np.split(np.arange(n), cuts)
        stats = [client_stats(X[i], D[i], act=act, dtype=jnp.float64)
                 for i in idx if len(i)]
        W_fed = solve_weights(merge_many(stats), lam)
    np.testing.assert_allclose(np.asarray(W_fed), np.asarray(W_cen),
                               rtol=1e-6, atol=1e-8)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(30, 80),
    m=st.integers(2, 8),
    seed=st.integers(0, 10_000),
)
def test_merge_commutative_and_associative(n, m, seed):
    """merge(a,b) and merge(b,a); (a·b)·c and a·(b·c) give the same model."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(3 * n, m))
    D = rng.uniform(0.1, 0.9, size=(3 * n, 1))
    with jax_enable_x64(True):
        a, b, c = (client_stats(X[i * n:(i + 1) * n], D[i * n:(i + 1) * n],
                                dtype=jnp.float64) for i in range(3))
        W_ab = solve_weights(merge_stats(a, b), 1e-3)
        W_ba = solve_weights(merge_stats(b, a), 1e-3)
        W_ab_c = solve_weights(merge_stats(merge_stats(a, b), c), 1e-3)
        W_a_bc = solve_weights(merge_stats(a, merge_stats(b, c)), 1e-3)
    np.testing.assert_allclose(np.asarray(W_ab), np.asarray(W_ba),
                               rtol=1e-7, atol=1e-9)
    np.testing.assert_allclose(np.asarray(W_ab_c), np.asarray(W_a_bc),
                               rtol=1e-7, atol=1e-9)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(10, 60), m=st.integers(2, 30),
       seed=st.integers(0, 1000))
def test_wide_and_tall_clients(n, m, seed):
    """eq. 5's economy SVD works for n ≫ m and m ≫ n alike (paper §3.1)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m))
    D = rng.uniform(0.1, 0.9, size=(n, 1))
    with jax_enable_x64(True):
        W = solve_weights(client_stats(X, D, dtype=jnp.float64), 1e-3)
        W_cen = centralized_solve_gram(X, D, dtype=jnp.float64)
    assert W.shape == (m + 1, 1)
    np.testing.assert_allclose(np.asarray(W), np.asarray(W_cen),
                               rtol=1e-6, atol=1e-8)
