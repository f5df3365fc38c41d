"""The per-client statistics pass is one compiled program per client shape.

``client_gram_stats`` jits the activation prep, the statistics and the
casts together on either backend. On the Pallas backend the result must
be bitwise what the kernel returns on eagerly prepped inputs (the same
arithmetic, now issued as one dispatch; tanh's F' may move by one ulp
where the compiler fuses it), and a shape must compile once.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import activations as acts
from repro.core import client_gram_stats, solver
from repro.kernels import ops as kops


def _problem(act, n=300, m=9, c=3, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, m)).astype(np.float32)
    if act == "logistic":
        D = np.asarray(acts.encode_labels(rng.integers(0, c, size=n), c))
    else:
        D = rng.uniform(-0.8, 0.8, size=(n, c)).astype(np.float32)
    return X, D


def _kernel(X, D, act):
    """The kernel on inputs prepped op by op, outside any jit."""
    Xb, d_bar, fp, _ = solver._prep(X, D, act, True, jnp.float32)
    if acts.get(act).name == "identity":
        return kops.client_gram_stats_shared(Xb, d_bar, interpret=True)
    return kops.client_gram_stats_fused(Xb, d_bar, fp, interpret=True)


def _assert_bitwise(st, G, m_vec, n):
    np.testing.assert_array_equal(np.asarray(st.G), np.asarray(G))
    np.testing.assert_array_equal(np.asarray(st.m_vec), np.asarray(m_vec))
    assert float(st.n) == n
    assert st.G.dtype == st.m_vec.dtype == st.n.dtype == jnp.float32


@pytest.mark.parametrize("act", ["logistic", "tanh", "identity"])
def test_pallas_bitmatches_eagerly_prepped_kernel(act):
    X, D = _problem(act)
    n = X.shape[0]
    st = client_gram_stats(X, D, act=act, backend="pallas", interpret=True)
    G, m_vec = _kernel(X, D, act)
    if act == "tanh":
        # compiled, tanh's F' = 1 - t·t becomes one fused multiply-subtract
        # on the CPU (one rounding where the eager pair of ops rounds
        # twice), as in the jitted fleet path: F' moves by one ulp at most
        np.testing.assert_allclose(np.asarray(st.G), np.asarray(G),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(st.m_vec), np.asarray(m_vec),
                                   rtol=1e-5, atol=1e-5)
    else:
        _assert_bitwise(st, G, m_vec, n)


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_one_dimensional_targets_are_one_column(backend):
    X, D = _problem("logistic", c=1)
    st1 = client_gram_stats(X, D[:, 0], backend=backend, interpret=True)
    st2 = client_gram_stats(X, D, backend=backend, interpret=True)
    assert st1.m_vec.shape == (X.shape[1] + 1, 1)
    _assert_bitwise(st1, st2.G, st2.m_vec, X.shape[0])
    if backend == "pallas":
        G, m_vec = _kernel(X, D[:, 0], "logistic")
        _assert_bitwise(st1, G, m_vec, X.shape[0])


@pytest.mark.parametrize("act", ["logistic", "identity"])
def test_empty_shard_is_exact_zero(act):
    m, c = 9, 3
    X = np.zeros((0, m), np.float32)
    D = np.full((0, c), 0.5, np.float32)
    st = client_gram_stats(X, D, act=act, backend="pallas", interpret=True)
    G, m_vec = _kernel(X, D, act)
    _assert_bitwise(st, G, m_vec, 0)
    k = 1 if act == "identity" else c
    assert st.G.shape == (k, m + 1, m + 1)
    assert not np.asarray(st.G).any() and not np.asarray(st.m_vec).any()


def test_interpret_none_resolves_before_the_program():
    """``interpret=None`` takes the backend's default and shares its
    compiled program with the explicit value."""
    X, D = _problem("logistic", n=77, m=5, c=2, seed=3)
    default = kops._default_interpret()
    solver._gram_stats.clear_cache()
    a = client_gram_stats(X, D, backend="pallas", interpret=None)
    b = client_gram_stats(X, D, backend="pallas", interpret=default)
    assert solver._gram_stats._cache_size() == 1
    _assert_bitwise(a, b.G, b.m_vec, X.shape[0])


@pytest.mark.parametrize("backend", ["pallas", "xla"])
def test_one_program_per_client_shape(backend, monkeypatch):
    """Two same-shape clients leave one compiled entry and a new shape
    adds one; the prep only ever sees tracers, so no op of the pass runs
    eagerly outside the program."""
    seen = []
    prep = solver._prep

    def spy(X, D, *args):
        seen.append((isinstance(X, jax.core.Tracer),
                     isinstance(D, jax.core.Tracer)))
        return prep(X, D, *args)

    monkeypatch.setattr(solver, "_prep", spy)
    solver._gram_stats.clear_cache()
    Xa, Da = _problem("logistic", n=200, seed=1)
    Xb, Db = _problem("logistic", n=200, seed=2)
    Xc, Dc = _problem("logistic", n=260, seed=3)
    sa = client_gram_stats(Xa, Da, backend=backend, interpret=True)
    client_gram_stats(Xb, Db, backend=backend, interpret=True)
    assert solver._gram_stats._cache_size() == 1
    client_gram_stats(Xc, Dc, backend=backend, interpret=True)
    assert solver._gram_stats._cache_size() == 2
    assert seen == [(True, True), (True, True)]
    again = client_gram_stats(Xa, Da, backend=backend, interpret=True)
    assert solver._gram_stats._cache_size() == 2 and len(seen) == 2
    _assert_bitwise(again, sa.G, sa.m_vec, Xa.shape[0])
