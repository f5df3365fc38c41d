"""Pallas kernel validation: interpret-mode execution vs jnp oracles,
swept over shapes and dtypes."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import (decode_gqa, gram_stats, gram_stats_fleet,
                           gram_stats_fleet_shared, gram_stats_multi,
                           gram_stats_shared)
from repro.kernels import ops, ref


@pytest.mark.parametrize("n,m", [(64, 8), (512, 19), (1000, 29),
                                 (130, 128), (257, 200)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_stats_matches_ref(n, m, dtype):
    rng = np.random.default_rng(hash((n, m)) % 2**31)
    X = jnp.asarray(rng.normal(size=(n, m)), dtype)
    fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(n,)), dtype)
    dbar = jnp.asarray(rng.normal(size=(n,)), dtype)
    G, mv = gram_stats(X, fp, dbar, interpret=True)
    G_ref, mv_ref = ref.gram_stats_ref(X, fp, dbar)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref),
                               rtol=tol, atol=tol * 10)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mv_ref),
                               rtol=tol, atol=tol * 10)
    assert G.dtype == jnp.float32 and mv.dtype == jnp.float32


@pytest.mark.parametrize("bm,bn", [(128, 256), (128, 512), (256, 128)])
def test_gram_stats_block_shape_invariance(bm, bn):
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(700, 50)), jnp.float32)
    fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(700,)), jnp.float32)
    dbar = jnp.asarray(rng.normal(size=(700,)), jnp.float32)
    G, mv = gram_stats(X, fp, dbar, bm=bm, bn=bn, interpret=True)
    G_ref, mv_ref = ref.gram_stats_ref(X, fp, dbar)
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mv_ref),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n,m,c", [(64, 8, 2), (300, 50, 3), (257, 130, 4)])
def test_gram_stats_multi_matches_ref(n, m, c):
    """The (c, mi, mj, nk) grid kernel vs the per-class k=1 oracle."""
    rng = np.random.default_rng(hash((n, m, c)) % 2**31)
    X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    Fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(n, c)), jnp.float32)
    Db = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    G, mv = gram_stats_multi(X, Fp, Db, interpret=True)
    assert G.shape == (c, m, m) and mv.shape == (m, c)
    assert G.dtype == jnp.float32 and mv.dtype == jnp.float32
    for k in range(c):
        Gr, mr = ref.gram_stats_ref(X, Fp[:, k], Db[:, k])
        np.testing.assert_allclose(np.asarray(G[k]), np.asarray(Gr),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(mv[:, k]), np.asarray(mr),
                                   rtol=1e-5, atol=1e-4)


def test_gram_stats_multi_acceptance_shape():
    """ISSUE acceptance: (n=1024, m=192, c=10) logistic inputs must match
    the XLA einsum path to ≤1e-4 max-abs."""
    from repro.core import activations as acts
    rng = np.random.default_rng(42)
    n, m, c = 1024, 192, 10
    X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    y = rng.integers(0, c, size=n)
    D = jnp.asarray(acts.encode_labels(y, c))
    act = acts.get("logistic")
    dbar = act.f_inv(D)
    fp = act.f_prime(dbar)
    G, mv = gram_stats_multi(X, fp, dbar, interpret=True)
    XF = jnp.einsum("nm,nc->cnm", X, fp)
    G_ref = jnp.einsum("cnm,cnp->cmp", XF, XF)
    mv_ref = X.T @ (fp * fp * dbar)
    assert float(jnp.abs(G - G_ref).max()) <= 1e-4
    assert float(jnp.abs(mv - mv_ref).max()) <= 1e-4


@pytest.mark.parametrize("bm,bn", [(128, 256), (256, 128)])
def test_gram_stats_multi_block_shape_invariance(bm, bn):
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(500, 40)), jnp.float32)
    Fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(500, 2)), jnp.float32)
    Db = jnp.asarray(rng.normal(size=(500, 2)), jnp.float32)
    G, mv = gram_stats_multi(X, Fp, Db, bm=bm, bn=bn, interpret=True)
    G_ref, mv_ref = gram_stats_multi(X, Fp, Db, interpret=True)
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref),
                               rtol=1e-6, atol=1e-5)
    np.testing.assert_allclose(np.asarray(mv), np.asarray(mv_ref),
                               rtol=1e-6, atol=1e-5)


def test_gram_stats_multi_output_wrapper():
    rng = np.random.default_rng(1)
    n, m, c = 300, 12, 3
    X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    Fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(n, c)), jnp.float32)
    Db = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    G, mv = ops.client_gram_stats_fused(X, Db, Fp, interpret=True)
    assert G.shape == (c, m, m) and mv.shape == (m, c)
    for k in range(c):
        Gr, mr = ref.gram_stats_ref(X, Fp[:, k], Db[:, k])
        np.testing.assert_allclose(np.asarray(G[k]), np.asarray(Gr),
                                   rtol=1e-5, atol=1e-4)
        np.testing.assert_allclose(np.asarray(mv[:, k]), np.asarray(mr),
                                   rtol=1e-5, atol=1e-4)


def test_gram_stats_feeds_paper_solver():
    """Kernel stats plugged into eq.-3 solve == centralized solve."""
    from repro.core import activations as acts
    from repro.core import centralized_solve_gram
    rng = np.random.default_rng(2)
    n, m = 400, 10
    X = rng.normal(size=(n, m)).astype(np.float32)
    y = rng.integers(0, 2, size=n)
    D = np.asarray(acts.encode_labels(y, 2))
    act = acts.get("logistic")
    dbar = act.f_inv(jnp.asarray(D))
    fp = act.f_prime(dbar)
    Xb = jnp.concatenate([jnp.ones((n, 1)), jnp.asarray(X)], axis=1)
    G, mv = ops.client_gram_stats_fused(Xb, dbar, fp, interpret=True)
    lam = 1e-3
    W = jnp.linalg.solve(G[0] + lam * jnp.eye(m + 1), mv[:, 0])
    W_ref = centralized_solve_gram(X, D[:, 0], act="logistic", lam=lam)
    np.testing.assert_allclose(np.asarray(W), np.asarray(W_ref[:, 0]),
                               rtol=1e-3, atol=1e-4)


# ------------------------------------------------------ shared-F moment
@pytest.mark.parametrize("n,m,c", [(64, 8, 2), (300, 50, 3), (257, 130, 4)])
def test_gram_stats_shared_matches_ref(n, m, c):
    """One pass emits the k=1 Gram AND every moment column (solver TODO:
    the identity path used to discard the kernel moment and re-read X)."""
    rng = np.random.default_rng(hash((n, m, c)) % 2**31)
    X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(n,)), jnp.float32)
    Db = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    G, mv = gram_stats_shared(X, fp, Db, interpret=True)
    assert G.shape == (m, m) and mv.shape == (m, c)
    G_ref, _ = ref.gram_stats_ref(X, fp, Db[:, 0])
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref),
                               rtol=1e-5, atol=1e-4)
    mv_ref = np.asarray(X).T @ (np.asarray(fp)[:, None] ** 2
                                * np.asarray(Db))
    np.testing.assert_allclose(np.asarray(mv), mv_ref,
                               rtol=1e-5, atol=1e-4)


def test_gram_stats_shared_ops_wrapper_identity():
    """ops.client_gram_stats_shared defaults fp to ones (identity act)."""
    rng = np.random.default_rng(11)
    n, m, c = 200, 9, 3
    X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
    Db = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
    G, mv = ops.client_gram_stats_shared(X, Db, interpret=True)
    assert G.shape == (1, m, m) and mv.shape == (m, c)
    np.testing.assert_allclose(np.asarray(G[0]),
                               np.asarray(X).T @ np.asarray(X),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(np.asarray(mv),
                               np.asarray(X).T @ np.asarray(Db),
                               rtol=1e-5, atol=1e-4)


# ------------------------------------------------------- fleet kernels
def test_gram_stats_fleet_bitmatches_per_client():
    """The (p, c, mi, mj, nk) fleet grid replays the per-client kernel's
    tile schedule exactly: every client slice is bitwise identical."""
    rng = np.random.default_rng(12)
    m, c = 20, 3
    ns = [300, 137, 77]
    npad = 512
    Xs = np.zeros((len(ns), npad, m), np.float32)
    Fps = np.zeros((len(ns), npad, c), np.float32)
    Dbs = np.zeros((len(ns), npad, c), np.float32)
    singles = []
    for i, n in enumerate(ns):
        X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        Fp = jnp.asarray(rng.uniform(0.05, 0.25, size=(n, c)), jnp.float32)
        Db = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        singles.append(gram_stats_multi(X, Fp, Db, interpret=True))
        Xs[i, :n], Fps[i, :n], Dbs[i, :n] = X, Fp, Db
    G, mv = gram_stats_fleet(jnp.asarray(Xs), jnp.asarray(Fps),
                             jnp.asarray(Dbs), interpret=True)
    assert G.shape == (len(ns), c, m, m) and mv.shape == (len(ns), m, c)
    for i in range(len(ns)):
        Gi, mvi = singles[i]
        assert np.array_equal(np.asarray(G[i]), np.asarray(Gi))
        assert np.array_equal(np.asarray(mv[i]), np.asarray(mvi))


def test_gram_stats_fleet_shared_bitmatches_per_client():
    rng = np.random.default_rng(13)
    m, c = 14, 2
    ns = [200, 450]
    Xs = np.zeros((2, 512, m), np.float32)
    Fp = np.zeros((2, 512, 1), np.float32)
    Db = np.zeros((2, 512, c), np.float32)
    singles = []
    for i, n in enumerate(ns):
        X = jnp.asarray(rng.normal(size=(n, m)), jnp.float32)
        D = jnp.asarray(rng.normal(size=(n, c)), jnp.float32)
        singles.append(gram_stats_shared(X, jnp.ones((n,), jnp.float32),
                                         D, interpret=True))
        Xs[i, :n], Fp[i, :n, 0], Db[i, :n] = X, 1.0, D
    G, mv = gram_stats_fleet_shared(jnp.asarray(Xs), jnp.asarray(Fp),
                                    jnp.asarray(Db), interpret=True)
    for i in range(2):
        Gi, mvi = singles[i]
        assert np.array_equal(np.asarray(G[i]), np.asarray(Gi))
        assert np.array_equal(np.asarray(mv[i]), np.asarray(mvi))


@pytest.mark.parametrize("shared", [False, True], ids=["k62", "shared"])
def test_gram_stats_fleet_fold_sums_the_clients(shared):
    """The client-folding grid (k, mi, mj, p, nk) writes the sum of what
    the per-client grid writes, on ragged shards (pad rows) with an empty
    pad client, at FEMNIST's k = 62 with a small m (the shared-F path:
    k = 1 and a 62-column moment)."""
    rng = np.random.default_rng(15)
    m, c = 20, 62
    k = 1 if shared else c
    ns = [512, 301, 0, 77, 450]
    npad = 640               # two sample blocks: nk > 1 inside each client
    Xs = np.zeros((len(ns), npad, m), np.float32)
    Fps = np.zeros((len(ns), npad, k), np.float32)
    Dbs = np.zeros((len(ns), npad, c), np.float32)
    for i, n in enumerate(ns):
        Xs[i, :n] = rng.normal(size=(n, m))
        Fps[i, :n] = 1.0 if shared else rng.uniform(0.04, 0.25, (n, k))
        Dbs[i, :n] = rng.normal(size=(n, c))
    fleet = gram_stats_fleet_shared if shared else gram_stats_fleet
    args = (jnp.asarray(Xs), jnp.asarray(Fps), jnp.asarray(Dbs))
    G, mv = fleet(*args, interpret=True)
    Gf, mvf = fleet(*args, interpret=True, fold=True)
    assert Gf.shape == G.shape[1:] and mvf.shape == (m, c)
    # float32 sums in another order: the folded block adds every
    # client's tiles into one accumulator, the per-client form sums
    # whole blocks after; over at most a bucket of clients the two stay
    # within a few float32 ulps of the largest entry (2**-23 ≈ 1.2e-7)
    for got, per in ((Gf, G), (mvf, mv)):
        want = np.asarray(per, np.float64).sum(0)
        np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                   atol=1e-6 * np.abs(want).max())
    # the pad client and the pad rows add exactly nothing
    keep = [i for i, n in enumerate(ns) if n]
    G2, mv2 = fleet(*(a[np.asarray(keep)] for a in args), interpret=True,
                    fold=True)
    assert np.array_equal(np.asarray(G2), np.asarray(Gf))
    assert np.array_equal(np.asarray(mv2), np.asarray(mvf))


# ----------------------------------------------------------- decode attn
@pytest.mark.parametrize("b,hq,hkv,hd,S", [
    (2, 8, 2, 64, 1024), (1, 9, 3, 64, 513), (2, 16, 16, 128, 300),
    (1, 8, 1, 128, 2048),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_gqa_matches_ref(b, hq, hkv, hd, S, dtype):
    rng = np.random.default_rng(hash((b, hq, S)) % 2**31)
    q = jnp.asarray(rng.normal(size=(b, hq, hd)), dtype)
    k = jnp.asarray(rng.normal(size=(b, S, hkv, hd)), dtype)
    v = jnp.asarray(rng.normal(size=(b, S, hkv, hd)), dtype)
    kv_len = S - 7
    out = decode_gqa(q, k, v, kv_len, interpret=True, block_s=256)
    out_ref = ref.decode_gqa_ref(q, k, v, kv_len)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               rtol=tol, atol=tol * 10)


def test_decode_gqa_kv_len_masking():
    """Entries past kv_len must not affect the output."""
    rng = np.random.default_rng(5)
    b, hq, hkv, hd, S = 1, 4, 2, 64, 512
    q = jnp.asarray(rng.normal(size=(b, hq, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, S, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, S, hkv, hd)), jnp.float32)
    out1 = decode_gqa(q, k, v, 100, interpret=True, block_s=128)
    k2 = k.at[:, 100:].set(999.0)
    v2 = v.at[:, 100:].set(-999.0)
    out2 = decode_gqa(q, k2, v2, 100, interpret=True, block_s=128)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               rtol=1e-6, atol=1e-6)
