"""Paper-claim validation tests for the core single-round FL method.

Claims under test (paper §3–§4):
  C1  federated solution == centralized solution (any #clients)
  C2  IID partitioning and pathological non-IID give the SAME model
  C3  incremental client admission == batch aggregation
  C4  sequential (Alg. 2 literal) == tree merge
  C5  exactly one aggregation round regardless of P
  C6  multi-output extension consistent with per-output solves
  C7  accuracy is competitive vs an iterative centralized baseline
"""
import numpy as np
import jax
from repro.core.util import enable_x64 as jax_enable_x64
import jax.numpy as jnp
import pytest

from repro.core import (FedONNCoordinator, FedONNClient, fed_fit,
                        centralized_solve_gram, client_stats, merge_stats,
                        merge_many, predict, predict_labels, solve_weights,
                        client_gram_stats, merge_gram, solve_weights_gram)
from repro.core import activations as acts
from repro.data import partition, synthetic


def _toy(n=600, m=12, classes=2, seed=0):
    spec = synthetic.DatasetSpec("toy", n, m, classes)
    X, y = synthetic.generate(spec, seed=seed)
    D = acts.encode_labels(y, classes)
    return X, y, np.asarray(D)


# ---------------------------------------------------------------- C1
@pytest.mark.parametrize("P", [1, 2, 5, 17])
@pytest.mark.parametrize("act", ["logistic", "identity", "tanh"])
def test_federated_equals_centralized(P, act):
    X, y, D = _toy()
    W_central = centralized_solve_gram(X, D, act=act, lam=1e-3)
    parts = partition.iid(X, y, P, seed=1)
    # re-encode targets per part
    pX = [p[0] for p in parts]
    pD = [acts.encode_labels(p[1], D.shape[1]) for p in parts]
    W_fed = fed_fit(pX, pD, act=act, lam=1e-3)
    np.testing.assert_allclose(np.asarray(W_fed), np.asarray(W_central),
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------- C2
@pytest.mark.parametrize("act", ["logistic", "identity"])
def test_iid_equals_noniid_fp32(act):
    # fp32: partition order only changes SVD rounding (≲1e-3 abs drift)
    X, y, D = _toy(n=800)
    c = D.shape[1]

    def fit(parts):
        return fed_fit([p[0] for p in parts],
                       [acts.encode_labels(p[1], c) for p in parts],
                       act=act, lam=1e-3)

    W_iid = fit(partition.iid(X, y, 8, seed=3))
    W_path = fit(partition.pathological(X, y, 8))
    W_dir = fit(partition.dirichlet(X, y, 8, alpha=0.1, seed=3))
    np.testing.assert_allclose(np.asarray(W_iid), np.asarray(W_path),
                               rtol=5e-2, atol=5e-3)
    np.testing.assert_allclose(np.asarray(W_iid), np.asarray(W_dir),
                               rtol=5e-2, atol=5e-3)


def test_iid_equals_noniid_fp64_exact():
    # fp64: the algebraic claim — partitioning does not change the model
    X, y, _ = _toy(n=400)
    with jax_enable_x64(True):
        def fit(parts):
            stats = [client_stats(p[0].astype(np.float64),
                                  np.asarray(acts.encode_labels(p[1], 2),
                                             dtype=np.float64),
                                  act="logistic", dtype=jnp.float64)
                     for p in parts]
            return solve_weights(merge_many(stats), 1e-3)

        W_iid = fit(partition.iid(X, y, 8, seed=3))
        W_path = fit(partition.pathological(X, y, 8))
        W_cen = centralized_solve_gram(X.astype(np.float64),
                                       np.asarray(acts.encode_labels(y, 2),
                                                  dtype=np.float64),
                                       act="logistic", dtype=jnp.float64)
    np.testing.assert_allclose(np.asarray(W_iid), np.asarray(W_path),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(W_iid), np.asarray(W_cen),
                               rtol=1e-7, atol=1e-9)


# ---------------------------------------------------------------- C3
def test_incremental_admission_matches_batch():
    X, y, D = _toy()
    parts = partition.iid(X, y, 6, seed=2)
    stats = [client_stats(p[0], acts.encode_labels(p[1], D.shape[1]))
             for p in parts]

    batch = FedONNCoordinator(lam=1e-3)
    batch.add_many(stats)
    W_batch = batch.solve()

    # clients 0..3 first; 4,5 arrive later (paper: dynamic client addition)
    late = FedONNCoordinator(lam=1e-3)
    late.add_many(stats[:4])
    _ = late.solve()            # model already usable after 4 clients
    late.add(stats[4])
    late.add(stats[5])
    W_late = late.solve()
    np.testing.assert_allclose(np.asarray(W_late), np.asarray(W_batch),
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------- C4
def test_tree_equals_sequential_equals_oneshot():
    X, y, D = _toy()
    parts = partition.iid(X, y, 7, seed=5)
    stats = [client_stats(p[0], acts.encode_labels(p[1], D.shape[1]))
             for p in parts]
    seq = FedONNCoordinator(); seq.add_many(stats, tree=False)
    tre = FedONNCoordinator(); tre.add_many(stats, tree=True)
    one = solve_weights(merge_many(stats), 1e-3)
    np.testing.assert_allclose(np.asarray(seq.solve()),
                               np.asarray(tre.solve()),
                               rtol=2e-3, atol=2e-4)
    np.testing.assert_allclose(np.asarray(one), np.asarray(tre.solve()),
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------- C5
def test_single_round():
    X, y, D = _toy()
    parts = partition.iid(X, y, 16, seed=0)
    coord = FedONNCoordinator()
    coord.add_many([client_stats(p[0], acts.encode_labels(p[1], 2))
                    for p in parts])
    assert coord.rounds == 1   # one aggregation pass, P=16 clients


# ---------------------------------------------------------------- C6
def test_multi_output_consistent_with_per_output():
    X, y, D = _toy(classes=3)
    W = centralized_solve_gram(X, D, act="logistic")
    for k in range(D.shape[1]):
        Wk = centralized_solve_gram(X, D[:, k], act="logistic")
        np.testing.assert_allclose(np.asarray(W[:, k]),
                                   np.asarray(Wk[:, 0]),
                                   rtol=1e-4, atol=1e-5)


# ------------------------------------------------------- gram wire format
def test_gram_wire_format_matches_svd():
    X, y, D = _toy()
    parts = partition.iid(X, y, 5, seed=9)
    gs = [client_gram_stats(p[0], acts.encode_labels(p[1], 2))
          for p in parts]
    agg = gs[0]
    for g in gs[1:]:
        agg = merge_gram(agg, g)
    W_gram = solve_weights_gram(agg, 1e-3)
    W_central = centralized_solve_gram(X, D)
    np.testing.assert_allclose(np.asarray(W_gram), np.asarray(W_central),
                               rtol=2e-3, atol=2e-4)


# ---------------------------------------------------------------- C7
def test_accuracy_competitive():
    spec = synthetic.DatasetSpec("bench", 4000, 18, 2)
    X, y = synthetic.generate(spec, seed=7)
    (Xtr, ytr), (Xte, yte) = synthetic.train_test_split(X, y)
    D = acts.encode_labels(ytr, 2)
    parts = partition.pathological(Xtr, ytr, 50)
    W = fed_fit([p[0] for p in parts],
                [acts.encode_labels(p[1], 2) for p in parts],
                act="logistic", lam=1e-3)
    pred = predict_labels(W, Xte, act="logistic")
    acc = float((np.asarray(pred) == yte).mean())
    # linear-separable component of the synthetic boundary ⇒ well above chance
    assert acc > 0.70, acc


def test_predict_shapes_and_finite():
    X, y, D = _toy(classes=4)
    W = centralized_solve_gram(X, D, act="logistic")
    out = predict(W, X, act="logistic")
    assert out.shape == (X.shape[0], 4)
    assert bool(jnp.isfinite(out).all())
