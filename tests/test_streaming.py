"""Streaming-client equivalence (paper Fig. 1 + eq. 10)."""
import numpy as np
import jax
from repro.core.util import enable_x64 as jax_enable_x64
import jax.numpy as jnp

from repro.core import activations as acts
from repro.core import (centralized_solve_gram, client_stats, merge_many,
                        solve_weights)
from repro.core.streaming import StreamingClient
from repro.data import synthetic


def test_chunkwise_ingest_equals_batch():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 8)).astype(np.float32)
    D = rng.uniform(0.1, 0.9, size=(300, 2)).astype(np.float32)
    with jax_enable_x64(True):
        c = StreamingClient(act="logistic", dtype=jnp.float64)
        for lo in range(0, 300, 37):          # uneven chunks
            c.ingest(X[lo:lo + 37], D[lo:lo + 37])
        W_stream = solve_weights(c.upload(), 1e-3)
        W_batch = solve_weights(
            client_stats(X, D, act="logistic", dtype=jnp.float64), 1e-3)
    np.testing.assert_allclose(np.asarray(W_stream), np.asarray(W_batch),
                               rtol=1e-8, atol=1e-10)
    assert c.n_seen == 300


def test_streaming_memory_bounded():
    """O(m·r) state no matter how much data streams through."""
    rng = np.random.default_rng(1)
    m = 10
    c = StreamingClient(act="identity")
    sizes = []
    for _ in range(6):
        X = rng.normal(size=(500, m)).astype(np.float32)
        D = rng.uniform(-0.8, 0.8, size=(500, 1)).astype(np.float32)
        c.ingest(X, D)
        sizes.append(c.memory_floats)
    # rank caps at m+1 after the first chunk: state stops growing
    assert len(set(sizes[1:])) == 1
    assert sizes[-1] <= (m + 1) ** 2 + 2 * (m + 1)


def test_streaming_clients_federate_to_centralized():
    X, y = synthetic.generate("susy", scale=4e-4, seed=2)
    D = np.asarray(acts.encode_labels(y, 2))
    # 4 streaming clients, each fed 3 chunks
    quarters = np.array_split(np.arange(len(y)), 4)
    ups = []
    for q in quarters:
        c = StreamingClient()
        for chunk in np.array_split(q, 3):
            c.ingest(X[chunk], D[chunk])
        ups.append(c.upload())
    W_fed = solve_weights(merge_many(ups), 1e-3)
    W_cen = centralized_solve_gram(X, D, act="logistic", lam=1e-3)
    np.testing.assert_allclose(np.asarray(W_fed), np.asarray(W_cen),
                               rtol=5e-3, atol=5e-4)
