"""``pathological``: McMahan et al. (arXiv:1602.05629 §3). Rows sorted by
label, cut into ``shards_per_client·P`` shards, dealt at random,
``shards_per_client`` to a client."""
import numpy as np


def split(src, n_total: int, P: int, wl: dict):
    import jax
    import jax.numpy as jnp
    per = int(wl.get("shards_per_client", 2))
    X, D, y = src.rows(0, n_total)
    order = np.argsort(np.asarray(y), kind="stable")
    n_shards = per * P
    size = n_total // n_shards
    shards = order[:n_shards * size].reshape(n_shards, size)
    deal = src.rng.permutation(n_shards).reshape(P, per)
    idx = jnp.asarray(shards[deal].reshape(P, per * size), jnp.int32)
    take = jax.jit(lambda A, I: [A[I[i]] for i in range(P)])
    return take(X, idx), take(D, idx)
