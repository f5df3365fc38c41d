"""``writers``: LEAF's FEMNIST writers (Caldas et al., arXiv:1812.01097,
Table 1: 226.83 ± 88.94 samples a device).

The writers' sizes come from the workload's fixed ``partition_seed``: a
lognormal draw of LEAF's mean and sd (``writer_mean``, ``writer_sd``),
scaled to that mean and sd exactly (a sample of a few hundred writers
would otherwise stray by about 6 % in its sd), rounded, at least one row
each. Client ``i`` is the ``i``-th writer drawn, so every run seed has
the same sizes in the same places: each edge aggregator of the tier tree
folds the same writers' sizes, and a round does the same work (the same
power-of-two buckets) under every seed. The run seed draws the rows and
deals them to the writers.

A writer federation's rows are its writers' sizes: ``n_total``, the
configuration's whole table, is not cut from.
"""
import numpy as np

CHUNK_ROWS = 1 << 20


def sizes(P: int, mean: float, sd: float,
          rng: np.random.Generator) -> np.ndarray:
    """``P`` writer sizes of the given mean and sd, each at least 1."""
    sigma2 = np.log1p((sd / mean) ** 2)
    x = rng.lognormal(np.log(mean) - sigma2 / 2, np.sqrt(sigma2), P)
    if P > 1:
        x = mean + (x - x.mean()) * (sd / x.std())
    return np.maximum(np.rint(x), 1).astype(np.int64)


def split(src, n_total: int, P: int, wl: dict):
    """The writers' rows are drawn on the device in chunks and read back
    once; each writer's shard goes to the device on its own, as a
    client's data would."""
    import jax
    n = sizes(P, float(wl["writer_mean"]), float(wl["writer_sd"]),
              np.random.default_rng(int(wl["partition_seed"])))
    total = int(n.sum())
    Xs, Ds = [], []
    for i, lo in enumerate(range(0, total, CHUNK_ROWS)):
        X, D, _ = src.rows(i, min(CHUNK_ROWS, total - lo))
        Xs.append(np.asarray(X))
        Ds.append(np.asarray(D))
        del X, D
    X, D = np.concatenate(Xs), np.concatenate(Ds)
    del Xs, Ds
    rows = src.rng.permutation(total)
    host = [(X[r], D[r]) for r in np.split(rows, np.cumsum(n)[:-1])]
    dev = jax.device_put(host)
    return [a for a, _ in dev], [b for _, b in dev]
