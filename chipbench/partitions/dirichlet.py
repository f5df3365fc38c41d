"""``dirichlet``: Dir(α) label skew (Hsu et al., arXiv:1909.06335), as
``data/partition.py``'s ``dirichlet`` draws it, with its proportions
drawn from the workload's fixed ``partition_seed``, so that every run
seed gets the same shard sizes (up to its label counts), in another
client order."""
from typing import List, Optional

import numpy as np

from chipbench import datagen

CHUNK_ROWS = 1 << 20


def indices(y: np.ndarray, P: int, alpha: float, rng: np.random.Generator,
            prop_rng: Optional[np.random.Generator] = None
            ) -> List[np.ndarray]:
    """Row indices of each client: ``data/partition.py``'s ``dirichlet``
    draw for draw, without its per-row Python lists. With ``prop_rng``
    every class's proportions come from it, drawn first, and only the
    permutations from ``rng``: the sizes then hardly depend on ``rng``."""
    classes = np.unique(y)
    props = None if prop_rng is None else \
        [prop_rng.dirichlet(np.full(P, alpha)) for _ in classes]
    owner = np.empty(len(y), np.int64)
    for j, c in enumerate(classes):
        idx = rng.permutation(np.where(y == c)[0])
        pr = rng.dirichlet(np.full(P, alpha)) if props is None else props[j]
        cuts = (np.cumsum(pr)[:-1] * len(idx)).astype(int)
        sizes = np.diff(np.concatenate([[0], cuts, [len(idx)]]))
        owner[idx] = np.repeat(np.arange(P), sizes)
    order = np.argsort(owner, kind="stable")
    counts = np.bincount(owner, minlength=P)
    out = np.split(order, np.cumsum(counts)[:-1])
    for p in range(P):
        if len(out[p]) == 0:   # as the repository's: one random sample
            out[p] = np.array([rng.integers(len(y))])
    return out


def split(src, n_total: int, P: int, wl: dict):
    """The whole table is drawn on the device in chunks and read back
    once; the partition is a host computation over the labels, and each
    client's shard goes to the device on its own, as a client's data
    would."""
    import jax
    Xs, ys = [], []
    for i, lo in enumerate(range(0, n_total, CHUNK_ROWS)):
        X, _, y = src.rows(i, min(CHUNK_ROWS, n_total - lo))
        Xs.append(np.asarray(X))
        ys.append(np.asarray(y))
        del X, y
    X, y = np.concatenate(Xs), np.concatenate(ys)
    del Xs, ys
    D = datagen.encode(y, src.classes)
    idx = indices(y, P, float(wl["alpha"]), src.rng,
                  prop_rng=np.random.default_rng(int(wl["partition_seed"])))
    order = src.rng.permutation(P)          # the run seed's client order
    host = [(X[idx[p]], D[idx[p]]) for p in order]
    dev = jax.device_put(host)
    return [a for a, _ in dev], [b for _, b in dev]
