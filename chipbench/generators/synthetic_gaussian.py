"""``synthetic_gaussian``: the synthetic generator the repository uses for
its UCI stand-ins, redrawn with ``jax.random`` on the device.

Two or more anisotropic Gaussian classes, labels flipped in a quadratic
region so that a linear model is good but not perfect. Two departures,
both stated in each configuration's ``assumed``: the flip threshold is
the quantile of a 2**20-row pilot sample rather than of the whole table
(so that shards can be drawn one at a time), and the random streams are
JAX's, not numpy's.

Targets use the program's label encoding for the logistic activation:
one-hot scaled into (0.05, 0.95).
"""
from __future__ import annotations

import functools

import numpy as np

from chipbench import datagen

PILOT_ROWS = 1 << 20


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


@functools.lru_cache(maxsize=None)
def _programs(m: int, classes: int, sep: float):
    jax, jnp = _jax()

    @jax.jit
    def params(key):
        km, ks = jax.random.split(key)
        means = jax.random.normal(km, (classes, m)) * (sep / np.sqrt(m))
        scales = 0.5 + jax.random.uniform(ks, (m,))
        return means, scales

    def raw(key, means, scales, n):
        ky, kx, kf = jax.random.split(key, 3)
        y = jax.random.randint(ky, (n,), 0, classes)
        X = jax.random.normal(kx, (n, m)) * scales + means[y]
        h = m // 2
        q = (X[:, :h] ** 2).sum(1) - (X[:, h:] ** 2).sum(1)
        return X, y, q, jax.random.uniform(kf, (n,))

    @functools.partial(jax.jit, static_argnames=("n", "quantile"))
    def threshold(key, means, scales, n, quantile):
        return jnp.quantile(raw(key, means, scales, n)[2], quantile)

    @functools.partial(jax.jit, static_argnames=("n",))
    def rows(key, means, scales, thr, n):
        X, y, q, u = raw(key, means, scales, n)
        y = jnp.where((q > thr) & (u < 0.5), classes - 1 - y, y)
        D = (jax.nn.one_hot(y, classes, dtype=jnp.float32)
             * (datagen.HIGH - datagen.LOW) + datagen.LOW)
        return X.astype(jnp.float32), D, y.astype(jnp.int32)

    return params, threshold, rows


class Source:
    """The configuration's table as a function of ``(seed, stream, index)``."""

    def __init__(self, config: dict, seed: int):
        jax, _ = _jax()
        self.m, self.classes = int(config["features"]), int(config["classes"])
        params, threshold, self._rows = _programs(
            self.m, self.classes, float(config["sep"]))
        s32, self.rng = datagen.seeds(seed)
        base = jax.random.key(s32)
        kp, kt, self._kr, self._kv = jax.random.split(base, 4)
        self.means, self.scales = params(kp)
        self.thr = threshold(kt, self.means, self.scales, n=PILOT_ROWS,
                             quantile=1.0 - float(config["nonlin"]) * 0.25)

    def rows(self, index: int, n: int, stream: str = "rows"):
        """Rows ``(X (n, m), D (n, c), y (n,))`` of one block, on the device."""
        jax, _ = _jax()
        key = jax.random.fold_in(self._kr if stream == "rows" else self._kv,
                                 int(index))
        return self._rows(key, self.means, self.scales, self.thr, n=n)
