"""The control: the eq.-3 solve with its products in bfloat16.

The configurations state float32 statistics (``FederationEngine``'s
``dtype``); the program computes them with every contraction at
``Precision.HIGHEST``. The control is the reference's algebra, put in the
program's place, with each contraction's operands rounded to bfloat16 and
the products summed in float32: what the MXU does at the default
precision, the step a later change would be tempted to take. Rounding is
by ``reduce_precision``, not a round trip through ``bfloat16``, which XLA
may drop as excess precision. The solve is a float32 Cholesky factor.

Two diagnostics sit beside it, read by ``calibrate.py`` only: the same
algebra at three bf16 passes (``Precision.HIGH``: ``a_hi·b_hi +
a_hi·b_lo + a_lo·b_hi``) and at the stated precision.

``correct`` has to come out false for the control: ``calibrate.py`` reads
it on the chip at each cell's size, through the comparison a run makes,
and ``tests/chipbench`` at a small size.
"""
from __future__ import annotations

import functools

import numpy as np


def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def to_bf16(x):
    """``x`` rounded to bfloat16's precision, kept in float32."""
    jax, _ = _jax()
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _exact(spec, a, b):
    # operands with at most 8 significant bits: float32 products are exact
    jax, jnp = _jax()
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def dot_bf16(spec: str, a, b):
    """``einsum(spec, a, b)`` in one bf16 pass, summed in float32."""
    return _exact(spec, to_bf16(a), to_bf16(b))


def dot_three_pass(spec: str, a, b):
    """``einsum(spec, a, b)`` in three bf16 passes (``Precision.HIGH``)."""
    ah, bh = to_bf16(a), to_bf16(b)
    al, bl = to_bf16(a - ah), to_bf16(b - bh)
    return _exact(spec, ah, bh) + _exact(spec, ah, bl) + _exact(spec, al, bh)


def dot_highest(spec: str, a, b):
    """``einsum(spec, a, b)`` at the stated precision."""
    jax, jnp = _jax()
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


@functools.lru_cache(maxsize=None)
def _programs(dot):
    jax, jnp = _jax()

    @jax.jit
    def block(X, D):
        Xb = jnp.concatenate([jnp.ones((X.shape[0], 1), X.dtype), X], 1)
        fp = D * (1.0 - D)
        dbar = jnp.log(D / (1.0 - D))
        XF = fp.T[:, :, None] * Xb[None]                 # (c, n, m)
        return (dot("knm,knp->kmp", XF, XF),
                dot("nm,nc->mc", Xb, fp * fp * dbar))

    @jax.jit
    def solve(G, M, lam):
        eye = jnp.eye(G.shape[-1], dtype=G.dtype)

        def one(Gk, mk):
            return jax.scipy.linalg.cho_solve(
                jax.scipy.linalg.cho_factor(Gk + lam * eye), mk)
        return jax.vmap(one, in_axes=(0, 1), out_axes=1)(G, M)

    return block, solve


def control(blocks, lam: float, dot=dot_bf16):
    """``(W, (G, M, n))`` of the eq.-3 solve over ``blocks`` of device
    ``(X, D)``, the statistics summed in float32 one block at a time."""
    block, solve = _programs(dot)
    G = M = None
    n = 0
    for X, D in blocks:
        g, mm = block(X, D)
        G = g if G is None else G + g
        M = mm if M is None else M + mm
        n += int(X.shape[0])
    return np.asarray(solve(G, M, lam), np.float64), (G, M, float(n))
