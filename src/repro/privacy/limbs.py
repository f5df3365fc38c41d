"""Jittable ring arithmetic over Z_{2^mod_bits} as int64 limb ops.

:mod:`.secagg` stores ring elements as little-endian base-``2^32``
limb arrays (int64 words, lazily carried) and encodes/merges them
host-side in numpy. That is exact but keeps masking off the fused and
mesh fast paths: a masked upload could not ride the engine's single
stats→merge→solve program, and a mesh device could not mask before its
psum. This module is the same algebra as traceable JAX ops, bit-for-bit
(property-tested in ``tests/test_limbs.py``):

* :func:`encode_limbs`      — the vectorized exact dyadic encoding
  (``SecAggSession._encode_leaves``'s frexp/mantissa-scatter, jitted),
* :func:`encode_tree`       — a stats pytree (optionally with a leading
  client axis) → one flat ``(…, n_elems, words)`` limb array in the
  session template's leaf order,
* :func:`add_limbs` / :func:`negate_limbs` / :func:`sum_limbs` — lazy
  ring algebra: plain int64 adds, no carries,
* :func:`carry_limbs`       — full carry normalization (the mirror of
  ``SecAggSession._carry`` as one ``lax.scan``), after which every limb
  is a clean base-2^32 digit and the host can decode.

Everything here requires x64 mode (``repro.core.util.enable_x64`` —
the engine wraps its masked programs in it): the lazy-carry
representation needs genuine int64 headroom, and the encoding needs the
full float64 mantissa. The f32 wire statistics themselves are
unaffected — JAX's weak-typing keeps explicitly-dtyped f32 programs
bit-identical under x64 (pinned by the conformance suite).

Int64 headroom bounds the fleet sizes the device-side ring sum may
take before normalizing: an encoded limb is < 2^34 and a cached
per-client pad sum is < (P−1)·2^32, so summing P uploads stays below
``P·(2^34 + P·2^32) ≤ 2^63`` for ``P ≤ 2^14`` — comfortably past any
in-process federation here; :func:`check_fleet_headroom` enforces it
loudly rather than wrapping silently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.ledger import _SHIFT

_LIMB_BITS = 32
_MASK32 = 0xFFFFFFFF
# see module docstring: largest fleet whose lazy ring sum provably
# fits int64 without intermediate carries
MAX_RING_SUMMANDS = 1 << 14


def require_x64(where: str = "limb ops") -> None:
    """Loud precondition: the jitted ring algebra is int64-only."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            f"{where} need int64 limbs: wrap the call in "
            "repro.core.util.enable_x64() (the engine's masked fused/"
            "mesh programs do this for you)")


def check_fleet_headroom(n_summands: int) -> None:
    """Reject ring sums whose lazy int64 limbs could overflow."""
    if n_summands > MAX_RING_SUMMANDS:
        raise ValueError(
            f"{n_summands} masked uploads in one device-side ring sum "
            f"exceeds the int64 lazy-carry headroom (max "
            f"{MAX_RING_SUMMANDS}); use the host loop path, which "
            "carry-normalizes incrementally")


def encode_limbs(x, words: int):
    """Exact dyadic-integer limbs of a float array, traceable.

    ``(…,) float → (…, words) int64`` — the same ring element as
    ``SecAggSession._encode_leaves``: after carry normalization the
    limb digits (and hence every decode) are bit-identical to the host
    encoder's. The *lazy* limbs may decompose differently — the host
    scatters a frexp-normalized 53-bit mantissa; here the IEEE bit
    pattern is taken apart directly (sign / exponent / fraction via
    integer bitcast, ``value = mant · 2^(shift − 1074)``), because any
    float *arithmetic* on device risks XLA's flush-to-zero eating f32
    subnormal statistics that numpy's widening cast preserves. Pure
    integer ops are FTZ-proof. Non-finite inputs are the caller's
    contract, as on the host path (the engine only ever encodes finite
    statistics; the conformance suite pins the refusal host-side).
    """
    require_x64("masked encodes")
    x = jnp.asarray(x)
    shape = x.shape
    if x.dtype == jnp.float64:
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1), jnp.uint64).astype(jnp.int64)
        # value = mant · 2^(max(expo,1) − 1075); +_SHIFT ⇒ bias −1
        frac_bits, exp_mask, shift_bias = 52, 0x7FF, -1
    else:
        if x.dtype != jnp.float32:
            # exotic float dtypes widen first (exact for finite values;
            # no wire currently rides them)
            return encode_limbs(x.astype(jnp.float64), words)
        bits = jax.lax.bitcast_convert_type(
            x.reshape(-1), jnp.uint32).astype(jnp.int64)
        # value = mant · 2^(max(expo,1) − 150); +_SHIFT ⇒ bias 924
        frac_bits, exp_mask, shift_bias = 23, 0xFF, _SHIFT - 150
    frac = bits & ((1 << frac_bits) - 1)
    expo = (bits >> frac_bits) & exp_mask
    sign = 1 - 2 * ((bits >> (8 * x.dtype.itemsize - 1)) & 1)
    # normals carry the implicit leading bit; subnormals read off the
    # bare fraction at the minimum exponent — both give the exact
    # integer mant with value = mant · 2^(shift − _SHIFT), shift ≥ 0
    mant = frac | ((expo > 0).astype(jnp.int64) << frac_bits)
    shift = jnp.maximum(expo, 1) + shift_bias
    word = shift // _LIMB_BITS
    r = shift % _LIMB_BITS
    lo = (mant & _MASK32) << r                      # ≤ 63 bits
    hi = (mant >> 32) << r
    rows = jnp.arange(bits.shape[0])
    limbs = jnp.zeros((bits.shape[0], words), jnp.int64)
    limbs = limbs.at[rows, word].add(lo & _MASK32)
    limbs = limbs.at[rows, word + 1].add((lo >> 32) + (hi & _MASK32))
    limbs = limbs.at[rows, word + 2].add(hi >> 32)
    limbs = limbs * sign[:, None]
    return limbs.reshape(shape + (words,))


def encode_tree(stats, words: int, stacked: bool = False):
    """A stats pytree → one flat ``(n_elems, words)`` limb array.

    Leaves flatten in tree order — the same order
    ``SecAggSession._bind`` fixes for the template, so the result is
    directly comparable to (and decodable by) the host session. With
    ``stacked=True`` the leaves carry a leading client axis and the
    result is ``(P, n_elems, words)``: one encoded upload per row.
    """
    leaves = jax.tree_util.tree_leaves(stats)
    if not leaves:
        raise ValueError("cannot encode an empty stats tree")
    if stacked:
        P = leaves[0].shape[0]
        parts = [encode_limbs(lf, words).reshape(P, -1, words)
                 for lf in leaves]
        return jnp.concatenate(parts, axis=1)
    parts = [encode_limbs(lf, words).reshape(-1, words)
             for lf in leaves]
    return jnp.concatenate(parts, axis=0)


def add_limbs(a, b):
    """Lazy ring add: plain int64 limb addition, carries deferred."""
    return a + b


def negate_limbs(a):
    """Ring negation (the lazy representation holds signed limbs)."""
    return -a


def sum_limbs(stacked, axis: int = 0):
    """Ring sum over one axis (e.g. the client axis of a masked fused
    bucket) — order-independent by associativity of integer addition."""
    return jnp.sum(stacked, axis=axis)


def carry_limbs(limbs):
    """Full carry propagation, traceable: lazy int64 limbs → clean
    base-2^32 digits in ``[0, 2^32)``.

    The mirror of ``SecAggSession._carry`` as one ``lax.scan`` over the
    word axis; the top word's carry wraps off the ring, so the value
    mod ``2^mod_bits`` is unchanged. After this, the host can decode
    the aggregate with zero further limb work.
    """
    require_x64("carry normalization")
    x = jnp.moveaxis(jnp.asarray(limbs), -1, 0)     # (words, …)

    def step(carry, v):
        v = v + carry
        c = v >> _LIMB_BITS
        return c, v - (c << _LIMB_BITS)

    _, out = jax.lax.scan(step, jnp.zeros(x.shape[1:], x.dtype), x)
    return jnp.moveaxis(out, 0, -1)
