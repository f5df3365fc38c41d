"""Shared array-shaping helpers for the federation stack.

These used to exist as three private copies (``solver._add_bias``,
``sharded._as_2d``, and per-callsite ``D[:, None]`` reshapes); the wire /
engine layers and the solver all share this single pair now.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def add_bias(X: jnp.ndarray) -> jnp.ndarray:
    """Prepend the bias column of ones: ``(n, m) -> (n, m+1)``."""
    ones = jnp.ones((X.shape[0], 1), dtype=X.dtype)
    return jnp.concatenate([ones, X], axis=1)


def as_2d(D) -> jnp.ndarray:
    """Targets as ``(n, c)``: a 1-D label/target vector becomes one column."""
    D = jnp.asarray(D)
    return D[:, None] if D.ndim == 1 else D


def enable_x64(on: bool = True):
    """Context manager scoping ``jax_enable_x64`` (int64 limbs, float64
    references): ``with enable_x64(): ...``."""
    return jax.enable_x64(on)
