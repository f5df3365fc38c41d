"""Jit'd public wrappers around the Pallas kernels.

Off-TPU the kernels run with ``interpret=True`` (Pallas executes the
kernel body with XLA ops, for correctness validation); on a TPU they
compile with Mosaic. ``interpret=None`` resolves by the default backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import gram_stats as _gram
from . import decode_attn as _dec


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def client_gram_stats_fused(X, D_bar, Fp, *, interpret=None):
    """Multi-output fused client statistics via the Pallas kernel.

    X: (n, m) with bias column; D_bar: (n, c) pre-activation targets;
    Fp: (n, c) per-output diagonal of F. Returns (G (c, m, m), mvec (m, c)).

    One pallas_call with a leading class grid dimension (DESIGN.md §3.2),
    the same kernel as the fleet path, so a fleet slice bit-matches it.
    """
    interpret = _default_interpret() if interpret is None else interpret
    return _gram.gram_stats_multi(X, Fp, D_bar, interpret=interpret)


def client_gram_stats_shared(X, D_bar, fp=None, *, interpret=None):
    """Shared-F (k = 1) client statistics with a c-column moment.

    X: (n, m) with bias column; D_bar: (n, c); fp: (n,) shared F diagonal
    (defaults to ones — the identity activation). Returns
    (G (1, m, m), mvec (m, c)) from ONE kernel pass — X is read once for
    both the Gram and every moment column (the identity path no longer
    discards the kernel moment and recomputes ``Xᵀ d̄`` densely).
    """
    interpret = _default_interpret() if interpret is None else interpret
    if fp is None:
        fp = jnp.ones((X.shape[0],), X.dtype)
    G, mv = _gram.gram_stats_shared(X, fp, D_bar, interpret=interpret)
    return G[None], mv


def client_gram_stats_fleet(Xs, D_bars, Fps, *, shared: bool = False,
                            fold: bool = False, interpret=None):
    """Fleet-batched client statistics: one pallas_call for P clients.

    Xs: (P, n_max, m) stacked, zero-padded client data (bias column
    already applied, 0 on pad rows); D_bars: (P, n_max, c); Fps:
    (P, n_max, c) per-output F diagonals, or (P, n_max, 1) with
    ``shared=True`` for the shared-F path (1 on real rows, 0 on pads).
    Returns (G (P, k, m, m), mvec (P, m, c)) with k = c (per-output) or
    k = 1 (shared); with ``fold=True`` their sums over the P clients,
    (G (k, m, m), mvec (m, c)), folded inside the kernel.
    """
    interpret = _default_interpret() if interpret is None else interpret
    if shared:
        G, mv = _gram.gram_stats_fleet_shared(Xs, Fps, D_bars, fold=fold,
                                              interpret=interpret)
        return (G[None] if fold else G[:, None]), mv
    return _gram.gram_stats_fleet(Xs, Fps, D_bars, fold=fold,
                                  interpret=interpret)


def decode_gqa(q, k, v, kv_len, *, interpret=None, block_s: int = 512):
    """Flash-decode GQA attention (one token vs a long KV cache)."""
    interpret = _default_interpret() if interpret is None else interpret
    return _dec.decode_gqa(q, k, v, kv_len, interpret=interpret,
                           block_s=block_s)
