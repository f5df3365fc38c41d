"""The plain eq.-3 solve in float64 numpy, and the numbers that decide
``correct``.

For every class k, ``(X̃ᵀ F_k² X̃ + λI) w_k = X̃ᵀ (F_k² d̄_k)`` with the
bias column first, ``d̄ = logit(D)`` and ``F = diag(D(1 − D))``, the
derivative of the logistic at ``d̄``. The Gram and the moments are summed
one client block at a time, so that the reference fits in the host's
memory at any federation size; inside a block, rows that share a target
row share every weight, so each distinct target row costs one Gram.

Two numbers are compared: ``rel_err_W``, the committed ``W`` against
the float64 ``W``, and ``rel_err_stats``, the statistics that ``W`` was
solved from against the float64 statistics, by the worst of their leaves
(``G``, the moments, the row count). The first is what a user gets; the
second sees what ``W`` cannot at this size: rows left out or kept past
their leave or revision, which move ``W`` by no more than sampling noise
when the rows are alike.

It imports nothing of the program under test.
"""
from __future__ import annotations

from typing import Iterable, NamedTuple, Tuple

import numpy as np


def block_stats(X, D) -> Tuple[np.ndarray, np.ndarray]:
    """``(G (c, m, m), M (m, c))`` of one block, in float64."""
    X, D = np.asarray(X), np.asarray(D)
    n, c = D.shape
    # rows grouped by their target row, compared byte for byte
    key = np.ascontiguousarray(D).view(
        np.dtype((np.void, D.dtype.itemsize * c))).ravel()
    _, inv = np.unique(key, return_inverse=True)
    order = np.argsort(inv, kind="stable")
    Xs = np.asarray(X[order], np.float64)
    m = X.shape[1] + 1
    G = np.zeros((c, m, m))
    M = np.zeros((m, c))
    lo = 0
    for hi in np.cumsum(np.bincount(inv)):
        rows = Xs[lo:hi]
        t = np.asarray(D[order[lo]], np.float64)
        s = np.concatenate([[hi - lo], rows.sum(axis=0)])
        g = np.empty((m, m))                    # [1, x]ᵀ [1, x] summed
        g[0], g[:, 0] = s, s
        g[1:, 1:] = rows.T @ rows
        fp2 = (t * (1.0 - t)) ** 2
        G += fp2[:, None, None] * g[None]
        M += np.outer(s, fp2 * np.log(t / (1.0 - t)))
        lo = hi
    return G, M


def solve(G: np.ndarray, M: np.ndarray, lam: float) -> np.ndarray:
    eye = np.eye(G.shape[-1])
    return np.stack([np.linalg.solve(G[k] + lam * eye, M[:, k])
                     for k in range(G.shape[0])], axis=1)


class Stats(NamedTuple):
    """The eq.-3 statistics: ``G (c, m, m)``, moments ``M (m, c)`` and
    the row count ``n``, in float64."""
    G: np.ndarray
    M: np.ndarray
    n: float


def stats(blocks: Iterable) -> Stats:
    """Float64 statistics over ``blocks`` of ``(X, D)``, block by block."""
    G = M = None
    n = 0
    for X, D in blocks:
        g, mm = block_stats(X, D)
        G = g if G is None else G + g
        M = mm if M is None else M + mm
        n += len(X)
    return Stats(G, M, float(n))


def rel_err(W, W64: np.ndarray) -> float:
    """‖W − W₆₄‖_F / ‖W₆₄‖_F, or infinity for a missing or broken ``W``."""
    W = np.asarray(W, np.float64)
    W64 = np.asarray(W64, np.float64)
    if W.shape != W64.shape or not np.all(np.isfinite(W)):
        return float("inf")
    return float(np.linalg.norm(W - W64) / np.linalg.norm(W64))


def rel_err_stats(got, ref: Stats) -> float:
    """The worst leaf's ``rel_err`` of ``got`` (``(G, M, n)`` in any float
    type, or ``None`` where no statistics were seen) against ``ref``."""
    if got is None:
        return float("inf")
    return max(rel_err(a, b) for a, b in zip(got, ref))


def check(W, got_stats, ref: Stats, lam: float) -> dict:
    """The numbers that decide ``correct``, by name."""
    return {"rel_err_W": rel_err(W, solve(ref.G, ref.M, lam)),
            "rel_err_stats": rel_err_stats(got_stats, ref)}
