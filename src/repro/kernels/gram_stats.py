"""Pallas TPU kernel: fused client-statistics accumulation.

The paper's client hot loop (Alg. 1) streams the local dataset once and
accumulates the eq.-3 sufficient statistics:

    G    += (X F)ᵀ (X F)        (m × m Gram)
    mvec += Xᵀ (fp² ⊙ d̄)        (m moment vector)

TPU mapping (DESIGN.md §3): grid = (mi, mj, nk) with the sample axis nk
innermost. Every operand is laid out *feature-major*, samples on the lane
axis: the wrappers hand the kernel ``Xᵀ`` (m_pad, n_pad) and the fp/d̄
diagonals as (1, n_pad) rows. Each step loads two (tm × bn) tiles of Xᵀ
and a (1 × bn) row of fp/d̄ into VMEM, scales the tiles along their lanes,
and feeds the MXU with a (tm × bn)·(bn × tm) contraction accumulated in
the f32 VMEM output tile. The sample dimension streams HBM→VMEM, so the
working set stays at 3 tiles regardless of n (edge-device datasets
stream at any size — the green-FL story on TPU).

Why feature-major: a TPU array's last two dimensions are stored in
(8, 128) tiles, so a sample-major (n, 1) or (n, 29) operand occupies a
full 128-lane row per sample in HBM — 128× (resp. 4.4×) its size, which
does not fit a 100-client HIGGS fleet in 16 GB — and a (bn, 1) block out
of an (n, c) array is refused by the TPU lowering outright. With samples
on the lanes every block is (8, 128)-aligned and HBM holds ~what the data
is. The feature tile is ``tm = bm`` when m > bm and the whole (8-rounded)
feature axis otherwise, so a narrow table is never padded to 128 columns.
The transposes in the wrappers are one O(n·m) copy against the kernel's
O(k·n·m) reads.

The moment row reuses the already-resident Xᵀ tile (j == 0 column of the
grid), which is what "fused" buys over two separate passes.

One kernel carries this mapping, over a stacked, zero-padded
(P, n_max, m) fleet (DESIGN.md §8): grid = (p, k, mi, mj, nk), with a
client and an F-row dimension outermost. Step (p, f, ·) streams client
p's Xᵀ tiles scaled by F row f and emits that row's (m, m) Gram, plus the
moments of the d̄ rows it weights. ONE pallas_call therefore emits the
whole federation's statistics while the VMEM working set stays at 3
tiles per grid step — never the O(c·n·m) intermediate that the XLA
``einsum("nm,nc->cnm", ...)`` reference path materializes. Its entry
points:

* ``gram_stats_fleet`` — the per-output path (nonlinear activations,
  k == c, DESIGN.md §3.2): F row and d̄ row cls per class; (P, c, m, m)
  Gram stack and (P, m, c) moments. X is re-read once per class.
* ``gram_stats_fleet_shared`` — the shared-F path (identity activation,
  k == 1): one F row, and all c d̄ rows ride along with the resident Xᵀ
  tile, so the identity activation never needs a second dense read of X.
* ``gram_stats_multi``, ``gram_stats_shared`` and ``gram_stats`` — their
  one-client (P = 1) calls, so each fleet slice is bitwise what the
  per-client call returns by construction (tests/test_fleet_batch.py).

Both fleet entry points take ``fold=True``, the client-folding form for a
caller that sums the stack at once (a fused bucket, an edge aggregator):
grid = (k, mi, mj, p, nk) with ``p`` a reduction axis beside ``nk``, the
output blocks indexed without ``p``, so the bucket's clients accumulate
in place into one (k, m, m) block. What the kernel writes then does not
grow with the bucket: at FEMNIST's k = 62, m = 785 one block is 199 MB
padded, where 64 per-client blocks would be 12.7 GB.

Zero pad rows are exact: they contribute nothing to either statistic.

Every contraction asks for ``Precision.HIGHEST``: the statistics are f32
end to end, and the TPU must not round the operands to bf16 on the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

# Xᵀ-tile · Xᵀ-tileᵀ (contract the sample/lane axis of both), and
# row · Xᵀ-tileᵀ for the moment; f32 on the MXU.
_NT = (((1,), (1,)), ((), ()))
_HI = jax.lax.Precision.HIGHEST
# a constant block index typed int32: a bare 0 turns int64 when the
# kernel is traced under x64 (the masked round), which Mosaic refuses
_Z = np.int32(0)


def _tiles(m: int, n: int, bm: int, bn: int):
    """Padded extents and the feature tile: ``(m_pad, n_pad, tm)``. An
    empty shard still gets one (all-zero) sample block, so its grid is
    not empty and its statistics come out exactly zero."""
    if m <= bm:
        mp = -(-m // 8) * 8
        tm = mp
    else:
        mp = -(-m // bm) * bm
        tm = bm
    return mp, max(-(-n // bn), 1) * bn, tm


def _rows(A, np_):
    """(..., n, k) sample-major → (..., k, n_pad) feature-major, zero-padded."""
    A = jnp.swapaxes(A, -1, -2)
    pad = [(0, 0)] * (A.ndim - 1) + [(0, np_ - A.shape[-1])]
    return jnp.pad(A, pad)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gram_stats(X, fp, dbar, *, bm: int = 128, bn: int = 512,
               interpret: bool = False):
    """X: (n, m); fp, dbar: (n,) → (G (m, m), mvec (m,)) float32.

    Pads n, m to tile multiples (zero rows/cols contribute nothing to
    either statistic, so padding is exact). A one-client
    :func:`gram_stats_fleet_shared`.
    """
    G, mvec = gram_stats_fleet_shared(X[None], fp[None, :, None],
                                      dbar[None, :, None], bm=bm, bn=bn,
                                      interpret=interpret)
    return G[0], mvec[0, :, 0]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gram_stats_multi(X, Fp, Dbar, *, bm: int = 128, bn: int = 512,
                     interpret: bool = False):
    """Multi-output fused statistics: X (n, m); Fp, Dbar (n, c).

    Returns ``(G (c, m, m), mvec (m, c))`` in float32, where
    ``G[k] = (X·diag(Fp[:, k]))ᵀ (X·diag(Fp[:, k]))`` and
    ``mvec[:, k] = Xᵀ (Fp[:, k]² ⊙ Dbar[:, k])`` — the eq.-3 sufficient
    statistics for every output class in one pallas_call.

    A one-client :func:`gram_stats_fleet`, grid = (1, c, mi, mj, nk),
    class outermost (DESIGN.md §3.2): Xᵀ tiles are re-streamed per class
    with the per-class fp/d̄ row selected by the class grid index, so
    VMEM holds 3 tiles + one (tm, tm) accumulator at any step regardless
    of n or c. Being the same kernel is what makes each fleet slice
    bitwise the per-client result.
    """
    G, mvec = gram_stats_fleet(X[None], Fp[None], Dbar[None], bm=bm, bn=bn,
                               interpret=interpret)
    return G[0], mvec[0]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret"))
def gram_stats_shared(X, fp, Dbar, *, bm: int = 128, bn: int = 512,
                      interpret: bool = False):
    """Shared-F statistics with a multi-column moment: X (n, m), fp (n,),
    Dbar (n, c) → ``(G (m, m), mvec (m, c))`` float32.

    The k = 1 Gram is identical to :func:`gram_stats`; the moment block
    carries every output column (``mvec[:, k] = Xᵀ (fp² ⊙ Dbar[:, k])``),
    computed from the already-resident (tm, bn) Xᵀ tile at j == 0. This is
    what closes the identity-activation gap where the fused kernel's
    single-column moment used to be discarded and ``Xᵀ d̄`` recomputed
    densely (X is now read exactly once). A one-client
    :func:`gram_stats_fleet_shared`.
    """
    G, mvec = gram_stats_fleet_shared(X[None], fp[None, :, None], Dbar[None],
                                      bm=bm, bn=bn, interpret=interpret)
    return G[0], mvec[0]


def _kernel(x_i_ref, x_j_ref, fp_ref, dbar_ref, g_ref, m_ref, *, fold):
    """Grid step (p, f, i, j, s): ``G[p, f, i, j] += (xi·f)(xj·f)ᵀ``, and
    at ``j == 0`` also ``M[p, f, :, i] += (f²·d̄)·xiᵀ`` — xi/xj client p's
    (tm, bn) Xᵀ tiles, f its (1, bn) F row, d̄ the (r, bn) rows it weights.

    With ``fold`` the grid is (f, i, j, p, s) and the output blocks drop
    ``p``: every client of the bucket adds into the same resident (tm, tm)
    block, which is zeroed at the first client's first sample block."""
    if fold:
        j, p, s = pl.program_id(2), pl.program_id(3), pl.program_id(4)
        first = (p == 0) & (s == 0)
    else:
        j, s = pl.program_id(3), pl.program_id(4)
        first = s == 0

    @pl.when(first)
    def _init_g():
        g_ref[...] = jnp.zeros_like(g_ref)

    # the (p, f, i) moment tile is revisited at every j with s == 0 — only
    # the j == 0 pass may initialize it, or later j passes would re-zero it
    @pl.when(first & (j == 0))
    def _init_m():
        m_ref[...] = jnp.zeros_like(m_ref)

    xi = x_i_ref[0].astype(jnp.float32)
    xj = x_j_ref[0].astype(jnp.float32)
    f = fp_ref[0, 0].astype(jnp.float32)
    g_ref[0, 0] += jax.lax.dot_general(
        xi * f, xj * f, _NT, precision=_HI,
        preferred_element_type=jnp.float32)

    @pl.when(j == 0)
    def _moment():
        w = f * f * dbar_ref[0, 0].astype(jnp.float32)
        m_ref[0, 0] += jax.lax.dot_general(
            w, xi, _NT, precision=_HI, preferred_element_type=jnp.float32)


def _fleet(Xs, Fps, DbT, np_, bm, bn, interpret, fold=False):
    """The one pallas_call: Xs (P, n, m); Fps (P, n, k) sample-major;
    DbT (P, k, r, n_pad) already feature-major → (G (P, k, m, m),
    moments (P, k, r, m)). Grid = (p, k, mi, mj, nk).

    ``fold``: grid = (k, mi, mj, p, nk), ``p`` a reduction axis beside
    ``nk``; the outputs are the bucket's sums, (1, k, m, m) and
    (1, k, r, m), so the kernel writes one block set whatever P is."""
    P, n, m = Xs.shape
    _, k, r, _ = DbT.shape
    mp, _, tm = _tiles(m, n, bm, bn)
    XT = jnp.pad(jnp.swapaxes(Xs, 1, 2),
                 ((0, 0), (0, mp - m), (0, np_ - n)))
    FpT = _rows(Fps, np_)[:, :, None, :]               # (P, k, 1, n_pad)
    gi, gk = mp // tm, np_ // bn
    Po = 1 if fold else P

    def at(index):              # index maps are written in (p, f, i, j, s)
        return (lambda f, i, j, p, s: index(p, f, i, j, s)) if fold \
            else index

    G, M = pl.pallas_call(
        functools.partial(_kernel, fold=fold),
        grid=(k, gi, gi, P, gk) if fold else (P, k, gi, gi, gk),
        in_specs=[
            pl.BlockSpec((1, tm, bn), at(lambda p, f, i, j, s: (p, i, s))),
            pl.BlockSpec((1, tm, bn), at(lambda p, f, i, j, s: (p, j, s))),
            pl.BlockSpec((1, 1, 1, bn),
                         at(lambda p, f, i, j, s: (p, f, _Z, s))),
            pl.BlockSpec((1, 1, r, bn),
                         at(lambda p, f, i, j, s: (p, f, _Z, s))),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, tm, tm), at(
                lambda p, f, i, j, s: (_Z if fold else p, f, i, j))),
            pl.BlockSpec((1, 1, r, tm), at(
                lambda p, f, i, j, s: (_Z if fold else p, f, _Z, i))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Po, k, mp, mp), jnp.float32),
            jax.ShapeDtypeStruct((Po, k, r, mp), jnp.float32),
        ],
        interpret=interpret,
    )(XT, XT, FpT, DbT)
    return G[:, :, :m, :m], M[..., :m]


def fleet_out_bytes(P: int, n: int, m: int, k: int, r: int, *,
                    fold: bool = False, bm: int = 128,
                    bn: int = 512) -> int:
    """Bytes the fleet kernel writes for P stacked (n, m) clients: its
    padded (k, m_pad, m_pad) Gram and (k, r, m_pad) moment blocks, once
    per client, or once for the whole stack when it folds."""
    mp = _tiles(m, n, bm, bn)[0]
    return 4 * (1 if fold else P) * k * (mp * mp + r * mp)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "fold"))
def gram_stats_fleet(Xs, Fps, Dbars, *, bm: int = 128, bn: int = 512,
                     interpret: bool = False, fold: bool = False):
    """Fleet-batched multi-output statistics over P stacked clients.

    Xs (P, n_max, m); Fps, Dbars (P, n_max, c) → ``(G (P, c, m, m),
    mvec (P, m, c))`` float32 — ONE pallas_call for the whole federation.

    Grid = (p, c, mi, mj, nk), client outermost (DESIGN.md §8): every
    (p, cls) slice streams client p's Xᵀ tiles scaled by its F row cls,
    with d̄ row cls riding along for the moment, so the VMEM working set
    stays 3 tiles + one (tm, tm) accumulator regardless of P. Clients
    shorter than n_max are zero-padded (rows with fp = 0 contribute
    exactly nothing to either statistic).

    ``fold=True`` returns the stack's sums, ``(G (c, m, m), mvec (m, c))``:
    grid = (c, mi, mj, p, nk), each client's tiles accumulate into the
    same resident output block, so the kernel writes one (c, m, m) block
    where the per-client form writes P of them (DESIGN.md §8).
    """
    np_ = _tiles(Xs.shape[2], Xs.shape[1], bm, bn)[1]
    G, M = _fleet(Xs, Fps, _rows(Dbars, np_)[:, :, None, :], np_, bm, bn,
                  interpret, fold)
    if fold:
        return G[0], M[0, :, 0].T
    return G, jnp.swapaxes(M[:, :, 0], 1, 2)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "interpret",
                                             "fold"))
def gram_stats_fleet_shared(Xs, Fps, Dbars, *, bm: int = 128, bn: int = 512,
                            interpret: bool = False, fold: bool = False):
    """Fleet-batched shared-F statistics: Xs (P, n_max, m), Fps (P, n_max, 1)
    shared diag (1 on real rows, 0 on pads), Dbars (P, n_max, c) →
    ``(G (P, m, m), mvec (P, m, c))`` float32, or with ``fold=True`` their
    sums over the stack, ``(G (m, m), mvec (m, c))``.

    The fleet analogue of :func:`gram_stats_shared`: grid =
    (p, 1, mi, mj, nk), one k = 1 Gram and a c-row moment block per client
    in a single pallas_call.
    """
    np_ = _tiles(Xs.shape[2], Xs.shape[1], bm, bn)[1]
    G, M = _fleet(Xs, Fps, _rows(Dbars, np_)[:, None], np_, bm, bn,
                  interpret, fold)
    if fold:
        return G[0, 0], M[0, 0].T
    return G[:, 0], jnp.swapaxes(M[:, 0], 1, 2)
