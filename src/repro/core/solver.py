"""Closed-form one-layer solver — the paper's §3 in JAX.

Terminology follows the paper with a samples-first public API:
``X`` is ``(n, m_in)`` (we transpose internally to the paper's ``m×n`` and
prepend the bias row), ``D`` is ``(n, c)`` desired outputs inside the
activation range.

Two mathematically equivalent paths are provided:

* **SVD path (eq. 5)** — the paper's federated representation. Client
  statistics are ``(U_k, s_k)`` from the economy SVD of ``X F_k`` (one per
  output ``k``, because ``F = diag(f'(d̄_{:,k}))`` differs per output) and
  ``m = X F F d̄``. Stats merge associatively via Iwen & Ong (eq. 6).
* **Gram path (eq. 3)** — ``(X F F Xᵀ + λI) w = X F F d̄`` solved directly.
  Used as the centralized oracle in tests, and as a beyond-paper
  lower-communication federated variant (clients publish the ``m×m`` Gram
  instead of ``m×r`` factors; merge is a plain sum / psum).

The identity activation gets a fast path: ``F = I`` is shared across
outputs, so one SVD serves any number of outputs (this is what makes the
method usable as an analytic large-vocab readout, see ``core/head.py``).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import jax.scipy.linalg as jsp_linalg

from . import activations as acts
from .util import add_bias as _add_bias, as_2d as _as_2d

# sample-axis block of the fixed-shape chunked accumulation (matches the
# Pallas kernels' default bn tile). Keeping every chunk the same compiled
# shape is what makes zero-padding and fleet-stacking bitwise exact — see
# gram_stats_scan.
GRAM_BLOCK_N = 512


class ClientStats(NamedTuple):
    """Sufficient statistics a client publishes (paper Alg. 1 outputs).

    ``U``: (k, m, r) left singular vectors of X F_k, ``s``: (k, r) singular
    values, ``m_vec``: (m, c) moment vector. ``k == c`` for per-output F
    (nonlinear activations) or ``k == 1`` for the shared-F identity path.
    ``n``: scalar sample count (used only for bookkeeping/energy model).
    """
    U: jnp.ndarray
    s: jnp.ndarray
    m_vec: jnp.ndarray
    n: jnp.ndarray

    @property
    def US(self) -> jnp.ndarray:  # (k, m, r) — what the paper's client sends
        return self.U * self.s[..., None, :]


def _prep(X, D, act, add_bias, dtype):
    act = acts.get(act)
    X = jnp.asarray(X, dtype)
    D = _as_2d(jnp.asarray(D, dtype))
    if add_bias:
        X = _add_bias(X)
    d_bar = act.f_inv(D)          # (n, c) pre-activation targets
    fp = act.f_prime(d_bar)       # (n, c) diagonal of F per output
    return X, d_bar, fp, act


def client_stats(X, D, act="logistic", add_bias: bool = True,
                 dtype=jnp.float32) -> ClientStats:
    """Paper Algorithm 1: the client's local computation."""
    X, d_bar, fp, act = _prep(X, D, act, add_bias, dtype)
    m_vec = X.T @ (fp * fp * d_bar)                    # (m, c), eq. 7-9
    if act.name == "identity":
        # F = I shared across outputs: single economy SVD.
        U, s, _ = jnp.linalg.svd(X.T, full_matrices=False)  # (m, r), (r,)
        U, s = U[None], s[None]                             # k = 1
    else:
        # per-output F_k: batched SVD of (c, m, n)
        A = jnp.einsum("nm,nc->cmn", X, fp)
        U, s, _ = jnp.linalg.svd(A, full_matrices=False)
    return ClientStats(U=U, s=s, m_vec=m_vec,
                       n=jnp.asarray(X.shape[0], dtype))


def merge_stats(a: ClientStats, b: ClientStats) -> ClientStats:
    """Iwen & Ong incremental SVD merge (paper eq. 6 / Alg. 2 line 6).

    ``SVD([A|B])`` has the same U, s as ``SVD([U_a S_a | U_b S_b])``.
    Associative and commutative up to sign/rounding, which is what lets the
    coordinator add clients in any order or incrementally.
    """
    wide = jnp.concatenate([a.US, b.US], axis=-1)      # (k, m, ra+rb)
    U, s, _ = jnp.linalg.svd(wide, full_matrices=False)
    m = a.U.shape[-2]
    r = min(m, wide.shape[-1])
    return ClientStats(U=U[..., :r], s=s[..., :r],
                       m_vec=a.m_vec + b.m_vec, n=a.n + b.n)


def merge_many(stats_list) -> ClientStats:
    """One-shot Iwen–Ong merge of P partials: SVD([U₁S₁|…|U_P S_P]).

    Equivalent to any sequence of pairwise merges but a single wide SVD;
    this is the form the mesh-sharded solver uses after all_gather.
    """
    wide = jnp.concatenate([st.US for st in stats_list], axis=-1)
    U, s, _ = jnp.linalg.svd(wide, full_matrices=False)
    m = wide.shape[-2]
    r = min(m, wide.shape[-1])
    m_vec = sum(st.m_vec for st in stats_list)
    n = sum(st.n for st in stats_list)
    return ClientStats(U=U[..., :r], s=s[..., :r], m_vec=m_vec, n=n)


def solve_weights(stats: ClientStats, lam: float = 1e-3) -> jnp.ndarray:
    """Paper eq. 5 / Alg. 2 line 8: W = U (SSᵀ + λI)⁻¹ Uᵀ m. → (m, c)."""
    U, s, m_vec = stats.U, stats.s, stats.m_vec
    k = U.shape[0]
    gain = 1.0 / (s * s + lam)                         # (k, r)
    if k == 1:
        # shared F: solve all c outputs with the single factorization
        return U[0] @ (gain[0, :, None] * (U[0].T @ m_vec))
    proj = jnp.einsum("kmr,mk->kr", U, m_vec)          # Uₖᵀ m_{:,k}
    return jnp.einsum("kmr,kr->mk", U, gain * proj)


def centralized_solve_gram(X, D, act="logistic", lam: float = 1e-3,
                           add_bias: bool = True,
                           dtype=jnp.float32) -> jnp.ndarray:
    """Oracle: direct eq. 3 solve on the full (centralized) dataset."""
    X, d_bar, fp, act = _prep(X, D, act, add_bias, dtype)
    m_vec = X.T @ (fp * fp * d_bar)                    # (m, c)
    m = X.shape[1]
    eye = jnp.eye(m, dtype=dtype)

    def solve_one(fp_k, m_k):
        XF = X * fp_k[:, None]                         # (n, m)
        G = XF.T @ XF                                  # X F F Xᵀ
        return jnp.linalg.solve(G + lam * eye, m_k)

    if act.name == "identity":
        G = X.T @ X
        return jnp.linalg.solve(G + lam * eye, m_vec)
    return jax.vmap(solve_one, in_axes=(1, 1), out_axes=1)(fp, m_vec)


class GramStats(NamedTuple):
    """Beyond-paper federated representation: the eq.-3 sufficient stats.

    ``G``: (k, m, m) per-output Gram ``X F_k F_k Xᵀ`` (k==1 when F shared),
    ``m_vec``: (m, c). Merging is elementwise addition — on a mesh this is
    a single psum instead of an all_gather + wide SVD (see core/sharded.py
    and EXPERIMENTS.md §Perf for the communication comparison).
    """
    G: jnp.ndarray
    m_vec: jnp.ndarray
    n: jnp.ndarray


@functools.partial(jax.jit, static_argnames=("block",))
def gram_stats_scan(X, fp, dbar, *, block: int = GRAM_BLOCK_N):
    """Fixed-block streaming accumulation of the eq.-3 statistics.

    ``X`` (n, m_b), ``fp`` (n, k) per-output F diagonals (k == 1 for the
    shared-F identity path), ``dbar`` (n, c) → ``(G (k, m_b, m_b),
    mvec (m_b, c))``. The sample axis is zero-padded to a ``block``
    multiple, reshaped to a chunk axis, and folded with ``lax.scan`` —
    the carry is the O(k·m²) running statistics, and no intermediate ever
    exceeds O(k·block·m) (the XLA analogue of the Pallas kernels' HBM→VMEM
    streaming; the old one-shot einsum materialized O(c·n·m)).

    Because every chunk is the *same compiled shape*, the result is
    bitwise identical whether the same rows arrive alone, zero-padded to
    a larger block multiple, or stacked under ``vmap`` — the property the
    fleet-batched engine path's bit-parity rests on
    (tests/test_fleet_batch.py).
    """
    n, mb = X.shape
    k, c = fp.shape[1], dbar.shape[1]
    npad = -(-max(n, 1) // block) * block
    if npad != n:
        X = jnp.pad(X, ((0, npad - n), (0, 0)))
        fp = jnp.pad(fp, ((0, npad - n), (0, 0)))
        dbar = jnp.pad(dbar, ((0, npad - n), (0, 0)))
    Xc = X.reshape(-1, block, mb)
    fpc = fp.reshape(-1, block, k)
    dbc = dbar.reshape(-1, block, c)

    # f32 statistics need f32 contractions: at the TPU's default
    # precision the MXU rounds operands to bf16, which put W 1.3e-3 off
    # the float64 solve at HIGGS size (chip_smoke.py phase c)
    hi = jax.lax.Precision.HIGHEST

    def fold(carry, xs):
        G, mv = carry
        Xb, fb, db = xs
        XF = fb.T[:, :, None] * Xb[None]               # (k, block, m_b)
        return (G + jnp.einsum("knm,knp->kmp", XF, XF, precision=hi),
                mv + jnp.matmul(Xb.T, fb * fb * db, precision=hi)), None

    init = (jnp.zeros((k, mb, mb), X.dtype), jnp.zeros((mb, c), X.dtype))
    (G, mvec), _ = jax.lax.scan(fold, init, (Xc, fpc, dbc))
    return G, mvec


@functools.partial(jax.jit, static_argnames=("act", "add_bias", "dtype",
                                             "backend", "interpret"))
def _gram_stats(X, D, act, add_bias, dtype, backend, interpret):
    """One jitted program per client shape: prep, statistics, casts."""
    X, d_bar, fp, act = _prep(X, D, act, add_bias, dtype)
    if backend == "pallas":
        from ..kernels import ops as _kops
        if act.name == "identity":
            # shared F = I: one kernel pass emits the Gram AND the full
            # (m, c) moment block (kernels.gram_stats_shared)
            G, m_vec = _kops.client_gram_stats_shared(X, d_bar,
                                                      interpret=interpret)
        else:
            G, m_vec = _kops.client_gram_stats_fused(X, d_bar, fp,
                                                     interpret=interpret)
    else:
        fpk = jnp.ones((X.shape[0], 1), X.dtype) \
            if act.name == "identity" else fp
        G, m_vec = gram_stats_scan(X, fpk, d_bar)
    return GramStats(G=G.astype(dtype), m_vec=m_vec.astype(dtype),
                     n=jnp.asarray(X.shape[0], dtype))


def client_gram_stats(X, D, act="logistic", add_bias: bool = True,
                      dtype=jnp.float32, backend: str = "xla",
                      interpret: Optional[bool] = None) -> GramStats:
    """Eq.-3 sufficient statistics of one client's local data.

    Either backend runs as ONE jitted program per client shape: the
    activation prep, the statistics and the casts compile together, so
    the host issues one dispatch per client. ``backend`` selects how the
    per-output Gram stack is computed:

    * ``"xla"``    — :func:`gram_stats_scan`: a fixed-block ``lax.scan``
      accumulation (O(c·block·m) transient, never the O(c·n·m) blowup the
      old einsum reference paid).
    * ``"pallas"`` — the fused streaming kernel
      (``kernels.gram_stats_multi``, or ``gram_stats_shared`` on the
      identity path, whose c-column moment output means X is read exactly
      once): the sample axis streams HBM→VMEM, working set 3 tiles per
      class. ``interpret`` defaults by backend (interpret-mode off-TPU so
      tests run anywhere). The kernel accumulates in float32, so
      non-float32 ``dtype`` requests (e.g. fp64 exactness tests) fall
      back to the XLA path, which honors ``dtype`` end to end.
    """
    if backend == "pallas" and jnp.dtype(dtype) != jnp.float32:
        backend = "xla"
    if backend == "pallas":
        from ..kernels import ops as _kops
        if interpret is None:
            interpret = _kops._default_interpret()
    elif backend == "xla":
        interpret = False          # unused: keep one cache entry a shape
    else:
        raise ValueError(f"unknown backend {backend!r}")
    return _gram_stats(X, D, act=act, add_bias=add_bias, dtype=dtype,
                       backend=backend, interpret=interpret)


def merge_gram(a: GramStats, b: GramStats) -> GramStats:
    return GramStats(a.G + b.G, a.m_vec + b.m_vec, a.n + b.n)


def _fleet_mask(Xs, ns, dtype):
    """(P, n_max) validity mask from per-client sample counts."""
    npad = Xs.shape[1]
    return (jnp.arange(npad)[None, :] < ns[:, None]).astype(dtype)


@functools.partial(jax.jit, static_argnames=("act", "add_bias", "dtype",
                                             "backend", "block",
                                             "interpret", "fold"))
def client_gram_stats_fleet(Xs, Ds, ns, act="logistic",
                            add_bias: bool = True, dtype=jnp.float32,
                            backend: str = "xla",
                            block: int = GRAM_BLOCK_N,
                            interpret: Optional[bool] = None,
                            fold: bool = False) -> GramStats:
    """Eq.-3 statistics for a whole fleet of clients in ONE dispatch.

    ``Xs`` (P, n_max, m_in) stacked client shards, zero-padded on the
    sample axis; ``Ds`` (P, n_max, c) targets (pad rows should carry the
    activation midpoint ``f(0)`` so ``f_inv`` stays tame — any finite
    value is exact, pad rows are masked out of every statistic); ``ns``
    (P,) true per-client sample counts. Returns a *stacked*
    :class:`GramStats` with leading client axis: ``G`` (P, k, m_b, m_b),
    ``m_vec`` (P, m_b, c), ``n`` (P,).

    The bias column is the validity mask itself (1 on real rows, 0 on
    pads), so pad rows are all-zero and contribute exactly nothing.
    ``backend="pallas"`` routes to the fleet kernels
    (``kernels.gram_stats_fleet[_shared]``, grid (p, c, mi, mj, nk));
    ``"xla"`` vmaps :func:`gram_stats_scan`. Either way each client's
    slice is bitwise identical to its per-client
    :func:`client_gram_stats` result on the same backend.

    ``fold=True`` returns the fleet's sum instead, an unstacked
    :class:`GramStats` (``G`` (k, m_b, m_b), ``m_vec`` (m_b, c), ``n``
    the total count): on Pallas the kernel folds the clients in place
    (``gram_stats_fleet(fold=True)``, one output block whatever P is),
    on XLA the vmapped scan is summed over its client axis.
    """
    act = acts.get(act)
    if backend == "pallas" and jnp.dtype(dtype) != jnp.float32:
        backend = "xla"
    Xs = jnp.asarray(Xs, dtype)
    Ds = jnp.asarray(Ds, dtype)
    ns = jnp.asarray(ns)
    mask = _fleet_mask(Xs, ns, dtype)
    if add_bias:
        Xs = jnp.concatenate([mask[..., None], Xs], axis=-1)
    d_bar = act.f_inv(Ds)
    fp = act.f_prime(d_bar)
    fpk = mask[..., None] if act.name == "identity" \
        else fp * mask[..., None]
    if backend == "pallas":
        from ..kernels import ops as _kops
        G, m_vec = _kops.client_gram_stats_fleet(
            Xs, d_bar, fpk, shared=(act.name == "identity"), fold=fold,
            interpret=interpret)
    elif backend == "xla":
        G, m_vec = jax.vmap(
            lambda x, f, d: gram_stats_scan(x, f, d, block=block))(
                Xs, fpk, d_bar)
        if fold:
            G, m_vec = G.sum(axis=0), m_vec.sum(axis=0)
    else:
        raise ValueError(f"unknown backend {backend!r}")
    n = ns.astype(dtype)
    return GramStats(G=G.astype(dtype), m_vec=m_vec.astype(dtype),
                     n=n.sum() if fold else n)


@functools.partial(jax.jit, static_argnames=("act", "add_bias", "dtype"))
def client_stats_fleet(Xs, Ds, ns, act="logistic", add_bias: bool = True,
                       dtype=jnp.float32) -> ClientStats:
    """Paper Alg. 1 for a stacked fleet: batched SVDs, one dispatch.

    Same stacking convention as :func:`client_gram_stats_fleet`. Returns
    a stacked :class:`ClientStats` (``U`` (P, k, m_b, r), ``s`` (P, k, r),
    ``m_vec`` (P, m_b, c), ``n`` (P,)) with ``r = min(m_b, n_max)``;
    all-zero pad rows only add exactly-zero singular directions, so
    truncating client p to ``min(m_b, n_p)`` columns recovers its
    per-client factors up to SVD rounding (callers that need the paper's
    per-client rank — e.g. wire-byte accounting — slice there).
    """
    act = acts.get(act)
    Xs = jnp.asarray(Xs, dtype)
    Ds = jnp.asarray(Ds, dtype)
    ns = jnp.asarray(ns)
    mask = _fleet_mask(Xs, ns, dtype)
    if add_bias:
        Xs = jnp.concatenate([mask[..., None], Xs], axis=-1)
    d_bar = act.f_inv(Ds)
    fp = act.f_prime(d_bar) * mask[..., None]
    m_vec = jnp.einsum("pnm,pnc->pmc", Xs, fp * fp * d_bar)
    if act.name == "identity":
        U, s, _ = jnp.linalg.svd(jnp.swapaxes(Xs, 1, 2),
                                 full_matrices=False)
        U, s = U[:, None], s[:, None]                   # k = 1
    else:
        A = jnp.einsum("pnm,pnc->pcmn", Xs, fp)
        U, s, _ = jnp.linalg.svd(A, full_matrices=False)
    return ClientStats(U=U, s=s, m_vec=m_vec, n=ns.astype(dtype))


def solve_weights_gram(stats: GramStats, lam: float = 1e-3,
                       method: str = "cholesky") -> jnp.ndarray:
    """Coordinator solve on the eq.-3 wire: ``(G + λI) w = m_vec``.

    ``G + λI`` is symmetric positive definite (Gram + ridge), so the
    default factorization is Cholesky (``jax.scipy.linalg.cho_factor`` /
    ``cho_solve`` — one triangular factor, ~half the FLOPs and better
    backward stability than LU on SPD systems). ``method="solve"`` is the
    ``jnp.linalg.solve`` (LU) fallback flag, kept for conditioning
    comparisons and as an escape hatch; both agree to fp32 rounding
    (tested).

    Conditioning: with the ridge, ``cond(G+λI) ≤ (‖G‖+λ)/λ``, so even a
    singular Gram (duplicated features, n < m) stays SPD and both
    factorizations are backward stable. Documented tolerance (regression
    tested in tests/test_wire_algebra.py): relative residual
    ``‖(G+λI)w − m_vec‖ / (‖G+λI‖·‖w‖ + ‖m_vec‖) ≤ 1e-5`` at fp32 for
    λ ≥ 1e-3 on unit-scale data, for BOTH methods.
    """
    G, m_vec = stats.G, stats.m_vec
    m = G.shape[-1]
    eye = jnp.eye(m, dtype=G.dtype)
    if method == "cholesky":
        def solve_one(A, b):
            return jsp_linalg.cho_solve(jsp_linalg.cho_factor(A), b)
    elif method == "solve":
        solve_one = jnp.linalg.solve
    else:
        raise ValueError(f"unknown method {method!r} "
                         "(expected 'cholesky'|'solve')")
    if G.shape[0] == 1:
        return solve_one(G[0] + lam * eye, m_vec)
    sol = jax.vmap(lambda Gk, mk: solve_one(Gk + lam * eye, mk),
                   in_axes=(0, 1), out_axes=1)(G, m_vec)
    return sol


def predict(W: jnp.ndarray, X, act="logistic", add_bias: bool = True):
    act = acts.get(act)
    X = jnp.asarray(X, W.dtype)
    if add_bias:
        X = _add_bias(X)
    return act.f(X @ W)


def predict_labels(W, X, act="logistic", add_bias: bool = True):
    out = predict(W, X, act, add_bias)
    if out.shape[1] == 1:  # binary, single output unit
        return (out[:, 0] > 0.5).astype(jnp.int32)
    return jnp.argmax(out, axis=1)
