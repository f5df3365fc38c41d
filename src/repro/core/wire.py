"""Wire protocol: the pluggable sufficient-statistics representation.

A *wire* bundles everything the federation engine needs to know about one
representation of the paper's client statistics:

* ``local_stats(X, d)``    — the client-side pass (paper Alg. 1),
* ``local_stats_batch(Xs, Ds, ns)`` — the *fleet* client pass: one
  dispatch computes every client's statistics from a stacked,
  zero-padded ``(P, n_max, m)`` input (DESIGN.md §8). The base-class
  default is the per-client loop, so custom wires compose with the
  batched engine path unchanged,
* ``merge(a, b)``          — the associative coordinator merge (Alg. 2),
* ``merge_many(list)``     — deterministic sequential left fold of
  ``merge`` (merge *topology* — tree vs sequential — is engine policy),
* ``solve(stats, lam)``    — the coordinator solve,
* ``wire_bytes(stats)``    — upload size of one client's publication,
* ``stats_bytes(n, m, c)`` — the same, analytically from shapes (used for
  mesh transports where per-client stats never materialize host-side),
* ``mesh_reduce(stats, axis)`` — the merge expressed as mesh collectives,
  for use inside ``shard_map`` (DESIGN.md §4).

The built-in wires additionally provide ``fleet_stats(Xs, Ds, ns)``
(stacked statistics with a leading client axis, jit-traceable) and
``merge_axis(stacked)`` (the merge over that leading axis).
``fleet_stats(..., fold=True)`` is the two at once — the fleet's merged
statistics, which the gram wire's Pallas kernel folds in place without
ever writing the per-client stack — and is what the engine's *fused*
bucket programs (flat and per edge aggregator) compile into a single
stats → merge (→ solve) program.

Two implementations wrap ``core/solver.py``:

* :class:`SvdWire`  — the paper's eq.-5/eq.-6 representation
  (``(U·S, m_vec)`` factors, Iwen–Ong merge, all_gather + wide SVD on a
  mesh),
* :class:`GramWire` — the eq.-3 representation (``(G, m_vec)``, additive
  merge, single psum on a mesh). Its ``backend`` field carries the
  ``"pallas"``/``"xla"`` choice for the client statistics pass
  (``backend=None`` resolves to the fused Pallas kernel on TPU and the
  XLA einsum elsewhere, matching the historical ``fed_fit_sharded_gram``
  default).

Adding a representation (e.g. a compressed Gram) is one new class — every
transport and scenario in ``core/engine.py`` composes with it unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Protocol, Sequence, \
    runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from . import activations as acts
from . import solver
from .solver import ClientStats, GramStats


@runtime_checkable
class Wire(Protocol):
    """Structural type every wire implements (see module docstring)."""
    name: str
    act: str

    def local_stats(self, X, d): ...
    def local_stats_batch(self, Xs, Ds, ns): ...
    def merge(self, a, b): ...
    def merge_many(self, stats_list): ...
    def merge_tree(self, stats_list): ...
    def solve(self, stats, lam: float): ...
    def wire_bytes(self, stats) -> int: ...
    def stats_bytes(self, n_local: int, m_in: int, c: int) -> int: ...
    def mesh_reduce(self, stats, axis: str): ...


class _WireBase:
    def local_stats_batch(self, Xs, Ds, ns) -> List:
        """Per-client statistics from a stacked ``(P, n_max, …)`` batch.

        Default: trim each client back to its true ``ns[p]`` rows and run
        the per-client pass — correct for any wire, one dispatch per
        client. The built-in wires override this with a true one-dispatch
        fleet pass.
        """
        return [self.local_stats(np.asarray(Xs[p])[:int(n)],
                                 np.asarray(Ds[p])[:int(n)])
                for p, n in enumerate(ns)]

    def merge_many(self, stats_list: Sequence):
        stats_list = list(stats_list)
        if not stats_list:
            raise ValueError("merge_many of zero clients")
        agg = stats_list[0]
        for st in stats_list[1:]:
            agg = self.merge(agg, st)
        return agg

    def merge_stream(self, stats_iter):
        """Left-fold an ITERATOR of statistics without materializing
        the list — at any instant only the running aggregate and the
        incoming item are resident, the O(c·m²) streaming primitive a
        tier aggregator runs (``core/topology.py``, DESIGN.md §11).
        Same bracketing as :meth:`merge_many` (bit-identical on
        additive wires); returns ``None`` for an empty iterator, so an
        all-empty tier can be skipped rather than raise mid-stream.
        """
        agg = None
        for st in stats_iter:
            agg = st if agg is None else self.merge(agg, st)
        return agg

    def merge_tree(self, stats_list: Sequence):
        """Pairwise log-depth fold (what a real coordinator pool does)."""
        items = list(stats_list)
        if not items:
            raise ValueError("merge_tree of zero clients")
        while len(items) > 1:
            nxt = [self.merge(items[i], items[i + 1])
                   for i in range(0, len(items) - 1, 2)]
            if len(items) % 2:
                nxt.append(items[-1])
            items = nxt
        return items[0]

    def validate_stats(self, stats) -> None:
        """Coordinator-side admission check for one upload: reject
        non-finite statistics before anything folds. The ledger's
        ``_validate`` and the fault subsystem's ``validate_upload``
        both route through this hook, so a wire with non-float stats
        (the masked wire's ring elements) can override it with its
        own invariants."""
        for leaf in jax.tree_util.tree_flatten(stats)[0]:
            arr = np.asarray(jax.device_get(leaf))
            if np.issubdtype(arr.dtype, np.floating) and \
                    not np.all(np.isfinite(arr)):
                raise ValueError(
                    "non-finite statistic cannot enter the ledger")

    def _k(self, c: int) -> int:
        # per-output F stacks (k == c) except the shared-F identity path
        return 1 if acts.get(self.act).name == "identity" else c


@dataclasses.dataclass(frozen=True)
class SvdWire(_WireBase):
    """The paper's eq.-5 wire: clients publish ``(U·S, m_vec)``."""
    act: str = "logistic"
    dtype: Any = jnp.float32
    add_bias: bool = True

    name = "svd"

    def local_stats(self, X, d) -> ClientStats:
        return solver.client_stats(X, d, act=self.act,
                                   add_bias=self.add_bias,
                                   dtype=self.dtype)

    def fleet_stats(self, Xs, Ds, ns, fold: bool = False) -> ClientStats:
        """Stacked Alg.-1 statistics, one batched-SVD dispatch; with
        ``fold`` their Iwen–Ong merge (:meth:`merge_axis`)."""
        st = solver.client_stats_fleet(Xs, Ds, ns, act=self.act,
                                       add_bias=self.add_bias,
                                       dtype=self.dtype)
        return self.merge_axis(st) if fold else st

    def local_stats_batch(self, Xs, Ds, ns) -> List[ClientStats]:
        st = self.fleet_stats(Xs, Ds, jnp.asarray(ns))
        # one host materialization, then zero-copy per-client views — P
        # eager slice dispatches would eat the batching win at P ≫ 1
        U, s = np.asarray(st.U), np.asarray(st.s)
        m_vec, n_arr = np.asarray(st.m_vec), np.asarray(st.n)
        mb = U.shape[-2]
        out = []
        for p, n in enumerate(ns):
            # padded sample columns only add exactly-zero singular
            # directions; truncating to the true per-client rank recovers
            # the paper's (m, r) factor and its upload size
            r = min(mb, int(n))
            out.append(ClientStats(U=U[p][..., :r], s=s[p][..., :r],
                                   m_vec=m_vec[p], n=n_arr[p]))
        return out

    def merge_axis(self, st: ClientStats) -> ClientStats:
        """Iwen–Ong merge over the leading client axis (one wide SVD)."""
        US = st.US                                      # (P, k, m, r)
        Pn, k, m, r = US.shape
        wide = jnp.moveaxis(US, 0, -2).reshape(k, m, Pn * r)
        U, s, _ = jnp.linalg.svd(wide, full_matrices=False)
        rr = min(m, Pn * r)
        return ClientStats(U=U[..., :rr], s=s[..., :rr],
                           m_vec=st.m_vec.sum(axis=0), n=st.n.sum())

    def merge(self, a: ClientStats, b: ClientStats) -> ClientStats:
        return solver.merge_stats(a, b)

    def secagg_encode(self, stats: Optional[ClientStats] = None):
        """Exact-masking capability probe — the svd wire has none.

        Secure aggregation (``privacy/secagg.py``) masks each upload
        with pairwise pads that must cancel through the coordinator
        merge. The Iwen–Ong merge recombines singular factors through
        an SVD — it is not additive, so a pad added to ``U·S`` does
        not cancel against its negation in another client's factors
        (and there is no exact dyadic encoding of the merge to mask
        over). Raising here (rather than silently falling back to a
        different wire or skipping the masking) keeps the privacy
        policy honest; use :class:`GramWire` for ``privacy=secagg``.
        """
        raise NotImplementedError(
            "wire 'svd' cannot carry masked (secagg) uploads: the "
            "Iwen-Ong singular-factor merge is not additive, so "
            "pairwise masks cannot cancel through it; use wire='gram' "
            "for privacy=secagg")

    def merge_oneshot(self, stats_list) -> ClientStats:
        """One wide SVD over all partials (what a mesh all_gather feeds)."""
        return solver.merge_many(stats_list)

    def solve(self, stats: ClientStats, lam: float = 1e-3) -> jnp.ndarray:
        return solver.solve_weights(stats, lam)

    def wire_bytes(self, stats: ClientStats) -> int:
        itemsize = jnp.dtype(stats.U.dtype).itemsize
        return int((stats.U.size + stats.m_vec.size + 1) * itemsize)

    def stats_bytes(self, n_local: int, m_in: int, c: int) -> int:
        mb = m_in + (1 if self.add_bias else 0)
        r = min(mb, n_local)
        itemsize = jnp.dtype(self.dtype).itemsize
        return int((self._k(c) * mb * r + mb * c + 1) * itemsize)

    def mesh_reduce(self, st: ClientStats, axis: str) -> ClientStats:
        # "upload" = all_gather of every client's factors, then the
        # coordinator's one-shot Iwen-Ong merge, replicated per device
        US = jax.lax.all_gather(st.US, axis)            # (Pₐ, k, m, r)
        m_vec = jax.lax.psum(st.m_vec, axis)            # Σ m_p (eq. 10)
        Pn, k, m, r = US.shape
        wide = jnp.moveaxis(US, 0, -2).reshape(k, m, Pn * r)
        U, s, _ = jnp.linalg.svd(wide, full_matrices=False)
        rr = min(m, Pn * r)
        return ClientStats(U=U[..., :rr], s=s[..., :rr], m_vec=m_vec,
                           n=jax.lax.psum(st.n, axis))


@dataclasses.dataclass(frozen=True)
class GramWire(_WireBase):
    """The eq.-3 wire: clients publish ``(G, m_vec)``; merge is addition.

    ``solve_method`` selects the coordinator factorization:
    ``"cholesky"`` (default — G+λI is SPD) or ``"solve"`` (the
    ``jnp.linalg.solve`` LU fallback flag; see
    :func:`solver.solve_weights_gram`).
    """
    act: str = "logistic"
    backend: Any = "xla"        # "pallas" | "xla" | None (auto by platform)
    dtype: Any = jnp.float32
    add_bias: bool = True
    solve_method: str = "cholesky"

    name = "gram"

    def _backend(self) -> str:
        if self.backend is None:
            return "pallas" if jax.default_backend() == "tpu" else "xla"
        return self.backend

    def local_stats(self, X, d) -> GramStats:
        return solver.client_gram_stats(X, d, act=self.act,
                                        add_bias=self.add_bias,
                                        dtype=self.dtype,
                                        backend=self._backend())

    def fleet_stats(self, Xs, Ds, ns, fold: bool = False) -> GramStats:
        """Stacked eq.-3 statistics: ONE dispatch for the whole fleet
        (the Pallas fleet kernel on TPU, a vmapped ``lax.scan`` on XLA).
        ``fold=True``: the fleet's sum, :meth:`merge_axis` of the stack,
        which the Pallas kernel accumulates in place (one output block,
        not P).
        """
        return solver.client_gram_stats_fleet(Xs, Ds, ns, act=self.act,
                                              add_bias=self.add_bias,
                                              dtype=self.dtype,
                                              backend=self._backend(),
                                              fold=fold)

    def fleet_out_bytes(self, P: int, n_max: int, m_in: int, c: int,
                        fold: bool = False) -> int:
        """Bytes of statistics a :meth:`fleet_stats` pass over a
        (P, n_max, m_in) stack writes: the Pallas kernel's padded output
        blocks, or the XLA scan's (k, m, m) and (m, c) results, per
        client or once when it folds."""
        mb = m_in + (1 if self.add_bias else 0)
        k = self._k(c)
        if self._backend() == "pallas" and \
                jnp.dtype(self.dtype) == jnp.float32:
            from ..kernels.gram_stats import fleet_out_bytes
            return fleet_out_bytes(P, n_max, mb, k, c if k == 1 else 1,
                                   fold=fold)
        itemsize = jnp.dtype(self.dtype).itemsize
        return int((1 if fold else P) * (k * mb * mb + mb * c) * itemsize)

    def local_stats_batch(self, Xs, Ds, ns) -> List[GramStats]:
        st = self.fleet_stats(Xs, Ds, jnp.asarray(ns))
        # one host materialization, then zero-copy per-client views (P
        # eager slice dispatches would eat the batching win at P ≫ 1);
        # each client's slice is bitwise identical to its per-client
        # local_stats — same fixed block shapes (tests/test_fleet_batch.py)
        G, m_vec = np.asarray(st.G), np.asarray(st.m_vec)
        n_arr = np.asarray(st.n)
        return [GramStats(G=G[p], m_vec=m_vec[p], n=n_arr[p])
                for p in range(len(ns))]

    def merge_axis(self, st: GramStats) -> GramStats:
        """The additive merge over the leading client axis (one sum)."""
        return GramStats(G=st.G.sum(axis=0), m_vec=st.m_vec.sum(axis=0),
                         n=st.n.sum())

    def local_stats_chunked(self, X, d, chunks: int) -> GramStats:
        """Edge-client chunk folding as ONE ``lax.scan`` program.

        Semantically the stream transport's per-chunk merge (each chunk's
        statistics added into the running aggregate, O(c·m²) carry, data
        never held whole past the activation prep) — but the Python
        fold over ``np.array_split`` pieces becomes a single scan over a
        reshaped ``(chunks, ⌈n/chunks⌉, …)`` chunk axis: one dispatch per
        client instead of one per chunk.

        On the Pallas backend the fused kernel *is* the chunk pass (it
        already streams the sample axis tile by tile), so the explicit
        per-chunk kernel fold is kept rather than silently dropping the
        selected backend for the XLA scan.
        """
        n = int(X.shape[0])
        chunks = max(1, min(int(chunks), n))
        if self._backend() == "pallas" and \
                jnp.dtype(self.dtype) == jnp.float32:
            agg = None
            for idx in np.array_split(np.arange(n), chunks):
                st = self.local_stats(X[idx], d[idx])
                agg = st if agg is None else self.merge(agg, st)
            return agg
        X, d_bar, fp, act = solver._prep(X, d, self.act, self.add_bias,
                                         self.dtype)
        fpk = jnp.ones((n, 1), X.dtype) if act.name == "identity" else fp
        G, m_vec = solver.gram_stats_scan(X, fpk, d_bar,
                                          block=-(-n // chunks))
        return GramStats(G=G.astype(self.dtype),
                         m_vec=m_vec.astype(self.dtype),
                         n=jnp.asarray(n, self.dtype))

    def merge(self, a: GramStats, b: GramStats) -> GramStats:
        return solver.merge_gram(a, b)

    def secagg_encode(self, stats: Optional[GramStats] = None):
        """The gram wire IS secagg-capable: its statistics are sums of
        per-sample terms, so the ledger's exact dyadic-integer image of
        a :class:`GramStats` is already the additive encoding pairwise
        masks cancel over — the encoding is the identity here. Called
        with no argument as the capability probe
        (``privacy/policy.py``); the svd wire's override raises.
        """
        return stats

    def merge_signed(self, a: GramStats, b: GramStats,
                     sign: int = 1) -> GramStats:
        """Signed merge: ``a ± b`` elementwise on every statistic.

        ``sign=-1`` is the *downdate* — removing client ``b`` from an
        aggregate it was previously merged into (``G−G_b``,
        ``m_vec−M_b``, ``n−n_b``). The downdate is mathematically exact
        (the statistics are linear in the data), but in floating point
        ``(a+b)−b`` recovers ``a`` only when no accumulation step
        rounded; :class:`~.ledger.ExactAccumulator` is the ledger's
        unconditional-bit-exactness upgrade of this operation.
        """
        s = jnp.asarray(sign, a.G.dtype)
        return GramStats(G=a.G + s * b.G, m_vec=a.m_vec + s * b.m_vec,
                         n=a.n + s * b.n)

    def subtract(self, a: GramStats, b: GramStats) -> GramStats:
        """Exact-form downdate ``a − b`` (see :meth:`merge_signed`).

        Presence of this method is the trait the
        :class:`~.ledger.FederationLedger` keys on to run O(c·m²)
        delta rounds instead of re-merging the surviving registry.
        """
        return self.merge_signed(a, b, -1)

    def solve(self, stats: GramStats, lam: float = 1e-3) -> jnp.ndarray:
        return solver.solve_weights_gram(stats, lam,
                                         method=self.solve_method)

    def wire_bytes(self, stats: GramStats) -> int:
        itemsize = jnp.dtype(stats.G.dtype).itemsize
        return int((stats.G.size + stats.m_vec.size + 1) * itemsize)

    def stats_bytes(self, n_local: int, m_in: int, c: int) -> int:
        mb = m_in + (1 if self.add_bias else 0)
        itemsize = jnp.dtype(self.dtype).itemsize
        return int((self._k(c) * mb * mb + mb * c + 1) * itemsize)

    def mesh_reduce(self, st: GramStats, axis: str) -> GramStats:
        return GramStats(G=jax.lax.psum(st.G, axis),
                         m_vec=jax.lax.psum(st.m_vec, axis),
                         n=jax.lax.psum(st.n, axis))


WIRES = {"svd": SvdWire, "gram": GramWire}


def get_wire(spec, act: str = "logistic", backend: Any = "xla",
             dtype: Any = jnp.float32) -> Wire:
    """Resolve a wire name (``"svd"``/``"gram"``) or pass an instance through."""
    if not isinstance(spec, str):
        return spec
    if spec not in WIRES:
        raise ValueError(f"unknown wire {spec!r} (expected 'svd'|'gram')")
    if spec == "gram":
        return GramWire(act=act, backend=backend, dtype=dtype)
    return SvdWire(act=act, dtype=dtype)
