"""FederationLedger: incremental join/leave/revise with exact unlearning.

The paper's round is one-shot, but its statistics form a commutative
monoid — and on the gram wire the monoid has *exact inverses*: client
contributions are linear in the data, so removing a client is the signed
merge ``G−G_i, m_vec−M_i, n−n_i``. That turns membership churn (late
arrivals, data revisions, data-protection deletions) into O(c·m²) deltas
against a persisted global state instead of a full re-aggregation —
the "avoid redundant recomputation" energy argument of Green Federated
Learning (Yousefpour et al., 2023) applied to stats-passing FL
(Savazzi et al., 2022). See DESIGN.md §9.

Why a ledger and not just ``GramWire.subtract``: floating-point
``(a+b)−b`` recovers ``a`` only when no accumulation step rounded, so a
float aggregate drifts under churn and *exact* unlearning ("the model
bit-equals one trained without me") is unprovable. The ledger therefore
folds uploads into an :class:`ExactAccumulator`: every finite float is
the dyadic rational ``p·2^-1074``; scaling by ``2^1074`` makes it a
Python integer, and integer adds/subtracts are exact and
order-independent. A snapshot rounds once, so the global statistics —
and hence ``W`` — depend ONLY on the multiset of live contributions,
never on the join/leave/revise history that produced it. That is the
bit-identity the unlearning tests assert. The per-event cost is
O(c·m²) host-side integer ops — the same order as the float downdate.

Wires without ``subtract`` (the SVD wire: a singular-value merge has no
useful inverse) fall back to re-merging the surviving registry via
``merge_tree`` in sorted-client order at the next solve — no client
recompute or re-upload (the coordinator retains the registry), but
O(P) coordinator merges per membership change.

State machine (per client id): absent → ``join`` → active →
(``revise`` → active | ``leave`` → absent). Everything else raises.
The ledger checkpoints through ``checkpoint/ckpt.py`` as the registry
plus metadata; restore re-folds the registry, which reproduces the
accumulator's integers exactly — a stopped federation continues with
bit-identical ``W``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import ckpt as _ckpt
from .solver import ClientStats, GramStats
from .wire import get_wire

# 2**-1074 is the smallest positive subnormal double: every finite
# float64 (hence every float32) is an integer multiple of it.
_SHIFT = 1074
_UNIT = 1 << _SHIFT

# stats classes by wire name, for checkpoint restore
_STATS_CLS = {"gram": GramStats, "svd": ClientStats}


def _leaf_to_ints(leaf) -> np.ndarray:
    """Exact dyadic-integer image of a float array (object-dtype ints)."""
    arr = np.asarray(jax.device_get(leaf), np.float64)
    if not np.all(np.isfinite(arr)):
        raise ValueError("non-finite statistic cannot enter the ledger")
    out = np.empty(max(arr.size, 1), dtype=object)
    for i, v in enumerate(arr.ravel().tolist()):
        p, q = v.as_integer_ratio()      # exact; q is a power of 2
        out[i] = p * (_UNIT // q)
    return out[:arr.size].reshape(arr.shape)


def _leaf_to_floats(ints: np.ndarray, dtype) -> jnp.ndarray:
    """Round the exact integers back to ``dtype`` (once, deterministic)."""
    # int/int true division is correctly rounded to float64; the cast to
    # the wire dtype is a second, equally deterministic rounding
    flat = [i / _UNIT for i in ints.ravel().tolist()]
    return jnp.asarray(
        np.asarray(flat, np.float64).reshape(ints.shape), dtype)


class ExactAccumulator:
    """Order-independent exact signed accumulator over a stats pytree.

    ``add(stats, sign)`` folds a contribution in; ``snapshot()`` rounds
    the exact state back to the template's dtypes. Because the integer
    arithmetic never rounds, ``add(b); add(b, -1)`` is an exact no-op
    and any two histories with the same multiset of live contributions
    snapshot to bit-identical arrays — the ledger's signed-merge
    algebra (property-tested in tests/test_wire_algebra.py).
    """

    def __init__(self, template):
        leaves, treedef = jax.tree_util.tree_flatten(template)
        self._treedef = treedef
        self._dtypes = [jnp.asarray(lf).dtype for lf in leaves]
        self._ints = [np.zeros(np.shape(lf), dtype=object)
                      for lf in leaves]

    def add(self, stats, sign: int = 1) -> "ExactAccumulator":
        leaves = jax.tree_util.tree_flatten(stats)[0]
        if len(leaves) != len(self._ints):
            raise ValueError("stats tree does not match the accumulator")
        # convert (and so validate) EVERY leaf before mutating any
        # state: a non-finite value in a later leaf must not leave the
        # accumulator partially folded
        ints = [_leaf_to_ints(leaf) for leaf in leaves]
        for acc, iv in zip(self._ints, ints):
            acc += int(sign) * iv
        return self

    def subtract(self, stats) -> "ExactAccumulator":
        return self.add(stats, -1)

    def snapshot(self):
        leaves = [_leaf_to_floats(ints, dt)
                  for ints, dt in zip(self._ints, self._dtypes)]
        return jax.tree_util.tree_unflatten(self._treedef, leaves)


class FederationLedger:
    """Persisted global wire-stats + per-client registry under events.

    ``exact=True`` (default, additive wires only) maintains the global
    state in an :class:`ExactAccumulator`; ``exact=False`` keeps a
    float aggregate via ``Wire.merge_signed`` — cheaper per event but
    rounding drifts with history, so only the exact path guarantees
    bit-identical unlearning. Non-subtractable wires ignore ``exact``
    and re-merge the surviving registry (``merge_tree``, sorted ids)
    lazily at the next solve.
    """

    def __init__(self, wire: Any = "gram", *, lam: float = 1e-3,
                 act: str = "logistic", backend: Any = "xla",
                 dtype: Any = jnp.float32, exact: bool = True):
        self.wire = get_wire(wire, act=act, backend=backend, dtype=dtype)
        self.lam = lam
        self.registry: Dict[int, Any] = {}
        self.departed: set = set()     # left and not rejoined — a
        # continued run must not auto-readmit them (their departure was
        # an explicit event, possibly a deletion request)
        self.evicted: Dict[int, str] = {}  # post-hoc quarantines by
        # reason (core/faults.py) — tracked DISTINCTLY from graceful
        # departures so fault accounting never conflates the two;
        # checkpointed (restore of an older, evicted-less file stays
        # valid via the back-compat guard in :meth:`restore`)
        self.tick = -1                 # last applied tick (-1 = fresh)
        self.n_events = 0
        self.subtractable = hasattr(self.wire, "subtract")
        # wires whose merge algebra is already exact (the masked wire's
        # integer ring arithmetic) skip the dyadic accumulator: their
        # merge_signed never rounds, so the float-drift argument above
        # doesn't apply and their stats aren't float leaves anyway
        self.exact = bool(exact) and self.subtractable \
            and not getattr(self.wire, "exact_by_construction", False)
        self._acc: Optional[ExactAccumulator] = None
        self._agg = None               # float aggregate / re-merge cache
        # flight-recorder hook (obs/, DESIGN.md §14): run_events points
        # this at the engine's tracer so membership changes land as
        # ledger.* trace events and snapshots as ledger.snapshot spans;
        # the default records nothing
        from ..obs.trace import NULL_TRACER
        self.tracer = NULL_TRACER

    # ------------------------------------------------------ membership
    @property
    def clients(self) -> Tuple[int, ...]:
        return tuple(sorted(self.registry))

    @property
    def seen(self) -> Tuple[int, ...]:
        """Every client id the ledger has a standing decision for —
        active, departed, or evicted. Auto-admission must not override
        any of the three (an evicted client was quarantined; only an
        explicit rejoin clears that flag)."""
        return tuple(sorted(set(self.registry) | self.departed
                            | set(self.evicted)))

    def _validate(self, stats) -> None:
        """Reject non-finite statistics BEFORE any state mutates — a
        failed event must leave registry and global state untouched.
        Wires with non-float stats (the masked wire's ring elements)
        supply their own ``validate_stats`` hook instead."""
        hook = getattr(self.wire, "validate_stats", None)
        if hook is not None:
            hook(stats)
            return
        for leaf in jax.tree_util.tree_flatten(stats)[0]:
            arr = np.asarray(jax.device_get(leaf), np.float64)
            if not np.all(np.isfinite(arr)):
                raise ValueError(
                    "non-finite statistic cannot enter the ledger")

    def join(self, cid: int, stats) -> None:
        if cid in self.registry:
            raise ValueError(f"join of client {cid}: already active")
        self._validate(stats)
        self._apply(stats, +1)
        self.registry[cid] = stats
        self.tracer.event("ledger.join", cid=int(cid))
        self.departed.discard(cid)
        # a rejoin clears BOTH standing decisions: a client that was
        # quarantined and later readmitted must not stay permanently
        # flagged as evicted in fault reports (regression-tested)
        self.evicted.pop(int(cid), None)

    def leave(self, cid: int) -> None:
        if cid not in self.registry:
            raise ValueError(f"leave of client {cid}: not active")
        self._apply(self.registry.pop(cid), -1)
        self.departed.add(cid)
        self.tracer.event("ledger.leave", cid=int(cid))

    def evict(self, cid: int, reason: str = "quarantined") -> None:
        """Post-hoc quarantine: remove a client whose upload turned
        out to be bad AFTER it folded. On the exact path the signed
        downdate makes the next snapshot — and so ``W`` — bit-identical
        to a ledger that never folded the client (the unlearning
        guarantee, property-tested in tests/test_faults.py).

        Eviction is NOT a graceful departure: the client lands in
        :attr:`evicted` (with its reason), never in :attr:`departed`,
        so downstream timeline/fault accounting can tell a deletion
        request from a quarantine (asserted in the faults report
        schema test)."""
        if cid not in self.registry:
            raise ValueError(f"evict of client {cid}: not active")
        self._apply(self.registry.pop(cid), -1)
        self.evicted[int(cid)] = str(reason)
        self.tracer.event("ledger.evict", cid=int(cid),
                          reason=str(reason))

    def revise(self, cid: int, stats) -> None:
        if cid not in self.registry:
            raise ValueError(f"revise of client {cid}: not active")
        self._validate(stats)       # before the old contribution leaves
        self._apply(self.registry[cid], -1)
        self._apply(stats, +1)
        self.registry[cid] = stats
        self.tracer.event("ledger.revise", cid=int(cid))

    def _apply(self, stats, sign: int) -> None:
        self.n_events += 1
        if self.exact:
            if self._acc is None:
                self._acc = ExactAccumulator(stats)
            self._acc.add(stats, sign)
        elif self.subtractable:
            self._agg = stats if self._agg is None else \
                self.wire.merge_signed(self._agg, stats, sign)
        else:
            self._agg = None           # dirty: re-merge lazily at solve

    # ------------------------------------------------------ global state
    def global_stats(self):
        """The persisted global statistics over the live registry."""
        if not self.registry:
            # distinguish WHY the federation is empty: a selection/
            # fault round that evicted or deferred everyone debugs very
            # differently from a federation no client ever joined
            if self.evicted:
                raise ValueError(
                    "empty federation: all remaining clients were "
                    f"evicted/quorum-deferred (evicted ids "
                    f"{sorted(self.evicted)}"
                    + (f", departed ids {sorted(self.departed)}"
                       if self.departed else "") + ")")
            if self.departed:
                raise ValueError(
                    "empty federation: every client departed "
                    f"(departed ids {sorted(self.departed)})")
            raise ValueError(
                "empty federation: no client ever joined")
        if self.exact:
            with self.tracer.span("ledger.snapshot") as sp:
                return sp.ready(self._acc.snapshot())
        if self._agg is None:          # non-subtractable wire: re-merge
            self._agg = self.wire.merge_tree(
                [self.registry[c] for c in self.clients])
        return self._agg

    def peek_without(self, cid: int):
        """Global statistics over the live registry MINUS ``cid``,
        leaving every byte of ledger state bit-identical.

        This is the leave-one-out primitive behind
        ``core/contribution.py``: on the exact path the accumulator's
        integers are subtracted and re-added (integer arithmetic never
        rounds, so the round-trip is an exact no-op and the snapshot in
        between equals a from-scratch fold over the survivors); on
        subtractable float/ring wires it is a pure ``Wire.subtract`` of
        the cached aggregate (no mutation at all — the masked wire's
        ring downdate keeps LOO scoring plaintext-free); non-
        subtractable wires re-merge the survivors in sorted-client
        order, exactly what a fresh ledger of the survivors would fold.
        ``n_events`` and the registry are untouched in every case.
        """
        if cid not in self.registry:
            raise ValueError(f"peek_without client {cid}: not active")
        if len(self.registry) == 1:
            raise ValueError(
                f"peek_without client {cid}: it is the only active "
                "client — the leave-one-out cohort would be empty")
        st = self.registry[cid]
        if self.exact:
            self._acc.subtract(st)
            try:
                return self._acc.snapshot()
            finally:
                self._acc.add(st)
        if self.subtractable:
            return self.wire.subtract(self.global_stats(), st)
        return self.wire.merge_tree(
            [self.registry[c] for c in self.clients if c != cid])

    def solve(self, lam: Optional[float] = None) -> jnp.ndarray:
        W = self.wire.solve(self.global_stats(),
                            self.lam if lam is None else lam)
        jax.block_until_ready(W)
        return W

    def resident_bytes(self) -> int:
        """Coordinator-resident wire-stats bytes: every active client's
        registry entry plus one global aggregate. Exact unlearning is
        *paid for* in residency — the registry must persist so any
        departure can be downdated exactly — so a tier topology cannot
        flatten event-driven rounds the way it flattens one-shot folds
        (``RoundReport.peak_coordinator_bytes`` reports this number on
        ledger ticks; DESIGN.md §11)."""
        total = sum(self.wire.wire_bytes(st)
                    for st in self.registry.values())
        if self.registry and (self._acc is not None
                              or self._agg is not None):
            total += max(self.wire.wire_bytes(st)
                         for st in self.registry.values())
        return total

    # ------------------------------------------------------ checkpoint
    def state_tree(self):
        """Checkpointable pytree: registry + metadata (flat-npz safe)."""
        if not getattr(self.wire, "checkpointable", True):
            raise NotImplementedError(
                f"ledger on wire {self.wire.name!r} does not "
                "checkpoint: masked ring elements have no flat-npz "
                "registry form (and restoring one would need the mask "
                "session re-keyed); checkpoint an unmasked federation "
                "or keep the masked ledger in memory (DESIGN.md §10)")
        meta = {"wire": np.asarray(self.wire.name),
                "act": np.asarray(self.wire.act),
                "lam": np.float64(self.lam),
                "exact": np.asarray(self.exact),
                "tick": np.int64(self.tick),
                "events": np.int64(self.n_events),
                "ids": np.asarray(self.clients, np.int64),
                "departed": np.asarray(sorted(self.departed), np.int64),
                "evicted_ids": np.asarray(sorted(self.evicted),
                                          np.int64),
                "evicted_reasons": np.asarray(
                    [self.evicted[c] for c in sorted(self.evicted)],
                    dtype=np.str_)}
        clients = {str(cid): {f: np.asarray(v) for f, v in
                              zip(type(st)._fields, st)}
                   for cid, st in self.registry.items()}
        return {"meta": meta, "clients": clients}

    def save(self, path: str) -> str:
        return _ckpt.save_checkpoint(path, self.state_tree())

    @classmethod
    def restore(cls, path: str, *, backend: Any = "xla",
                dtype: Any = jnp.float32) -> "FederationLedger":
        """Rebuild a ledger from :meth:`save` output.

        The registry is re-folded in sorted-client order; on the exact
        path the accumulator's integers — and so every future snapshot
        and ``W`` — are bit-identical to the pre-save ledger's,
        regardless of the event history that produced it.
        """
        flat = _ckpt.load_flat(path)
        wire_name = str(flat["meta/wire"].item())
        if wire_name not in _STATS_CLS:
            raise ValueError(f"cannot restore wire {wire_name!r} "
                             f"(known: {sorted(_STATS_CLS)})")
        led = cls(wire_name, lam=float(flat["meta/lam"]),
                  act=str(flat["meta/act"].item()), backend=backend,
                  dtype=dtype, exact=bool(flat["meta/exact"]))
        stats_cls = _STATS_CLS[wire_name]
        for cid in flat["meta/ids"].tolist():
            fields = {f: jnp.asarray(flat[f"clients/{cid}/{f}"])
                      for f in stats_cls._fields}
            led.join(int(cid), stats_cls(**fields))
        led.tick = int(flat["meta/tick"])
        led.n_events = int(flat["meta/events"])
        led.departed = set(flat["meta/departed"].tolist()) \
            if "meta/departed" in flat else set()
        if "meta/evicted_ids" in flat:    # absent in pre-eviction files
            led.evicted = dict(zip(
                (int(c) for c in flat["meta/evicted_ids"].tolist()),
                (str(r) for r in flat["meta/evicted_reasons"].tolist())))
        return led
