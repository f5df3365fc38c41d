"""Federation flight recorder: typed spans and events (DESIGN.md §14).

A :class:`Tracer` records what one federated round actually did —
phase by phase, client by client, tier by tier — as a flat list of
**spans** (named intervals with wall time, process-CPU time, and
scalar attributes such as byte counts) and **events** (named instants:
fault injections, ledger membership changes, quorum decisions,
journal commits). Exporters (``obs/export.py``) render the same
record three ways: Perfetto/Chrome-trace JSON, a Prometheus-style
textfile, and a console round summary.

Two invariants shape the design:

* **Zero overhead when off.** The engine threads an unconditional
  ``with self.trace.span(...)`` through every hot path; when no
  tracer is attached it holds the module-level :data:`NULL_TRACER`,
  whose ``span``/``event`` are constant no-ops (a shared context
  manager object, no allocation, no clock reads). Tracing never
  touches arrays, RNG state, or dispatch structure, so a traced round
  returns the bit-identical ``W`` and dispatch counts of an untraced
  one (tested in tests/test_obs.py). The one thing a live tracer adds
  is waiting: a span's ``ready(x)`` blocks on ``x`` so that the span
  ends on its result; on the null tracer it returns ``x`` untouched.

* **Sizes and timings, never statistics.** Span/event attributes are
  restricted to scalars (bool/int/float/str) and *short* sequences of
  them — :func:`sanitize_attrs` raises ``TypeError`` on any array
  (numpy or JAX) or long sequence, so a client's Gram/SVD payload can
  never leak into the trace stream by construction. The secagg spy
  test asserts it: a traced masked round's exported trace carries no
  statistic value.

Span and event names are a closed taxonomy (:data:`SPAN_NAMES`,
:data:`EVENT_NAMES`) so exporters and dashboards can't drift silently
— the golden-schema test pins both sets plus each span's required
fields.

While a :class:`Tracer` is attached, every span also opens a
``jax.profiler.TraceAnnotation`` of its name, so a profiler capture
holds the program's spans on its host plane, on the device ops' clock.
Each span records its ``parent``, the innermost span open when it began
on any track, so a reader can take a span's self time. The tracer also
records Python's cyclic-collector passes as ``gc`` spans (track
``host``) for as long as it lives.
"""
from __future__ import annotations

import dataclasses
import gc
import itertools
import time
import weakref
from typing import Any, Dict, List, Optional

__all__ = [
    "EVENT_NAMES",
    "NULL_TRACER",
    "NullTracer",
    "SPAN_NAMES",
    "Span",
    "TraceEvent",
    "Tracer",
    "sanitize_attrs",
]

# ------------------------------------------------------------ taxonomy
# The closed span vocabulary: round → client-phase → bucket-dispatch →
# mask/encode → tier-fold → solve → commit. Adding a name here is an
# exporter-schema change — update DESIGN.md §14 and the golden test.
SPAN_NAMES = (
    "round",            # one engine run (or one ledger tick)
    "client.stats",     # one client's local statistics pass
    "bucket.dispatch",  # one fleet-batched/fused bucket program
    "mask.encode",      # client-side privacy step (clip/noise/mask)
    "collective",       # the mesh transport's sharded round program
    "tier.fold",        # one tier merge of the hierarchical fold
    "merge",            # flat coordinator fold, ended on its aggregate
    "solve",            # coordinator solve (W or W_first)
    "score.pass",       # the contribution-scoring client phase
    "ledger.apply",     # applying one tick's events to the ledger
    "round.prep",       # per-shard preamble before the round's work
    "bucket.stack",     # stacking one bucket's shards on the host
    "client.wait",      # host blocked on a client pass's device work
    "ledger.snapshot",  # rounding the ledger's exact state to floats
    "gc",               # one pass of Python's cyclic collector
)

# Instantaneous events: bookkeeping decisions, not work.
EVENT_NAMES = (
    "fault.retry",        # a client's upload was retried
    "fault.quarantine",   # a client's upload was rejected pre-fold
    "fault.failover",     # a tier aggregator failed over to a sibling
    "fault.recovered",    # an edge aggregate recovered from the WAL
    "quorum.commit",      # the round committed at a sample quorum
    "journal.commit",     # one edge aggregate became durable
    "ledger.join",        # membership events (event-driven rounds)
    "ledger.leave",
    "ledger.revise",
    "ledger.evict",
    "score.client",       # one client's exact-LOO score
)

# Fields every exported span carries (the golden-schema contract).
SPAN_REQUIRED_FIELDS = ("name", "track", "t0", "dur_s", "cpu_s")

_SCALARS = (bool, int, float, str, type(None))
_MAX_SEQ = 16


def _scalar(v: Any) -> Any:
    """One attribute value → a pure-Python scalar, or TypeError."""
    if isinstance(v, _SCALARS):
        return v
    # numpy scalars quack like item(); arrays/jax arrays have shape —
    # any value with a nonzero ndim is a payload, not an attribute
    if getattr(v, "ndim", None) == 0 and hasattr(v, "item"):
        return _scalar(v.item())
    raise TypeError(
        f"trace attribute of type {type(v).__name__} is not a scalar: "
        "spans carry sizes and timings, never statistics payloads "
        "(DESIGN.md §14)")


def sanitize_attrs(attrs: Dict[str, Any]) -> Dict[str, Any]:
    """Coerce span/event attributes to pure-Python scalars.

    Allows scalars and short (≤16) lists/tuples of scalars; anything
    array-like raises ``TypeError`` — the structural guarantee behind
    the trace stream's privacy stance (a Gram block physically cannot
    ride an attribute).
    """
    out = {}
    for k, v in attrs.items():
        if isinstance(v, (list, tuple)):
            if len(v) > _MAX_SEQ:
                raise TypeError(
                    f"trace attribute {k!r} is a length-{len(v)} "
                    f"sequence (max {_MAX_SEQ}): aggregate it to a "
                    "count instead of shipping a payload")
            out[k] = [_scalar(x) for x in v]
        else:
            out[k] = _scalar(v)
    return out


# ------------------------------------------------------------- records
@dataclasses.dataclass
class Span:
    """One named interval of round work."""
    name: str
    track: str                    # timeline row: coordinator|client|host
    t0: float                     # wall clock at entry (perf_counter s)
    dur_s: float = 0.0            # wall duration
    cpu_s: float = 0.0            # process-CPU time inside the span
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    depth: int = 0                # nesting depth at entry (same track)
    id: int = 0                   # unique within its tracer
    parent: Optional[int] = None  # innermost span open at entry, any
    #                               track (None at top level)

    def to_dict(self) -> dict:
        return {"name": self.name, "track": self.track,
                "t0": float(self.t0), "dur_s": float(self.dur_s),
                "cpu_s": float(self.cpu_s), "depth": int(self.depth),
                "id": int(self.id), "parent": self.parent,
                "attrs": dict(self.attrs)}


@dataclasses.dataclass
class TraceEvent:
    """One named instant (a decision, not work)."""
    name: str
    track: str
    t: float
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def to_dict(self) -> dict:
        return {"name": self.name, "track": self.track,
                "t": float(self.t), "attrs": dict(self.attrs)}


class _SpanCtx:
    """Context manager recording one span: it opens on ``__enter__``
    (with the profiler annotation of the same name) and closes on
    ``__exit__``, exceptions included."""

    __slots__ = ("_tracer", "_name", "_track", "_attrs", "_span",
                 "_cpu0", "_mark")

    def __init__(self, tracer: "Tracer", name: str, track: str,
                 attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._attrs = attrs

    def __enter__(self) -> "_SpanCtx":
        tr = self._tracer
        depth = tr._depth.get(self._track, 0)
        tr._depth[self._track] = depth + 1
        sp = Span(name=self._name, track=self._track, t0=0.0,
                  attrs=self._attrs, depth=depth, id=next(tr._ids),
                  parent=tr._open[-1].id if tr._open else None)
        tr.spans.append(sp)
        tr._open.append(sp)
        self._span = sp
        self._mark = tr._annotation(self._name)
        self._mark.__enter__()
        # the wall clock next to the annotation's own ends (the CPU
        # clock is a system call on some hosts)
        sp.t0 = time.perf_counter() - tr.t_origin
        self._cpu0 = time.process_time()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._mark.__exit__(None, None, None)
        sp = self._span
        tr = self._tracer
        sp.cpu_s = time.process_time() - self._cpu0
        # t0 is origin-relative; subtract on the same clock basis
        sp.dur_s = (t1 - tr.t_origin) - sp.t0
        tr._depth[sp.track] = max(0, tr._depth.get(sp.track, 1) - 1)
        tr._unwind(sp)
        return False

    # mid-span attribute attachment (e.g. byte counts known only after
    # the dispatch returns) — sanitized like constructor attrs
    def set(self, **attrs) -> None:
        self._span.attrs.update(sanitize_attrs(attrs))

    def ready(self, x):
        """Wait for ``x`` on the device, so that the span ends on its
        result rather than on its enqueue; returns ``x``."""
        self._tracer._block(x)
        return x


def _watch_gc(tracer: "Tracer") -> None:
    """Record the cyclic collector's passes on ``tracer`` while it
    lives. The callback holds the tracer weakly and leaves
    ``gc.callbacks`` when the tracer is collected."""
    ref = weakref.ref(tracer)

    def on_gc(phase: str, info: dict) -> None:
        tr = ref()
        if tr is not None:
            tr._on_gc(phase, info)

    def unwatch() -> None:
        if on_gc in gc.callbacks:
            gc.callbacks.remove(on_gc)

    gc.callbacks.append(on_gc)
    weakref.finalize(tracer, unwatch)


class Tracer:
    """Collects spans/events for one or more federated rounds.

    ``strict=True`` (default) rejects span/event names outside the
    taxonomy — exporters rely on the closed vocabulary. All wall
    clocks are ``time.perf_counter`` relative to the tracer's birth
    (``t_origin``), so exported timestamps start near zero.
    """

    enabled = True

    def __init__(self, *, strict: bool = True):
        # JAX only with a live tracer: NULL_TRACER imports nothing
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self._block = jax.block_until_ready
        self.strict = bool(strict)
        self.t_origin = time.perf_counter()
        self.spans: List[Span] = []
        self.events: List[TraceEvent] = []
        self._depth: Dict[str, int] = {}
        self._open: List[Span] = []      # open spans, innermost last
        self._ids = itertools.count()
        self._gc: Optional[_SpanCtx] = None
        _watch_gc(self)

    # ------------------------------------------------------------ spans
    def span(self, name: str, track: str = "coordinator",
             **attrs) -> _SpanCtx:
        if self.strict and name not in SPAN_NAMES:
            raise ValueError(
                f"unknown span name {name!r} (taxonomy: {SPAN_NAMES})")
        return _SpanCtx(self, name, track, sanitize_attrs(attrs))

    def _unwind(self, sp: Span) -> None:
        """Take a closing span off the open stack (innermost first)."""
        for i in range(len(self._open) - 1, -1, -1):
            if self._open[i] is sp:
                del self._open[i]
                return

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc = _SpanCtx(self, "gc", "host",
                                {"generation": int(info["generation"])})
            self._gc.__enter__()
        elif self._gc is not None:
            ctx, self._gc = self._gc, None
            ctx.set(collected=int(info["collected"]))
            ctx.__exit__(None, None, None)

    def event(self, name: str, track: str = "coordinator",
              **attrs) -> TraceEvent:
        if self.strict and name not in EVENT_NAMES:
            raise ValueError(
                f"unknown event name {name!r} (taxonomy: {EVENT_NAMES})")
        ev = TraceEvent(name=name, track=track,
                        t=time.perf_counter() - self.t_origin,
                        attrs=sanitize_attrs(attrs))
        self.events.append(ev)
        return ev

    # ------------------------------------------------------- inspection
    def spans_named(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name]

    def clear(self) -> None:
        self.spans.clear()
        self.events.clear()
        self._depth.clear()
        self._open.clear()
        self.t_origin = time.perf_counter()


class _NullCtx:
    """The shared no-op span context (NULL_TRACER's only allocation)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **attrs) -> None:
        pass

    def ready(self, x):
        return x


_NULL_CTX = _NullCtx()


class NullTracer:
    """Tracing off: every call is a constant no-op.

    The engine holds this when no tracer is attached, so hot paths
    never branch on ``if tracer is not None`` — the off cost is one
    attribute lookup and an empty ``with``.
    """

    enabled = False
    spans: tuple = ()
    events: tuple = ()

    def span(self, name: str, track: str = "coordinator", **attrs):
        return _NULL_CTX

    def event(self, name: str, track: str = "coordinator", **attrs):
        return None

    def spans_named(self, name):
        return []

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()
