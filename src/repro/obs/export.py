"""Trace exporters: Perfetto JSON, Prometheus textfile, console summary.

Three renderings of one :class:`~.trace.Tracer` record (DESIGN.md §14):

* :func:`to_perfetto` / :func:`write_perfetto` — Chrome-trace-event
  JSON (``{"traceEvents": [...]}``) loadable in Perfetto UI /
  ``chrome://tracing``: spans as complete (``"ph": "X"``) events on
  per-track rows, events as instants, timestamps in microseconds from
  the tracer's origin.
* :func:`to_prometheus` / :func:`write_prometheus` — a textfile in the
  Prometheus exposition format (node-exporter textfile-collector
  style): the documented counters/gauges/histograms of
  :data:`PROM_METRICS`. Metric names are a frozen contract — the
  golden-schema test pins them, ci_smoke greps the file for them.
* :func:`console_summary` — the human rendering: a per-phase
  wall/ΣCPU/bytes/joules table plus the energy ledger's category
  split.

All exporters are pure functions of the tracer (plus an optional
:class:`~..core.engine.RoundReport` for totals) — they never touch
the engine, so a crashed round's partial trace still exports.
"""
from __future__ import annotations

import json
from typing import Dict, List, Optional

from .energy import EnergyLedger
from .trace import SPAN_NAMES, Tracer

__all__ = [
    "PROM_METRICS",
    "console_summary",
    "to_perfetto",
    "to_prometheus",
    "write_perfetto",
    "write_prometheus",
]

# The frozen Prometheus metric-name contract (golden-schema-tested;
# ci_smoke greps the textfile for every name listed here).
PROM_METRICS = (
    "fed_round_dispatches_total",     # counter: client-phase dispatches
    "fed_round_wire_bytes_total",     # counter: admitted upload bytes
    "fed_round_retry_bytes_total",    # counter: duplicate upload bytes
    "fed_round_retry_joules_total",   # counter: retry surcharge (J)
    "fed_round_energy_joules_total",  # counter: joules by {category}
    "fed_round_cpu_seconds_total",    # counter: ΣCPU by {track}
    "fed_round_quarantined_total",    # counter: rejected uploads
    "fed_round_tier_peak_bytes",      # gauge: peak fold bytes by {tier}
    "fed_round_span_seconds",         # histogram: span wall by {name}
)

_HIST_BUCKETS = (1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0)

# Perfetto track (tid) ordering: stable rows in the timeline UI.
_TRACKS = ("coordinator", "client", "host")


def _tid(track: str) -> int:
    return _TRACKS.index(track) if track in _TRACKS \
        else len(_TRACKS) + (hash(track) % 100)


# ------------------------------------------------------------- perfetto
def to_perfetto(tracer: Tracer, *, pid: int = 1) -> dict:
    """Tracer → Chrome-trace-event JSON dict (Perfetto-loadable)."""
    events: List[dict] = []
    for track in sorted({s.track for s in tracer.spans}
                        | {e.track for e in tracer.events}
                        | set(_TRACKS)):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": _tid(track),
                       "args": {"name": f"fed/{track}"}})
    for sp in tracer.spans:
        events.append({
            "name": sp.name, "cat": sp.track, "ph": "X",
            "ts": round(sp.t0 * 1e6, 3),
            "dur": round(sp.dur_s * 1e6, 3),
            "pid": pid, "tid": _tid(sp.track),
            "args": {"cpu_ms": round(sp.cpu_s * 1e3, 6), **sp.attrs},
        })
    for ev in tracer.events:
        events.append({
            "name": ev.name, "cat": ev.track, "ph": "i",
            "ts": round(ev.t * 1e6, 3), "s": "t",
            "pid": pid, "tid": _tid(ev.track), "args": dict(ev.attrs),
        })
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": {"exporter": "repro.obs", "schema": 1,
                          "span_names": list(SPAN_NAMES)}}


def write_perfetto(tracer: Tracer, path: str, *, pid: int = 1) -> str:
    with open(path, "w") as f:
        json.dump(to_perfetto(tracer, pid=pid), f)
    return path


# ----------------------------------------------------------- prometheus
def _fmt_labels(labels: Dict[str, object]) -> str:
    if not labels:
        return ""
    body = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return "{" + body + "}"


def _line(out: List[str], metric: str, value, **labels) -> None:
    if isinstance(value, float):
        value = format(value, ".10g")
    out.append(f"{metric}{_fmt_labels(labels)} {value}")


def to_prometheus(tracer: Tracer,
                  report=None,
                  ledger: Optional[EnergyLedger] = None) -> str:
    """Tracer (+ optional report/energy ledger) → Prometheus textfile.

    With a ``report``, the totals come from the round's own
    bookkeeping (dispatches, wire bytes, faults ledger) so they
    reconcile exactly with ``RoundReport``; the span histogram and
    per-tier peaks always come from the trace.
    """
    if ledger is None and report is not None:
        ledger = EnergyLedger.from_report(report)
    out: List[str] = []

    out.append("# HELP fed_round_dispatches_total client-phase "
               "compiled-call dispatches")
    out.append("# TYPE fed_round_dispatches_total counter")
    if report is not None:
        _line(out, "fed_round_dispatches_total", int(report.dispatches))
    else:
        n = len([s for s in tracer.spans
                 if s.name in ("client.stats", "bucket.dispatch",
                               "collective")])
        _line(out, "fed_round_dispatches_total", n)

    out.append("# HELP fed_round_wire_bytes_total admitted upload bytes")
    out.append("# TYPE fed_round_wire_bytes_total counter")
    _line(out, "fed_round_wire_bytes_total",
          int(report.wire_bytes) if report is not None
          else int(ledger.bytes("uplink")) if ledger else 0)

    faults = (report.faults or {}) if report is not None else {}
    out.append("# HELP fed_round_retry_bytes_total duplicate upload "
               "bytes resent by the fault plan")
    out.append("# TYPE fed_round_retry_bytes_total counter")
    _line(out, "fed_round_retry_bytes_total",
          int(faults.get("retry_bytes", 0)))
    out.append("# HELP fed_round_retry_joules_total retry surcharge "
               "priced through the J/byte radio model")
    out.append("# TYPE fed_round_retry_joules_total counter")
    _line(out, "fed_round_retry_joules_total",
          float(faults.get("retry_j", 0.0)))

    out.append("# HELP fed_round_quarantined_total uploads rejected "
               "before the fold")
    out.append("# TYPE fed_round_quarantined_total counter")
    _line(out, "fed_round_quarantined_total",
          len(faults.get("quarantined", {})))

    out.append("# HELP fed_round_energy_joules_total attributed round "
               "energy by category")
    out.append("# TYPE fed_round_energy_joules_total counter")
    for cat, j in sorted((ledger.by_category() if ledger
                          else {}).items()):
        _line(out, "fed_round_energy_joules_total", float(j),
              category=cat)

    out.append("# HELP fed_round_cpu_seconds_total measured span CPU "
               "seconds by track")
    out.append("# TYPE fed_round_cpu_seconds_total counter")
    # sum each track's *top-level work* spans: those whose parent is a
    # round span or none, so nested sub-spans never double-count
    rounds = {s.id for s in tracer.spans if s.name == "round"}
    cpu_by_track: Dict[str, float] = {}
    for sp in tracer.spans:
        if sp.name != "round" and (sp.parent is None
                                   or sp.parent in rounds):
            cpu_by_track[sp.track] = cpu_by_track.get(sp.track, 0.0) \
                + sp.cpu_s
    for track, s in sorted(cpu_by_track.items()) or [("none", 0.0)]:
        _line(out, "fed_round_cpu_seconds_total", float(s), track=track)

    out.append("# HELP fed_round_tier_peak_bytes peak aggregate bytes "
               "folded at each tier")
    out.append("# TYPE fed_round_tier_peak_bytes gauge")
    tier_peak: Dict[int, int] = {}
    for sp in tracer.spans_named("tier.fold"):
        t = int(sp.attrs.get("tier", 0))
        b = int(sp.attrs.get("bytes", 0))
        tier_peak[t] = max(tier_peak.get(t, 0), b)
    for t, b in sorted(tier_peak.items()) or [(0, 0)]:
        _line(out, "fed_round_tier_peak_bytes", b, tier=t)

    out.append("# HELP fed_round_span_seconds span wall-time "
               "histogram by span name")
    out.append("# TYPE fed_round_span_seconds histogram")
    by_name: Dict[str, List[float]] = {}
    for sp in tracer.spans:
        by_name.setdefault(sp.name, []).append(sp.dur_s)
    for name in sorted(by_name):
        durs = by_name[name]
        cum = 0
        for le in _HIST_BUCKETS:
            cum = sum(1 for d in durs if d <= le)
            _line(out, "fed_round_span_seconds_bucket", cum,
                  name=name, le=format(le, "g"))
        _line(out, "fed_round_span_seconds_bucket", len(durs),
              name=name, le="+Inf")
        _line(out, "fed_round_span_seconds_sum", float(sum(durs)),
              name=name)
        _line(out, "fed_round_span_seconds_count", len(durs), name=name)
    return "\n".join(out) + "\n"


def write_prometheus(tracer: Tracer, path: str, report=None,
                     ledger: Optional[EnergyLedger] = None) -> str:
    with open(path, "w") as f:
        f.write(to_prometheus(tracer, report=report, ledger=ledger))
    return path


# -------------------------------------------------------------- console
def console_summary(tracer: Tracer, report=None,
                    ledger: Optional[EnergyLedger] = None) -> str:
    """Human-readable per-phase round summary (fedtrain prints it)."""
    if ledger is None and report is not None:
        ledger = EnergyLedger.from_report(report)
    rows = []
    by_name: Dict[str, List] = {}
    for sp in tracer.spans:
        by_name.setdefault(sp.name, []).append(sp)
    for name in sorted(by_name, key=lambda n: SPAN_NAMES.index(n)
                       if n in SPAN_NAMES else 99):
        sps = by_name[name]
        rows.append((name, len(sps), sum(s.dur_s for s in sps),
                     sum(s.cpu_s for s in sps)))
    width = max([len(r[0]) for r in rows] + [10])
    lines = [f"{'span':<{width}}  {'n':>5}  {'wall_s':>9}  {'cpu_s':>9}"]
    for name, n, wall, cpu in rows:
        lines.append(f"{name:<{width}}  {n:>5}  {wall:>9.4f}  "
                     f"{cpu:>9.4f}")
    if ledger is not None:
        cats = ledger.by_category()
        total = ledger.total_j() or 1.0
        lines.append("energy: " + "  ".join(
            f"{c}={j:.4g}J ({100 * j / total:.1f}%)"
            for c, j in cats.items() if j) or "energy: none attributed")
    nev = len(tracer.events)
    if nev:
        kinds: Dict[str, int] = {}
        for e in tracer.events:
            kinds[e.name] = kinds.get(e.name, 0) + 1
        lines.append("events: " + ", ".join(
            f"{k}×{v}" for k, v in sorted(kinds.items())))
    return "\n".join(lines)
