"""What the span readers share: the program's spans (``Record.spans``,
each a ``repro.obs.trace.Span``) summed per step of the window.

A span carries ``name`` and ``dur_s``; a program whose tracer links its
spans also gives each an ``id``, the ``parent`` id of the innermost span
open when it began, and ``attrs``. A reader returns None, never 0,
where the program does not record what it reads.
"""
from __future__ import annotations

from typing import Optional


def linked(rec) -> bool:
    """Whether the program's spans carry parent links."""
    return any(hasattr(s, "parent") for s in rec.spans)


def named(rec, *names):
    return [s for s in rec.spans if s.name in names]


def per_step_ms(rec, unit: str, *names: str) -> Optional[float]:
    """Summed duration of the named spans per step, in ms: None in
    another unit's cells, or where no such span was recorded."""
    if rec.unit != unit or not rec.steps:
        return None
    spans = named(rec, *names)
    if not spans:
        return None
    return 1e3 * sum(s.dur_s for s in spans) / rec.steps


def self_ms(rec, unit: str, names, child: str) -> Optional[float]:
    """Summed self time of the named spans per step, in ms: their
    durations less those of their ``child`` children. None in another
    unit's cells, or where no ``child`` span was recorded."""
    if rec.unit != unit or not rec.steps:
        return None
    children = named(rec, child)
    if not children:
        return None
    inner = {}
    for s in children:
        inner[s.parent] = inner.get(s.parent, 0.0) + s.dur_s
    total = sum(s.dur_s - inner.get(s.id, 0.0)
                for s in named(rec, *names))
    return 1e3 * total / rec.steps
