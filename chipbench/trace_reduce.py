"""From a profiler trace to busy time, kernel time and the breakdown.

A run with ``--trace 1`` records the JAX profiler over its window, with
the window and every step marked by host annotations (``chipbench.*``)
on the profiler's own clock. The reduction works on plain lists of
``(name, start_ns, end_ns)``:

* busy time is the union of the device's op intervals inside the window,
  averaged over the devices used; idle is the rest of the window;
* a kernel's time is the summed device duration of its events;
* each idle gap is charged to the innermost span of the program that
  was open at the gap's midpoint (the spans are placed on the trace
  clock through the window's annotation), or to ``(no span)`` where
  none was.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]          # (name, start_ns, end_ns)

WINDOW_MARK = "chipbench.window"
STEP_MARK = "chipbench.step"
NO_SPAN = "(no span)"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"[._]\d+$")
_INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(a, b) for a, b in out]


def clip(events: Sequence[Event], lo: int, hi: int) -> List[Event]:
    return [(n, max(a, lo), min(b, hi)) for n, a, b in events
            if b > lo and a < hi]


def busy_ns(events: Sequence[Event], lo: int, hi: int) -> int:
    return sum(b - a for a, b in union([(a, b) for _, a, b
                                        in clip(events, lo, hi)]))


def gaps(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out, t = [], lo
    for a, b in union([(a, b) for _, a, b in clip(events, lo, hi)]):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_family(name: str) -> str:
    """An op event's HLO instruction name without its number:
    ``%fusion.123 = f32[...] fusion(...)`` and ``fusion.7`` are one."""
    m = _INSTRUCTION.match(name)
    if m:
        name = m.group(1)
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name


def top(totals: Dict[str, float], k: int = 10) -> List[List]:
    return [[n, s] for n, s in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:k]]


def kernel_ns(events: Sequence[Event], pattern: str) -> int:
    rx = re.compile(pattern)
    return sum(b - a for n, a, b in events if rx.search(n))


def attribute(spans: Sequence[Event], points: Sequence[int]
              ) -> List[Optional[str]]:
    """For each time in ``points`` (ascending), the name of the innermost
    span ``(name, start, end)`` open at it — the one opened last, since
    the program's spans nest in time whatever their track — or None."""
    spans = sorted(spans, key=lambda s: s[1])
    out, active, i = [], [], 0
    for t in points:
        while i < len(spans) and spans[i][1] <= t:
            active.append(spans[i])
            i += 1
        active = [s for s in active if s[2] > t]
        out.append(max(active, key=lambda s: s[1])[0] if active else None)
    return out


class Summary:
    """What a run's trace says about its window."""

    def __init__(self, device_ops: Dict[str, List[Event]], lo: int, hi: int,
                 n_steps: int):
        self.lo, self.hi = lo, hi
        self.window_s = (hi - lo) / 1e9
        self.n_steps = n_steps
        self.ops = {d: clip(ev, lo, hi) for d, ev in device_ops.items()}
        busy = [busy_ns(ev, lo, hi) for ev in self.ops.values()]
        self.busy_s = sum(busy) / len(busy) / 1e9 if busy else 0.0

    @property
    def all_ops(self) -> List[Event]:
        return [e for ev in self.ops.values() for e in ev]

    def kernel_s(self, pattern: str) -> float:
        return kernel_ns(self.all_ops, pattern) / 1e9 / max(len(self.ops), 1)

    def device_ops(self, k: int = 10) -> List[List]:
        totals: Dict[str, float] = {}
        for n, a, b in self.all_ops:
            f = op_family(n)
            totals[f] = totals.get(f, 0.0) + (b - a) / 1e9
        return top(totals, k)

    def idle_gaps(self, spans, k: int = 10) -> List[List]:
        """Idle seconds by the program span open in each gap (first device)."""
        if not self.ops:
            return []
        ev = next(iter(self.ops.values()))
        holes = gaps(ev, self.lo, self.hi)
        names = attribute(spans, [(a + b) // 2 for a, b in holes])
        totals: Dict[str, float] = {}
        for (a, b), name in zip(holes, names):
            name = name or NO_SPAN
            totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
        return top(totals, k)


# ------------------------------------------------------------ xplane
def find_xplane(log_dir: Path) -> Optional[Path]:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def read_xplane(path: Path):
    """``(device_ops by plane, host marks)`` from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device_ops: Dict[str, List[Event]] = {}
    marks: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(
                        (e.name, int(e.start_ns), int(e.end_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                marks.extend((e.name, int(e.start_ns), int(e.end_ns))
                             for e in line.events
                             if e.name.startswith("chipbench."))
    return device_ops, marks


def summarize(device_ops: Dict[str, List[Event]], marks: Sequence[Event]
              ) -> Summary:
    win = [m for m in marks if m[0] == WINDOW_MARK]
    if not win:
        raise ValueError(f"the trace holds no {WINDOW_MARK!r} annotation")
    _, lo, hi = win[0]
    steps = sum(1 for m in marks if m[0] == STEP_MARK and lo <= m[1] < hi)
    return Summary(device_ops, lo, hi, steps)
