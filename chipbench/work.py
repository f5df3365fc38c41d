"""Operations and bytes of the eq.-3 round, from shapes alone.

The work is what eq. 3 asks for, whatever the kernel does: for a client
with ``n`` rows, ``m`` columns (bias included), ``k`` Gram rows of F and
``c`` classes,

* Gram: ``2·n·m²·k`` FLOPs (``G_f = (X F_f)ᵀ (X F_f)`` for every f);
* moments: ``2·n·m·c`` FLOPs (``Xᵀ (F² ⊙ d̄)``);
* bytes: X, the F rows and the d̄ rows read once, the statistics written
  once, in float32.

A kernel that re-reads X once per class therefore reads at most ``1/k``
of the memory bound. The coordinator's solve is ``c`` Cholesky factors
and their two triangular solves.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

F32 = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def gram_flops(n: int, m: int, k: int, c: int) -> int:
    return 2 * n * m * m * k + 2 * n * m * c


def gram_bytes(n: int, m: int, k: int, c: int, itemsize: int = F32) -> int:
    return itemsize * (n * m + n * k + n * c + k * m * m + m * c)


def solve_flops(m: int, c: int) -> int:
    """``c`` Cholesky factors (m³/3) and forward/back solves (2·m² each)."""
    return c * (m ** 3 // 3 + 4 * m * m)


def merge_flops(n_clients: int, m: int, k: int, c: int) -> int:
    return max(n_clients - 1, 0) * (k * m * m + m * c + 1)


def stats_work(ns: Iterable[int], m: int, k: int, c: int) -> Dict[str, int]:
    """The Gram kernel's work over clients with ``ns`` rows each."""
    ns = list(ns)
    return {"flops": sum(gram_flops(n, m, k, c) for n in ns),
            "bytes": sum(gram_bytes(n, m, k, c) for n in ns)}


def round_flops(ns: Iterable[int], m: int, k: int, c: int) -> int:
    """One round: every client's statistics, the fold and the solve."""
    ns = list(ns)
    return (stats_work(ns, m, k, c)["flops"] + merge_flops(len(ns), m, k, c)
            + solve_flops(m, c))


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's peaks by ``device_kind``; an unknown device is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name} (known: {sorted(table)})")
    return table[device_kind]


def roofline(flops: float, nbytes: float, pk: Dict[str, float]):
    """Least time the chip could take, and which term bounds it."""
    t_flops = flops / pk["bf16_flops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
