"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one cell is found by name: the cell's entry
in ``BENCHMARK.json``, its workload file ``workloads/<cell>.json``, its
configuration's file, the driver ``drivers/<driver>.py``, the generator
``generators/<generator>.py`` and partition ``partitions/<partition>.py``
(``datagen.py``), and one reader ``metrics/<metric>.py`` per per-layer
metric.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import shutil
import statistics
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from .common import HERE, by_name

ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"          # fixed: the path is in the key
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict               # the workload entry of BENCHMARK.json
    workload: dict            # workloads/<cell>.json
    config: dict              # the configuration's file
    spec: dict                # the whole BENCHMARK.json

    @classmethod
    def load(cls, name: str, root: Path = ROOT,
             overrides: Optional[dict] = None) -> "Cell":
        spec = json.loads((root / "BENCHMARK.json").read_text())
        entry = next((w for w in spec["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json")
        cfg = next(c for c in spec["configs"] if c["name"] == entry["config"])
        workload = json.loads((HERE / "workloads" / f"{name}.json")
                              .read_text())
        config = json.loads((root / cfg["file"]).read_text())
        for key, val in (overrides or {}).items():
            (config if key in config else workload)[key] = val
        return cls(name, entry, workload, config, spec)

    def metrics(self, section: str) -> List[dict]:
        return [m for m in self.spec[section]
                if self.name in m.get("workloads", [self.name])]


def configure_jax_cache() -> None:
    """The persistent compilation cache, inside the checkout, for every
    program: the minimum compile time is 0, so that a warm run compiles
    nothing at all."""
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(chips: int) -> None:
    import jax
    if jax.default_backend() != "tpu":
        raise NoChip(f"needs a TPU, JAX's backend is "
                     f"{jax.default_backend()!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes() -> int:
    """Peak device bytes on the fullest chip (0 where not reported)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks))


class CompileCounter:
    """Counts the programs compiled or loaded from the cache while on."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0

        def listen(event, duration, **kw):
            if self.on and event == COMPILE_EVENT:
                self.count += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


@dataclasses.dataclass
class Record:
    """What a metric reader gets: the window, its steps and its trace."""
    unit: str                       # "round" | "event"
    steps: int
    window_s: float
    latencies: List[float]
    reports: List[Any]
    spans: List[Any]
    trace: Any                      # trace_reduce.Summary or None
    work: Dict[str, Any]
    peaks: Dict[str, float]

    def span_ms(self, *names: str) -> Optional[float]:
        """Summed duration of the named spans per step, in ms."""
        if not self.steps:
            return None
        return 1e3 * sum(s.dur_s for s in self.spans
                         if s.name in names) / self.steps


def quantile(values: List[float], q: float) -> float:
    """The ``q`` quantile (inclusive method), as ``statistics`` gives it."""
    if len(values) == 1:
        return values[0]
    cuts = statistics.quantiles(values, n=100, method="inclusive")
    return cuts[int(round(q * 100)) - 1]


def run_window(step: Callable[[], None], seconds: float,
               mark: Callable[[str], Any]):
    """Steps back to back until ``seconds`` have passed; the step in
    flight at the deadline finishes inside the window."""
    lat: List[float] = []
    with mark("chipbench.window"):
        t0 = time.perf_counter()
        while True:
            with mark("chipbench.step"):
                s = time.perf_counter()
                step()
                e = time.perf_counter()
            lat.append(e - s)
            if e - t0 >= seconds:
                break
        window_s = time.perf_counter() - t0
    return t0, window_s, lat


def judge(checks: Dict[str, float], limits: Dict[str, float]) -> bool:
    """``correct``: every number compared is within its limit."""
    return all(checks[k] <= limits[k] for k in limits)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_tpu: bool = True, log=print) -> dict:
    """One run; returns the result line's object."""
    if require_tpu:
        require_chips(int(cell.entry["chips"]))
    import jax
    from . import trace_reduce, work
    from repro.obs.trace import Tracer

    device = device_info()
    pk = work.peaks(device["kind"]) if device["platform"] == "tpu" else {}
    tracer = Tracer() if trace else None
    driver = by_name("drivers", cell.workload["driver"])
    drv = driver.Driver(cell.config, cell.workload, seed, tracer)
    peak_setup = peak_bytes()
    counter = CompileCounter()
    gc.collect()

    log_dir = None
    if trace:
        log_dir = Path(tempfile.mkdtemp(prefix="chipbench-trace-"))
        tracer.clear()
        jax.profiler.start_trace(str(log_dir))
    setup_s = time.perf_counter() - t_start
    counter.on = True
    mark = jax.profiler.TraceAnnotation
    t0, window_s, lat = run_window(drv.step, seconds, mark)
    counter.on = False
    summary, spans = None, []
    if trace:
        jax.profiler.stop_trace()
        path = trace_reduce.find_xplane(log_dir)
        summary = trace_reduce.summarize(*trace_reduce.read_xplane(path))
        # the program's spans on the trace clock, through the window mark
        base = summary.lo - int(round((t0 - tracer.t_origin) * 1e9))
        spans = [(s.name, base + int(s.t0 * 1e9),
                  base + int((s.t0 + s.dur_s) * 1e9)) for s in tracer.spans]
        shutil.rmtree(log_dir, ignore_errors=True)
    mem_peak = peak_bytes()

    rec = Record(unit=drv.unit, steps=len(lat), window_s=window_s,
                 latencies=lat, reports=drv.reports,
                 spans=list(tracer.spans) if tracer else [], trace=summary,
                 work=drv.work, peaks=pk)
    if trace:
        metrics = {}
        for m in cell.metrics("per_layer"):
            reader = by_name("metrics", m["name"])
            value = reader.read(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"setup_s": setup_s,
               "peak_hbm_gb": mem_peak / 1e9,
               f"{drv.unit}_s": window_s / len(lat),
               f"{drv.unit}_p95_s": quantile(lat, 0.95)}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.metrics("end_to_end")}

    checks = drv.check()
    limits = cell.workload["limits"]
    correct = judge(checks, limits)
    device["memory_peak_bytes"] = mem_peak
    result = {"correct": bool(correct), "attempted": len(lat), "failed": 0,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops(),
                               "idle_gaps": summary.idle_gaps(spans)}
    result["window_compiles"] = counter.count
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    log(f"cell {cell.name} seed {seed}: {len(lat)} {drv.unit}s in "
        f"{window_s:.3f} s, set-up {setup_s:.3f} s, compiles in window "
        f"{counter.count}, peak bytes after set-up {peak_setup}, after "
        f"window {mem_peak}; {drv.unit} seconds min {min(lat):.6f} median "
        f"{statistics.median(lat):.6f} max {max(lat):.6f}")
    for k in limits:
        log(f"check {k} {checks[k]!r} limit {limits[k]!r}")
    return result


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="one run of one chip "
                                 "benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chipbench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = Cell.load(args.workload)
    configure_jax_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=t_start, log=log)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0
