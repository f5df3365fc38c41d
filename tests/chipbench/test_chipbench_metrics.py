"""The benchmark's metric arithmetic: readers, quantiles, and the layout
that finds every cell's files by name (chipbench/harness.py, metrics/)."""
import json
import re
import statistics
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness, trace_reduce, work  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PEAKS = work.peaks("TPU v5 lite")


def reader(name):
    return harness.by_name("metrics", name)


def span(name, dur):
    return SimpleNamespace(name=name, dur_s=dur)


def summary(ops, lo=0, hi=10 ** 9, steps=4):
    return trace_reduce.Summary({"/device:TPU:0": ops}, lo, hi, steps)


def record(unit="round", steps=4, trace=None, **kw):
    base = dict(unit=unit, steps=steps, window_s=1.0, latencies=[0.25] * 4,
                reports=[SimpleNamespace(dispatches=100)] * steps,
                spans=[span("client.stats", 0.1), span("bucket.dispatch",
                                                       0.02),
                       span("solve", 0.004), span("ledger.apply", 0.008),
                       span("round", 1.0)],
                trace=trace, work={"kernel": {"flops": 4e9, "bytes": 2e9},
                                   "flops": 5e9},
                peaks=PEAKS)
    base.update(kw)
    return harness.Record(**base)


def test_quantile_is_pythons_inclusive_quantile():
    vals = [float(v) for v in range(1, 101)]
    assert harness.quantile(vals, 0.95) == statistics.quantiles(
        vals, n=100, method="inclusive")[94]
    assert harness.quantile([3.0], 0.95) == 3.0


def test_span_readers_divide_by_steps():
    rec = record()
    assert reader("stats_ms.round").read(rec) == pytest.approx(30.0)
    assert reader("solve_ms.round").read(rec) == pytest.approx(1.0)
    assert reader("dispatches.round").read(rec) == 100
    ev = record(unit="event")
    assert reader("ledger_ms.event").read(ev) == pytest.approx(2.0)
    assert reader("stats_ms.event").read(ev) == pytest.approx(30.0)
    # a reader of another unit's cells finds nothing to read
    assert reader("stats_ms.event").read(rec) is None
    assert reader("ledger_ms.event").read(rec) is None


def test_device_readers_need_a_trace():
    rec = record()
    for name in ("gram_roofline.round", "mfu.round", "idle_share.round"):
        assert reader(name).read(rec) is None


def test_idle_share_and_mfu_from_a_trace():
    ops = [("fusion.1", 0, 2 * 10 ** 8), ("fusion.2", 10 ** 8, 3 * 10 ** 8),
           ("copy.3", 5 * 10 ** 8, 6 * 10 ** 8)]
    rec = record(trace=summary(ops))
    assert reader("idle_share.round").read(rec) == pytest.approx(60.0)
    # 5e9 FLOPs per round, 4 rounds in 1 s of trace, over the bf16 peak
    assert reader("mfu.round").read(rec) == pytest.approx(
        100 * 5e9 / (0.25 * 197e12))


def test_gram_roofline_over_kernel_time():
    mod = reader("gram_roofline.round")
    ops = [("%fusion.2 = f32[77000,29] fusion()", 0, 10 ** 8)] + [
        (f"%gram_stats_fleet.{i} = (f32[1,2,32,32]) custom-call(), "
         'custom_call_target="tpu_custom_call"', 2 * 10 ** 8 + i * 10 ** 7,
         2 * 10 ** 8 + i * 10 ** 7 + 4 * 10 ** 6) for i in range(4)]
    rec = record(trace=summary(ops))
    kernel_s = 4 * 4e-3
    t_min = max(4 * 4e9 / 197e12, 4 * 2e9 / 819e9)
    assert rec.trace.kernel_s(mod.KERNEL) == pytest.approx(kernel_s)
    assert mod.read(rec) == pytest.approx(100 * t_min / kernel_s)
    # a trace without the kernel reads nothing, never 0
    assert mod.read(record(trace=summary(ops[:1]))) is None


def test_every_cell_finds_its_files_by_name():
    names = set()
    for w in SPEC["workloads"]:
        cell = harness.Cell.load(w["name"], root=ROOT)
        assert (ROOT / "chipbench" / "drivers" /
                f"{cell.workload['driver']}.py").is_file()
        assert (ROOT / "chipbench" / "generators" /
                f"{cell.config['generator']}.py").is_file()
        assert (ROOT / "chipbench" / "partitions" /
                f"{cell.workload['partition']}.py").is_file()
        assert cell.config["name"] == w["config"]
        assert set(cell.workload["limits"]) == {"rel_err_W", "rel_err_stats"}
        names.add(w["name"])
    for m in SPEC["per_layer"]:
        assert hasattr(reader(m["name"]), "read")
        assert set(m["workloads"]) <= names
        e2e = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(e2e.get("workloads", names))


def test_benchmark_file_keeps_to_its_limits():
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[sec]:
            assert name.match(e["name"]), e["name"]
    for w in SPEC["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for c in SPEC["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
    e2e = {e["name"]: e for e in SPEC["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= e["bound"] <= 0.25 for e in e2e.values())
    assert 1 <= SPEC["run_seconds"] <= 51
