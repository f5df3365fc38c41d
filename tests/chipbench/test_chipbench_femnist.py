"""The FEMNIST cell's own pieces: LEAF's writer partition
(partitions/writers.py), the readers of the tier fold and of the bucket
programs' output (``tier_fold_ms.round``, ``bucket_out_mb.round``), and
the cell driven through a run on the CPU at a small size."""
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import datagen, harness  # noqa: E402

CELL = "femnist.device355.tiered"
FEMNIST = json.loads((ROOT / "chipbench/configs/femnist.json").read_text())
WRITERS = harness.by_name("partitions", "writers")
# LEAF, arXiv:1812.01097 Table 1: samples a FEMNIST device
LEAF_MEAN, LEAF_SD = 226.83, 88.94


def workload():
    return harness.Cell.load(CELL).workload


def small_cell(clients=24, features=16):
    """The cell at a test size: its 62 classes, writers and gear, fewer
    writers and features, and fanout-8 edges so that both tiers fold."""
    engine = dict(workload()["engine"],
                  topology="fanout=8,tiers=2,exact=off")
    return harness.Cell.load(CELL, overrides={
        "clients": clients, "features": features, "engine": engine})


def test_writer_sizes_follow_leaf_and_the_partition_seed():
    wl = workload()
    rng = np.random.default_rng(int(wl["partition_seed"]))
    n = WRITERS.sizes(int(wl["clients"]), float(wl["writer_mean"]),
                      float(wl["writer_sd"]), rng)
    assert len(n) == 355 and n.min() >= 1
    assert abs(n.mean() / LEAF_MEAN - 1) < 0.05
    assert abs(n.std() / LEAF_SD - 1) < 0.05
    # LEAF's whole population follows the same law
    big = WRITERS.sizes(3550, LEAF_MEAN, LEAF_SD, np.random.default_rng(1))
    assert abs(big.mean() / LEAF_MEAN - 1) < 0.05
    assert abs(big.std() / LEAF_SD - 1) < 0.05
    assert FEMNIST["samples_per_writer"] == {"mean": LEAF_MEAN,
                                             "sd": LEAF_SD}


def test_every_writer_keeps_at_least_one_row():
    n = WRITERS.sizes(2000, 3.0, 40.0, np.random.default_rng(0))
    assert n.min() == 1


def test_writer_sizes_do_not_depend_on_the_run_seed():
    wl = {"partition": "writers", "clients": 12, "partition_seed": 0,
          "writer_mean": 60.0, "writer_sd": 25.0}
    config = dict(FEMNIST, features=8)
    got = []
    for seed in (1, 2 ** 40 + 5):
        _, pX, pd = datagen.federation(config, wl, seed)
        assert all(x.shape[0] == d.shape[0] for x, d in zip(pX, pd))
        assert all(d.shape[1] == 62 for d in pd)
        got.append([int(x.shape[0]) for x in pX])
    want = WRITERS.sizes(12, 60.0, 25.0, np.random.default_rng(0))
    # the same sizes in the same places: every edge folds the same
    # writers' sizes, so a round does the same work under every seed
    assert got[0] == got[1] == want.tolist()


def test_writer_shards_cover_the_rows_once():
    wl = {"partition": "writers", "clients": 9, "partition_seed": 3,
          "writer_mean": 50.0, "writer_sd": 20.0}
    config = dict(FEMNIST, features=8)
    src, pX, pd = datagen.federation(config, wl, 7)
    total = sum(int(x.shape[0]) for x in pX)
    X, D, _ = (np.asarray(a) for a in src.rows(0, total))
    allX = np.concatenate([np.asarray(x) for x in pX])
    allD = np.concatenate([np.asarray(d) for d in pd])
    order = np.lexsort(allX.T)
    ref = np.lexsort(X.T)
    np.testing.assert_array_equal(allX[order], X[ref])
    np.testing.assert_array_equal(allD[order], D[ref])
    assert len(np.unique(allX, axis=0)) == total


def reader(name):
    return harness.by_name("metrics", name)


def lspan(name, dur, id, parent=None, **attrs):
    return SimpleNamespace(name=name, dur_s=dur, id=id, parent=parent,
                           attrs=attrs)


def record(spans, unit="round", steps=2):
    return harness.Record(unit=unit, steps=steps, window_s=1.0,
                          latencies=[0.5] * steps, reports=[], spans=spans,
                          trace=None, work=None, peaks=None)


def tiered_spans():
    """Two rounds of a tiered program: two edge buckets and their fold
    each, then the root's fold."""
    out = []
    for k in range(2):
        b = 100 * k
        out += [lspan("round", 1.0, b),
                lspan("bucket.dispatch", 0.3, b + 1, b, bytes_out=199_000_000),
                lspan("client.wait", 0.29, b + 2, b + 1),
                lspan("bucket.dispatch", 0.2, b + 3, b, bytes_out=199_000_000),
                lspan("client.wait", 0.19, b + 4, b + 3),
                lspan("tier.fold", 0.0015, b + 5, b, tier=0),
                lspan("tier.fold", 0.0025, b + 6, b, tier=1),
                lspan("solve", 0.05, b + 7, b)]
    return out


def test_tier_fold_ms_reads_the_tier_fold_spans_per_round():
    assert reader("tier_fold_ms.round").read(record(tiered_spans())) == \
        pytest.approx(4.0)
    # a flat round has no tier fold
    flat = [s for s in tiered_spans() if s.name != "tier.fold"]
    assert reader("tier_fold_ms.round").read(record(flat)) is None
    assert reader("tier_fold_ms.round").read(
        record(tiered_spans(), unit="event")) is None


def test_bucket_out_mb_sums_the_bytes_out_per_round():
    assert reader("bucket_out_mb.round").read(record(tiered_spans())) == \
        pytest.approx(398.0)
    # dispatch spans without the count (a program that does not keep
    # it) read nothing, never 0
    bare = [lspan("bucket.dispatch", 0.3, 1), lspan("round", 1.0, 0)]
    assert reader("bucket_out_mb.round").read(record(bare)) is None
    assert reader("bucket_out_mb.round").read(
        record(tiered_spans(), unit="event")) is None


def test_small_traced_run_reads_every_program_metric_of_the_cell():
    """The cell at a test size on the CPU, traced: every metric the
    program records (spans and counters) reads a value, and each bucket
    program wrote one folded block set."""
    cell = small_cell()
    res = harness.run_cell(cell, 2 ** 31 + 13, 0.3, True,
                           t_start=time.perf_counter(), require_tpu=False,
                           log=lambda msg: None)
    want = {m["name"] for m in cell.metrics("per_layer")
            if m["source"] in ("program_span", "program_counter")}
    assert {"tier_fold_ms.round", "bucket_out_mb.round"} <= want
    assert want <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert res["correct"] is True


def test_small_run_is_correct_and_a_half_batch_is_not(monkeypatch):
    from chipbench import faults
    cell = small_cell()
    limits = cell.workload["limits"]
    drv = harness.by_name("drivers", "round").Driver(
        cell.config, cell.workload, 5, None)
    drv.step()
    assert harness.judge(drv.check(), limits)
    faults.half_batch(monkeypatch.setattr)
    drv = harness.by_name("drivers", "round").Driver(
        cell.config, cell.workload, 5, None)
    drv.step()
    assert not harness.judge(drv.check(), limits)
