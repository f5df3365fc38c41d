"""The benchmark's federations: device generator, partitions, and the
churn cell's shape-keeping revision (chipbench/datagen.py, generators/,
partitions/, drivers/)."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import datagen, harness  # noqa: E402
from repro.data import partition, synthetic  # noqa: E402

HIGGS = json.loads((ROOT / "chipbench/configs/higgs.json").read_text())
MNIST = json.loads((ROOT / "chipbench/configs/mnist.json").read_text())
GAUSSIAN = harness.by_name("generators", "synthetic_gaussian")
DIRICHLET = harness.by_name("partitions", "dirichlet")


def test_seeds_take_any_whole_number():
    a, _ = datagen.seeds(2 ** 40 + 3)
    b, _ = datagen.seeds(2 ** 40 + 4)
    c, _ = datagen.seeds(2 ** 40 + 3)
    assert a != b and a == c and 0 <= a < 2 ** 32


def test_device_rows_match_the_repository_generator_statistics():
    n = 60_000
    X, D, y = (np.asarray(a) for a in GAUSSIAN.Source(HIGGS, 5).rows(0, n))
    Xr, yr = synthetic.generate("higgs", scale=n / synthetic.HIGGS.n,
                                seed=5)
    assert X.shape == Xr.shape == (n, 28) and X.dtype == np.float32
    # same law, other random streams: label balance, spread, class gap
    assert abs(y.mean() - yr.mean()) < 0.03
    assert np.std(X, 0).mean() == pytest.approx(np.std(Xr, 0).mean(),
                                                rel=0.08)
    gap = np.abs(X[y == 1].mean(0) - X[y == 0].mean(0)).mean()
    gap_r = np.abs(Xr[yr == 1].mean(0) - Xr[yr == 0].mean(0)).mean()
    assert gap == pytest.approx(gap_r, rel=0.5)
    # targets: the program's 0.05 / 0.95 one-hot encoding of the labels
    np.testing.assert_array_equal(D, datagen.encode(y, 2))


@pytest.mark.parametrize("kind,key", [("generators", "generator"),
                                      ("partitions", "partition")])
def test_a_name_without_its_file_is_an_error(kind, key):
    config = dict(HIGGS, generator="synthetic_gaussian")
    wl = {"partition": "iid", "clients": 2, "rows": 64}
    (config if key == "generator" else wl)[key] = "no_such_" + key
    with pytest.raises(ValueError, match=f"no {kind} named 'no_such_{key}'"):
        datagen.federation(config, wl, 1)


def test_flip_threshold_cuts_the_stated_quantile():
    src = GAUSSIAN.Source(HIGGS, 9)
    import jax
    X, _, _ = src.rows(3, 40_000)
    X = np.asarray(X, np.float64)
    q = (X[:, :14] ** 2).sum(1) - (X[:, 14:] ** 2).sum(1)
    assert np.mean(q > float(src.thr)) == pytest.approx(0.6 * 0.25,
                                                        abs=0.01)
    assert isinstance(src.means, jax.Array)


@pytest.mark.parametrize("P,alpha,seed", [(10, 0.3, 0), (200, 0.3, 1),
                                          (50, 5.0, 2)])
def test_dirichlet_matches_the_repository_partitioner(P, alpha, seed):
    rng = np.random.default_rng(100 + seed)
    y = rng.integers(0, 2, 20_000).astype(np.int32)
    rows = np.arange(len(y))[:, None]
    ref = partition.dirichlet(rows, y, P, alpha=alpha, seed=seed)
    got = DIRICHLET.indices(y, P, alpha, np.random.default_rng(seed))
    assert [len(g) for g in got] == [len(r[1]) for r in ref]
    for g, (Xr, yr) in zip(got, ref):
        np.testing.assert_array_equal(g, Xr[:, 0])
        np.testing.assert_array_equal(y[g], yr)


def test_dirichlet_federation_sizes_do_not_depend_on_the_run_seed():
    wl = {"partition": "dirichlet", "clients": 30, "alpha": 0.3,
          "partition_seed": 0, "rows": 20_000}
    sizes = []
    for seed in (1, 2):
        _, pX, pd = datagen.federation(HIGGS, wl, seed)
        assert all(x.shape[0] == d.shape[0] for x, d in zip(pX, pd))
        sizes.append(sorted(int(x.shape[0]) for x in pX))
    assert sum(sizes[0]) >= 20_000
    # the same sizes in another client order, up to the few rows that
    # the seed's label counts move between the classes
    assert np.abs(np.subtract(*sizes)).max() <= 0.02 * max(sizes[0]) + 2


def test_pathological_split_gives_two_label_shards_per_client():
    wl = {"partition": "pathological", "clients": 20,
          "shards_per_client": 2, "rows": 6_000}
    src, pX, pd = datagen.federation(MNIST, wl, 3)
    assert len(pX) == 20
    assert all(x.shape == (300, 784) and d.shape == (300, 10)
               for x, d in zip(pX, pd))
    labels = [np.argmax(np.asarray(d), 1) for d in pd]
    # every row once: the 40 label-sorted shards of 150 rows are dealt out
    allX = np.concatenate([np.asarray(x) for x in pX])
    assert len(np.unique(allX, axis=0)) == 6_000
    # a shard is label-sorted, so a client sees at most 2 labels per shard
    assert all(len(np.unique(lb[:150])) <= 2 and len(np.unique(lb[150:]))
               <= 2 for lb in labels)
    assert np.mean([len(np.unique(lb)) <= 2 for lb in labels]) > 0.5


def test_iid_shards_are_equal_and_distinct():
    wl = {"partition": "iid", "clients": 4, "rows": 4_000}
    _, pX, pd = datagen.federation(HIGGS, wl, 11)
    assert [x.shape for x in pX] == [(1_000, 28)] * 4
    assert not np.allclose(np.asarray(pX[0]), np.asarray(pX[1]))


def test_churn_revision_keeps_the_shard_shape():
    cell = harness.Cell.load("higgs.churn", overrides={
        "rows_train": 4_096, "clients": 4,
        "events": {"block": {"revise": 2, "leave": 1, "join": 1},
                   "zipf_s": 1.1, "revise_fraction": 0.25,
                   "min_active": 2}})
    drv = harness.by_name("drivers", "events").Driver(
        cell.config, cell.workload, 5, None)
    cid = drv.ledger.clients[0]
    before = np.asarray(drv.parts_X[cid])
    drv._apply("revise", cid)
    after = np.asarray(drv.parts_X[cid])
    assert after.shape == before.shape == (1_024, 28)
    np.testing.assert_array_equal(after[:768], before[256:])
    assert not np.allclose(after[768:], before[:256])
    assert drv.parts_d[cid].shape == (1_024, 2)
