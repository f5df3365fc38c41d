"""The benchmark's work functions and peak table (chipbench/work.py)."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import work  # noqa: E402

HIGGS = dict(m=29, k=2, c=2)
MNIST = dict(m=785, k=10, c=10)


@pytest.mark.parametrize("n,shape,flops,nbytes", [
    # one HIGGS silo of 77,000 rows: 2·n·29²·2 + 2·n·29·2
    (77_000, HIGGS, 2 * 77_000 * 29 * 29 * 2 + 2 * 77_000 * 29 * 2,
     4 * (77_000 * 29 + 77_000 * 2 + 77_000 * 2 + 2 * 29 * 29 + 29 * 2)),
    # one MNIST client of 600 rows
    (600, MNIST, 2 * 600 * 785 * 785 * 10 + 2 * 600 * 785 * 10,
     4 * (600 * 785 + 600 * 10 + 600 * 10 + 10 * 785 * 785 + 785 * 10)),
])
def test_gram_work_per_client(n, shape, flops, nbytes):
    assert work.gram_flops(n, **shape) == flops
    assert work.gram_bytes(n, **shape) == nbytes


def test_round_work_sums_clients_fold_and_solve():
    ns = [600] * 100
    st = work.stats_work(ns, **MNIST)
    assert st["flops"] == 100 * work.gram_flops(600, **MNIST)
    # the whole MNIST round: 7.4e11 Gram FLOPs, the fold and ten solves
    total = work.round_flops(ns, **MNIST)
    assert total == st["flops"] + 99 * (10 * 785 ** 2 + 785 * 10 + 1) \
        + 10 * (785 ** 3 // 3 + 4 * 785 ** 2)
    assert 7.4e11 < total < 7.5e11


def test_higgs_round_is_memory_bound_and_mnist_compute_bound():
    pk = work.peaks("TPU v5 lite")
    h = work.stats_work([77_000] * 100, **HIGGS)
    t, bound = work.roofline(h["flops"], h["bytes"], pk)
    assert bound == "bytes" and t == pytest.approx(h["bytes"] / 819e9)
    mn = work.stats_work([600] * 100, **MNIST)
    t, bound = work.roofline(mn["flops"], mn["bytes"], pk)
    assert bound == "flops" and t == pytest.approx(mn["flops"] / 197e12)


def test_peaks_keyed_by_device_kind():
    pk = work.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    assert pk["hbm_bytes"] == 16e9
    assert "TPU v5e" in pk["source"]
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")
