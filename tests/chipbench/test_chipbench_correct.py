"""What decides ``correct``, driven through the rest of a run on the CPU
at a small size: sound runs pass, and a run whose timed path is broken
underneath reads not correct (chipbench/harness.py, drivers/)."""
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import faults, harness  # noqa: E402

SMALL = {"higgs.silo100": {"rows_train": 8_192, "clients": 4},
         "higgs.churn": {"rows_train": 8_192, "clients": 8,
                         "events": {"block": {"revise": 2, "leave": 1,
                                              "join": 1},
                                    "zipf_s": 1.1, "revise_fraction": 0.25,
                                    "min_active": 4}}}


def small_run(name, seconds=0.3, seed=2 ** 31 + 7):
    cell = harness.Cell.load(name, overrides=SMALL[name])
    return harness.run_cell(cell, seed, seconds, False,
                            t_start=time.perf_counter(), require_tpu=False,
                            log=lambda msg: None)


@pytest.mark.parametrize("name", sorted(SMALL))
def test_sound_run_is_correct(name):
    res = small_run(name)
    assert res["correct"] is True
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert res["window_compiles"] == 0
    assert list(res)[-1] == "checks"
    chk = res["checks"]["rel_err_W"]
    assert chk["value"] < chk["limit"]


@pytest.mark.parametrize("name,fault", [
    pytest.param(name, fault, id=f"{name}-_{fault}") for name, fault in (
        ("higgs.silo100", "half_batch"),
        ("higgs.silo100", "altered_answer"),
        ("higgs.churn", "altered_answer"),
        ("higgs.churn", "unchanged_state"))])
def test_broken_timed_path_reads_not_correct(monkeypatch, name, fault):
    cell = harness.Cell.load(name)
    assert fault in harness.by_name("drivers", cell.workload["driver"]).FAULTS
    getattr(faults, fault)(monkeypatch.setattr)
    res = small_run(name, seconds=0.5)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


def test_each_driver_lists_faults_that_exist():
    for path in sorted((ROOT / "chipbench" / "drivers").glob("*.py")):
        listed = harness.by_name("drivers", path.stem).FAULTS
        assert listed and all(callable(getattr(faults, f)) for f in listed)


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "higgs.silo100",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu_backend():
    out = _run_py(ROOT, {"PYTHONPATH": ""})
    assert out.returncode == 3 and out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in json.loads((ROOT / "BENCHMARK.json").read_text())["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0 and out.stdout == ""


def test_control_rounds_every_product_operand_to_bf16():
    import jax.numpy as jnp
    import numpy as np
    from chipbench import control
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(16, 32)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(32, 8)), jnp.float32)
    a16 = np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32), np.float64)
    b16 = np.asarray(b.astype(jnp.bfloat16).astype(jnp.float32), np.float64)
    np.testing.assert_allclose(control.dot_bf16("ij,jk->ik", a, b),
                               a16 @ b16, rtol=1e-6, atol=1e-6)
    exact = np.asarray(a, np.float64) @ np.asarray(b, np.float64)
    err = [np.abs(np.asarray(f("ij,jk->ik", a, b)) - exact).max()
           for f in (control.dot_bf16, control.dot_three_pass,
                     control.dot_highest)]
    assert err[0] > 30 * err[1] > 0 and err[1] > err[2]


def test_control_reads_not_correct():
    """The control — the reference in the program's place, its products
    in bf16 — on the rows of a sound run, at a size a test can hold: it
    fails the cell's limits through the comparison a run makes, which the
    program's own readings pass."""
    import jax
    from chipbench import common, control, reference
    cell = harness.Cell.load("higgs.silo100", overrides=SMALL["higgs.silo100"])
    limits = cell.workload["limits"]
    drv = harness.by_name("drivers", "round").Driver(
        cell.config, cell.workload, 3, None)
    drv.step()
    ref = reference.stats(common.host_blocks(*drv.live_parts()))
    sound = reference.check(drv.result_W(), drv.solved.stats, ref, drv.lam)
    assert harness.judge(sound, limits)
    blocks = (jax.device_put(b)
              for b in common.host_blocks(*drv.live_parts(), rows=4_096))
    W, stats = control.control(blocks, drv.lam)
    ctrl = reference.check(W, stats, ref, drv.lam)
    assert not harness.judge(ctrl, limits)
    assert all(ctrl[k] > 3 * sound[k] for k in limits)


def test_reference_stats_are_the_plain_float64_sums():
    import numpy as np
    from chipbench import datagen, reference
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 5)).astype(np.float32)
    D = datagen.encode(rng.integers(0, 3, 300), 3)
    ref = reference.stats([(X[:120], D[:120]), (X[120:], D[120:])])
    Xb = np.concatenate([np.ones((300, 1)), X.astype(np.float64)], 1)
    Dd = D.astype(np.float64)
    fp2 = (Dd * (1.0 - Dd)) ** 2
    np.testing.assert_allclose(
        ref.G, np.einsum("nk,nm,np->kmp", fp2, Xb, Xb), rtol=1e-12)
    np.testing.assert_allclose(
        ref.M, Xb.T @ (fp2 * np.log(Dd / (1.0 - Dd))), rtol=1e-12)
    assert ref.n == 300
    assert reference.rel_err_stats(None, ref) == float("inf")
    half = (ref.G, ref.M * 1.001, ref.n / 2)
    assert reference.rel_err_stats(half, ref) == pytest.approx(0.5)
