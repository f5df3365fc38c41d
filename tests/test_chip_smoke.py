"""The chip smoke's phases on the CPU, at a tiny HIGGS size.

``chip_smoke.py`` runs these phases at full size on a TPU; here the same
code runs in interpret mode against the same float64 reference and the
same bound, and the script itself must refuse a machine without a TPU.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.launch import compile_cache, smoke

ROOT = Path(__file__).resolve().parents[1]
TINY = dict(scale=2e-4, n_clients=4)       # 1,540 train / 660 test rows


def _env(**kw):
    """A child's environment: the CPU, this checkout's sources, and the
    host's own device count unless ``XLA_FLAGS`` is given."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.update(kw)
    return env


@pytest.mark.parametrize("phase", ["a", "b", "c", "d"])
def test_smoke_phase_meets_bound_on_cpu(phase):
    (out,) = smoke.run([phase], **TINY)
    assert out["phase"] == phase
    assert out["rel_err_W"] <= smoke.MAX_REL_ERR
    assert abs(out["acc"] - out["acc_ref"]) <= smoke.MAX_ACC_GAP
    fused = smoke.PHASES[phase][1].get("fused", False)
    assert out["dispatches"] == (1 if fused else TINY["n_clients"])


def test_reference_solve_matches_repo_solver_in_float64():
    """The numpy reference and the repo's JAX eq.-3 oracle agree in f64
    — two independent implementations of the same solve."""
    import jax.numpy as jnp
    from repro.core import centralized_solve_gram
    from repro.core.util import enable_x64
    (X, y), _ = smoke.load_higgs(scale=1e-4, seed=3)
    W64 = smoke.reference_solve(X, y)
    with enable_x64():
        W = centralized_solve_gram(
            X.astype(np.float64), smoke._soft_targets(y, smoke.N_CLASSES),
            lam=smoke.LAM, dtype=jnp.float64)
        np.testing.assert_allclose(np.asarray(W), W64, rtol=1e-9,
                                   atol=1e-12)


def test_smoke_phase_fails_loudly_past_the_bound(monkeypatch):
    monkeypatch.setattr(smoke, "MAX_REL_ERR", 0.0)
    with pytest.raises(smoke.SmokeFailure, match="relative W error"):
        smoke.run(["c"], **TINY)


def test_chip_smoke_refuses_a_cpu_backend():
    res = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         env=_env(), capture_output=True, text=True,
                         timeout=300)
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout
    assert "needs a TPU" in res.stderr


def test_mesh_phase_on_four_virtual_devices():
    """Rehearsal of ``chip_smoke.py --chips 4``: the mesh round shards
    the rows over four devices and still meets the bound."""
    code = textwrap.dedent("""
        import json, jax
        from repro.launch import smoke
        assert len(jax.devices()) == 4
        (out,) = smoke.run(["mesh"], scale=2e-4, n_clients=4)
        print(json.dumps(out))
    """)
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert res.returncode == 0, res.stderr[-2000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["rel_err_W"] <= smoke.MAX_REL_ERR


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_writes_only_where_the_env_says(tmp_path):
    """With ``JAX_COMPILATION_CACHE_DIR`` set, compiled programs land
    there and the repo's own cache directory is not touched."""
    repo_cache = ROOT / ".jax_cache"
    before = sorted(repo_cache.iterdir()) if repo_cache.exists() else None
    code = textwrap.dedent("""
        from repro.launch.compile_cache import enable_compile_cache
        print(enable_compile_cache())
        import jax, jax.numpy as jnp
        jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)))
    """)
    cache = tmp_path / "cache"
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300,
        env=_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                 JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0"))
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.strip() == str(cache)
    assert any(cache.iterdir())
    after = sorted(repo_cache.iterdir()) if repo_cache.exists() else None
    assert after == before
