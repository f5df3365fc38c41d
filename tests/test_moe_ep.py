"""Expert-parallel shard_map MoE (§Perf H1) — correctness vs the pjit
reference path, on a 2×2 forced-device mesh (subprocess)."""
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np, jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro import configs
    from repro.models import moe as moe_mod
    from repro.models import moe_ep
    from repro.models import build_model
    from repro.sharding import specs as sh
    from repro.data.pipeline import make_batch

    cfg = configs.get("olmoe-1b-7b", smoke=True)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    p = moe_mod.init_moe(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(4, 16, cfg.d_model)) * 0.5,
        jnp.bfloat16)
    cfg_ep = dataclasses.replace(cfg, moe_ep=True, capacity_factor=4.0)
    cfg_ref = dataclasses.replace(cfg, capacity_factor=4.0)
    out_ref, aux_ref = moe_mod.apply_moe(x, p, cfg_ref)
    with sh.use_rules(mesh):
        assert moe_ep.ep_applicable(x, cfg_ep)
        out_ep, aux_ep = jax.jit(
            lambda x: moe_ep.apply_moe_ep(x, p, cfg_ep))(x)
    np.testing.assert_allclose(np.asarray(out_ref, np.float32),
                               np.asarray(out_ep, np.float32),
                               rtol=5e-2, atol=5e-2)
    assert abs(float(aux_ref["lb_loss"]) - float(aux_ep["lb_loss"])) < 1e-3

    # full-model forward + grads with the EP path active under the mesh
    model = build_model(dataclasses.replace(cfg, moe_ep=True))
    params = model.init(jax.random.PRNGKey(0))
    batch = {k: jnp.asarray(v) for k, v in
             make_batch(cfg, 16, 4, seed=1).items()}
    with sh.use_rules(mesh):
        (loss, _), grads = jax.jit(jax.value_and_grad(
            model.loss, has_aux=True))(params, batch)
    assert bool(jnp.isfinite(loss)), float(loss)
    gn = sum(float(jnp.sum(jnp.abs(g.astype(jnp.float32))))
             for g in jax.tree.leaves(grads))
    assert gn > 0
    print("MOE_EP_OK")
""")


@pytest.mark.slow
def test_moe_ep_matches_reference():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "MOE_EP_OK" in out.stdout
