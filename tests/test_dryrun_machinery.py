"""Dry-run machinery tests (subprocess: needs forced host device count).

A reduced-scale end-to-end check of the deliverable-(e) pipeline: build a
multi-device mesh, lower + compile train/prefill/decode for a smoke arch,
and verify the roofline JSON has sane fields. The full 512-device sweep is
driven by ``python -m repro.launch.dryrun --all`` (see EXPERIMENTS.md).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro import configs
from repro.launch.dryrun import combo_supported, input_specs


def test_input_specs_cover_all_modalities():
    for arch in ("smollm-135m", "whisper-small", "pixtral-12b"):
        cfg = configs.get(arch)
        for shape_name in ("train_4k", "prefill_32k", "decode_32k"):
            shape = configs.get_shape(shape_name)
            specs = input_specs(cfg, shape, shape.kind)
            assert "tokens" in specs
            if shape.kind == "train":
                assert "labels" in specs
            if cfg.modality == "audio":
                assert "encoder_embeds" in specs
            if cfg.modality == "vlm" and shape.kind != "decode":
                assert "image_embeds" in specs
            for s in specs.values():   # stand-ins, not arrays
                assert not hasattr(s, "addressable_shards")


def test_long_decode_policy():
    expect_run = {"mamba2-2.7b", "jamba-v0.1-52b", "smollm-135m-swa"}
    shape = configs.get_shape("long_500k")
    for arch in configs.REGISTRY:
        ok, reason = combo_supported(configs.get(arch), shape)
        assert ok == (arch in expect_run), (arch, reason)
        if not ok:
            assert "skipped" in reason or "sliding-window" in reason


_SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    from jax.sharding import AxisType
    from repro.launch.dryrun import lower_combo
    import repro.launch.mesh as mesh_lib
    mesh = jax.make_mesh((4, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    import dataclasses
    import repro.configs as configs
    # reduced smoke configs on the small mesh, all three kinds
    for arch, shape in [("smollm-135m", "train_4k"),
                        ("mamba2-2.7b", "decode_32k")]:
        cfg = configs.get(arch, smoke=True)
        configs.REGISTRY[arch] = cfg    # route lower_combo to smoke cfg
        r = lower_combo(arch, shape, mesh=mesh, verbose=False)
        assert r["dominant"] in ("compute", "memory", "collective"), r
        assert r["hlo_flops"] > 0 and r["hlo_bytes"] > 0
        assert r["chips"] == 16
        print("OK", arch, shape, r["dominant"])
    print("DRYRUN_MACHINERY_OK")
""")


@pytest.mark.slow
def test_lower_combo_small_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN_MACHINERY_OK" in out.stdout
