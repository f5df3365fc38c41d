"""Compile the Gram kernels for a TPU v5e that is described, not attached.

Interpret mode never checks the TPU's tiling rules or memory; the chip's
compiler does. Each case lowers one kernel entry point with
``interpret=False`` at real widths — HIGGS (m = 29 with the bias, c = 2,
a 77,000-row client, a 100-client fleet bucketed to 131,072 rows) and
FEMNIST (m = 785, c = 62) — and checks that the compiled program holds
the Mosaic kernel and fits one chip's 16 GiB.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library.
"""
import importlib

import pytest

HBM_BYTES = 16 * 2**30

# (entry point, shapes of its three operands[, keyword arguments])
CASES = {
    "gram_stats-higgs": ("gram_stats", [(77000, 29), (77000,), (77000,)]),
    "gram_stats_shared-higgs": (
        "gram_stats_shared", [(77000, 29), (77000,), (77000, 2)]),
    "gram_stats_multi-higgs": (
        "gram_stats_multi", [(77000, 29), (77000, 2), (77000, 2)]),
    "gram_stats_fleet-higgs": (
        "gram_stats_fleet",
        [(100, 131072, 29), (100, 131072, 2), (100, 131072, 2)]),
    "gram_stats_fleet_shared-higgs": (
        "gram_stats_fleet_shared",
        [(100, 131072, 29), (100, 131072, 1), (100, 131072, 2)]),
    "gram_stats_multi-femnist": (
        "gram_stats_multi", [(227, 785), (227, 62), (227, 62)]),
    "gram_stats_fleet-femnist": (
        "gram_stats_fleet", [(16, 256, 785), (16, 256, 62), (16, 256, 62)]),
    # an edge aggregator's bucket folded in the kernel: one (62, 896, 896)
    # output block for the 32 clients
    "gram_stats_fleet_fold-femnist": (
        "gram_stats_fleet", [(32, 512, 785), (32, 512, 62), (32, 512, 62)],
        {"fold": True}),
}


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:      # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip: keep the cache out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    import jax
    import jax.numpy as jnp
    kernels = importlib.import_module("repro.kernels.gram_stats")
    name, shapes, *kw = CASES[case]
    fn = getattr(kernels, name)
    kw = kw[0] if kw else {}
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(lambda *a: fn(*a, interpret=False, **kw)).lower(
        *args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES, f"{case}: {used / 2**30:.2f} GiB"


# one client's whole statistics pass (prep, kernel, casts) as the single
# program ``client_gram_stats(backend="pallas")`` issues: (n, m, c)
CLIENT_PASSES = {"higgs-silo": (77000, 28, 2), "mnist": (600, 784, 10)}


@pytest.mark.parametrize("case", sorted(CLIENT_PASSES))
def test_client_pass_compiles_for_v5e(one_chip, case):
    import jax
    import jax.numpy as jnp
    from repro.core import solver
    n, m, c = CLIENT_PASSES[case]
    compiled = solver._gram_stats.lower(
        jax.ShapeDtypeStruct((n, m), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((n, c), jnp.float32, sharding=one_chip),
        act="logistic", add_bias=True, dtype=jnp.float32, backend="pallas",
        interpret=False).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 1
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES, f"{case}: {used / 2**30:.2f} GiB"
