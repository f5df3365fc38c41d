"""Production mesh construction.

Functions, not module-level constants — importing this module never
touches jax device state. The dry-run entry point sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import; everything else sees the host's real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 two-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests, CPU smoke)."""
    n = len(jax.devices())
    data = data or (n // model)
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def masked_round_specs(axis: str):
    """Partition specs for the masked (secagg) mesh round's collective.

    Inputs, each sharded one-row-per-device along ``axis``: the
    ``(Pₙ, n/Pₙ, m)`` sample shard, the matching target shard, the
    device's ``(1, n_elems, words)`` summed pairwise pad, and its
    noise-share key data (secagg+dp). Output: the ring-reduced
    ``(n_elems, words)`` limb aggregate, replicated — each device masks
    its own statistics before anything leaves it, so the psum only ever
    sees ring elements (`core/engine.py` builds the shard_fn; the pads
    come from ``SecAggSession.flat_pad_sums``).
    """
    from jax.sharding import PartitionSpec as P
    return ((P(axis, None), P(axis, None), P(axis, None, None),
             P(axis, None)), P(None, None))
