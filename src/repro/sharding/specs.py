"""Logical-axis sharding rules (DP × TP × optional pod axis).

Models are written once against *logical* axis names; the launcher binds
them to a physical mesh. ``shd(x, "batch", None, "heads", None)`` becomes a
``with_sharding_constraint`` when a rules context is active and a no-op
otherwise (single-device smoke tests).

Every binding is divisibility-checked: a logical axis whose dimension does
not divide by the bound mesh axes is silently replicated (e.g. GQA kv=8
heads on a 16-way model axis, or smollm's d_ff=1536 on 16 devices). This is
what lets one model definition serve 10 architectures × 3 meshes.
"""
from __future__ import annotations

import contextlib
import math
import re
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Union[None, str, Tuple[str, ...]]


# default logical→mesh bindings; the launcher overrides "batch" with
# ("pod", "data") on the multi-pod mesh.
DEFAULT_RULES: Dict[str, Tuple[str, ...]] = {
    "batch": ("data",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ffn": ("model",),
    "vocab": ("model",),
    "experts": ("model",),
    "embed_fsdp": ("data",),   # FSDP shard of weight d_model dims
    "ssm_heads": ("model",),
    "seq": (),                 # sequence stays unsharded (no CP in baseline)
}

_tls = threading.local()


def current_rules():
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def use_rules(mesh: Mesh, overrides: Optional[Dict[str, Tuple[str, ...]]] = None):
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    # drop bindings to axes the mesh doesn't have
    names = set(mesh.axis_names)
    rules = {k: tuple(a for a in (v if isinstance(v, tuple) else (v,))
                      if a in names)
             for k, v in rules.items()}
    prev = current_rules()
    _tls.ctx = (mesh, rules)
    try:
        yield
    finally:
        _tls.ctx = prev


def axis_size(mesh: Mesh, axes: Sequence[str]) -> int:
    return math.prod(mesh.shape[a] for a in axes) if axes else 1


def _resolve_dim(mesh: Mesh, rules, logical: Axes, dim: int) -> Axes:
    if logical is None:
        return None
    mesh_axes = rules.get(logical, ())
    if isinstance(mesh_axes, str):
        mesh_axes = (mesh_axes,)
    if not mesh_axes:
        return None
    if dim % axis_size(mesh, mesh_axes) != 0:
        return None  # divisibility fallback: replicate
    return mesh_axes if len(mesh_axes) > 1 else mesh_axes[0]


def logical_to_spec(mesh: Mesh, rules, logical_axes: Sequence[Axes],
                    shape: Sequence[int]) -> P:
    return P(*[_resolve_dim(mesh, rules, ax, d)
               for ax, d in zip(logical_axes, shape)])


def shd(x: jnp.ndarray, *logical_axes: Axes) -> jnp.ndarray:
    """Constrain an activation's sharding by logical axis names (or no-op)."""
    ctx = current_rules()
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = logical_to_spec(mesh, rules, logical_axes, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def batch_spec(mesh: Mesh, rules=None) -> Tuple[str, ...]:
    rules = rules or DEFAULT_RULES
    axes = rules["batch"]
    return tuple(a for a in axes if a in mesh.axis_names)


# --------------------------------------------------------------------------
# Parameter partition specs, by leaf path pattern.
#
# Weight layout conventions (see repro/models):
#   embed        (vocab, d)            → (vocab, embed_fsdp)
#   wq/wkv       (d, heads, head_dim)  → (embed_fsdp, heads, None)
#   wo           (heads, head_dim, d)  → (heads, None, embed_fsdp)
#   mlp wi/wg    (d, ff)               → (embed_fsdp, ffn)
#   mlp wo       (ff, d)               → (ffn, embed_fsdp)
#   moe experts  (E, d, ff)/(E, ff, d) → (experts, …, ffn on ff dim)
#   ssm in/out   (d, inner…)           → (embed_fsdp, ssm_heads-ish)
# Stacked layer params carry a leading L (or period) dim → None.
# --------------------------------------------------------------------------

_PARAM_RULES = [
    # (regex on '/'-joined path, logical axes for the LAST ndims)
    (r"embed$",            ("vocab", "embed_fsdp")),
    (r"unembed$",          ("embed_fsdp", "vocab")),
    (r"(wq|wk|wv)$",       ("embed_fsdp", "heads", None)),
    (r"wo$",               ("heads", None, "embed_fsdp")),
    # expert-parallel: E on the model axis; the per-expert ff dim stays
    # local (binding it would reuse the model axis — invalid)
    (r"experts_(wi|wg)$",  ("experts", "embed_fsdp", None)),
    (r"experts_wd$",       ("experts", None, "embed_fsdp")),
    (r"(wi|wg)$",          ("embed_fsdp", "ffn")),
    (r"wd$",               ("ffn", "embed_fsdp")),
    (r"router$",           ("embed_fsdp", None)),
    (r"in_proj$",          ("embed_fsdp", "ffn")),
    (r"out_proj$",         ("ffn", "embed_fsdp")),
    (r"conv_w$",           (None, "ffn")),
    (r"(scale|bias|gamma|beta|A_log|ssm_D|dt_bias|norm_w)$", None),
]


def _leaf_logical(path: str, ndim: int):
    for pat, axes in _PARAM_RULES:
        if re.search(pat, path):
            if axes is None:
                return (None,) * ndim
            if ndim == len(axes):
                return axes
            if ndim > len(axes):   # stacked: leading layer dims replicated
                return (None,) * (ndim - len(axes)) + tuple(axes)
            return (None,) * ndim
    return (None,) * ndim


def param_specs(params, mesh: Mesh, rules=None):
    """PartitionSpec pytree for a params pytree, by leaf path."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    names = set(mesh.axis_names)
    rules = {k: tuple(a for a in (v if isinstance(v, tuple) else (v,))
                      if a in names) for k, v in rules.items()}

    def spec_of(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        logical = _leaf_logical(pstr, leaf.ndim)
        return logical_to_spec(mesh, rules, logical, leaf.shape)

    return jax.tree_util.tree_map_with_path(spec_of, params)


def named_shardings(params, mesh: Mesh, rules=None):
    specs = param_specs(params, mesh, rules)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
