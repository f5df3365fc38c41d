"""Expert-parallel MoE via shard_map + all-to-all (§Perf H1).

Why this exists: the pjit scatter-dispatch path defeats the SPMD
partitioner — data-dependent scatter indices force XLA to replicate the
dispatch *and the expert FFN* across the mesh, so every device does the
full global MoE compute (useful-FLOPs ratio 0.003 at baseline).

The shard_map formulation makes the parallelism explicit:

  tokens:   data axes shard the batch; inside the block each model-axis
            peer takes a distinct 1/tp slice of the local tokens
            (sequence-parallel style), so nothing is computed twice.
  dispatch: purely local scatter into an (E, C, d) buffer — no partitioner
            involvement.
  exchange: one all-to-all over the model axis sends each expert's slots
            to the peer that owns it; expert FFN runs on (E/tp) experts ×
            (tp·C) slots; a second all-to-all returns the outputs.
  combine:  local gather + weighted sum, then an all-gather over the model
            axis reassembles the token slices.

Per-device FLOPs = global/|mesh| (the einsums see only local slices), at
the cost of 2 all-to-alls + 1 all-gather of activations per MoE layer —
the classic EP trade measured in EXPERIMENTS.md §Perf H1.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.sharding.specs import axis_size, current_rules
from .layers import cast


def ep_applicable(x, cfg) -> bool:
    ctx = current_rules()
    if ctx is None or not cfg.moe_ep:
        return False
    mesh, rules = ctx
    tp_axes = tuple(rules.get("experts", ()) or ())
    baxes = tuple(rules.get("batch", ()) or ())
    if not tp_axes or not baxes:
        return False
    tp = axis_size(mesh, tp_axes)
    dp = axis_size(mesh, baxes)
    b, s, d = x.shape
    if b % dp or cfg.n_experts % tp:
        return False
    t_loc = (b // dp) * s
    return t_loc % tp == 0 and t_loc // tp >= 1


def apply_moe_ep(x, p, cfg, *, dropless: bool = False
                 ) -> Tuple[jnp.ndarray, Dict]:
    """x: (b, s, d) global. Returns (out, aux). Call only if
    ep_applicable(x, cfg). ``dropless=True``: capacity = local token
    count (inference mode, same contract as ``apply_moe``)."""
    mesh, rules = current_rules()
    tp_axes = tuple(rules["experts"])
    baxes = tuple(rules["batch"])
    assert len(tp_axes) == 1, "expert axis must be a single mesh axis"
    ax = tp_axes[0]
    tp = axis_size(mesh, tp_axes)
    E, K, cf = cfg.n_experts, cfg.top_k, cfg.capacity_factor
    E_loc = E // tp

    def inner(xl, router, wi, wg, wd):
        b_loc, s, d = xl.shape
        T = b_loc * s
        tl = T // tp
        C = tl if dropless else max(int(tl * K / E * cf), 1)
        t = xl.reshape(T, d)
        mi = jax.lax.axis_index(ax)
        ts = jax.lax.dynamic_slice_in_dim(t, mi * tl, tl, 0)   # my slice

        logits = jnp.einsum("td,de->te", ts.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        # aux losses over ALL tokens (psum across every mesh axis)
        all_axes = baxes + tp_axes
        me = jax.lax.pmean(probs.mean(axis=0), all_axes)
        ce = jax.lax.pmean(
            jax.nn.one_hot(gate_idx[:, 0], E).mean(axis=0), all_axes)
        lb_loss = E * jnp.sum(me * ce)
        z_loss = jax.lax.pmean(
            jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2), all_axes)

        # ---- local dispatch (scatter is block-local: no SPMD involved)
        flat_e = gate_idx.reshape(-1)                          # (tl*K,)
        assign = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos = jnp.cumsum(assign, axis=0) - 1
        pos = jnp.take_along_axis(pos, flat_e[:, None], 1)[:, 0]
        keep = pos < C
        dropped = jax.lax.pmean(1.0 - keep.mean(), all_axes)
        safe_pos = jnp.where(keep, pos, C - 1)
        tok_of = jnp.repeat(jnp.arange(tl), K)
        contrib = jnp.where(keep[:, None], ts[tok_of], 0.0)
        buf = jnp.zeros((E, C, d), xl.dtype)
        buf = buf.at[flat_e, safe_pos].add(contrib)

        # ---- exchange: slots → owning expert shard
        buf = buf.reshape(tp, E_loc, C, d)
        buf = jax.lax.all_to_all(buf, ax, split_axis=0, concat_axis=0)
        # (tp, E_loc, C, d): axis 0 is now the source peer
        be = buf.transpose(1, 0, 2, 3).reshape(E_loc, tp * C, d)

        # ---- expert FFN on local experts
        h = jnp.einsum("ecd,edf->ecf", be, wi)
        g = jnp.einsum("ecd,edf->ecf", be, wg)
        y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, wd)

        # ---- return outputs to source peers
        y = y.reshape(E_loc, tp, C, d).transpose(1, 0, 2, 3)
        y = jax.lax.all_to_all(y, ax, split_axis=0, concat_axis=0)
        y = y.reshape(E, C, d)

        # ---- local combine
        picked = y[flat_e, safe_pos]
        w = jnp.where(keep, gate_vals.reshape(-1), 0.0)
        out_slice = jnp.zeros((tl, d), y.dtype).at[tok_of].add(
            picked * w[:, None].astype(y.dtype))

        # ---- reassemble the model-axis token slices
        out = jax.lax.all_gather(out_slice, ax, axis=0, tiled=True)
        aux = {"lb_loss": lb_loss, "z_loss": z_loss,
               "fraction_dropped": dropped}
        return out.reshape(b_loc, s, d), aux

    bspec = P(baxes if len(baxes) > 1 else baxes[0], None, None)
    espec = P(ax, None, None)
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=(bspec, P(None, None), espec, espec, espec),
        out_specs=(bspec, P()), check_vma=False)
    return fn(x, p["router"].astype(jnp.float32), cast(p["experts_wi"]),
              cast(p["experts_wg"]), cast(p["experts_wd"]))
