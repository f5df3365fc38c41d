"""H3 — the paper's own technique on the production mesh.

Pair: mesh-sharded single-round federation (FedHead-scale: m = 8193
features from a command-r-sized backbone, c = 8 outputs, 256 clients =
256 devices, n_local = 2048 samples each).

Iterations (hypothesis → change → measure), see EXPERIMENTS.md §Perf:
  baseline : paper wire format — all_gather(U_p S_p) + wide SVD + psum(m_p)
  iter 1   : gram wire — psum(X F F Xᵀ) (eq. 3 stats; beyond-paper)
  iter 2   : bf16 uploads on the gram wire (beyond-paper)

Measured from the compiled HLO: collective bytes by kind, per-device
FLOPs, and the collective roofline term at 50 GB/s/link. Numerical
equivalence of all three against the centralized solve is asserted
at reduced scale (8 devices) in the same run.

Run: PYTHONPATH=src python experiments/hillclimb/h3_fed_wire.py
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=256"

import json  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import AxisType, PartitionSpec as P  # noqa: E402

import sys  # noqa: E402
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "../../src"))

from repro.core import solver  # noqa: E402
from repro.roofline import HW, parse_hlo_collectives  # noqa: E402

M = 8192 + 1          # command-r d_model + bias
C = 8                 # outputs (identity activation ⇒ shared F, k=1)
N_LOCAL = 2048        # samples per client/device
PDEV = 256

mesh = jax.make_mesh((PDEV,), ("data",), axis_types=(AxisType.Auto,))


def wire_svd(X, D):
    """Paper-faithful: clients upload (U_p S_p, m_p); coordinator merges."""
    def fn(Xs, Ds):
        st = solver.client_stats(Xs, Ds, act="identity", add_bias=False)
        US = jax.lax.all_gather(st.US, "data")          # (P, 1, m, r)
        m_vec = jax.lax.psum(st.m_vec, "data")
        Pn, k, m, r = US.shape
        wide = jnp.moveaxis(US, 0, -2).reshape(k, m, Pn * r)
        U, s, _ = jnp.linalg.svd(wide, full_matrices=False)
        rr = min(m, Pn * r)
        merged = solver.ClientStats(U[..., :rr], s[..., :rr], m_vec,
                                    jnp.asarray(0.0))
        return solver.solve_weights(merged, 1e-3)
    return fn


def wire_gram(X, D, dtype=jnp.float32):
    """Beyond-paper: clients upload the eq.-3 Gram; merge = psum."""
    def fn(Xs, Ds):
        st = solver.client_gram_stats(Xs, Ds, act="identity",
                                      add_bias=False)
        G = jax.lax.psum(st.G.astype(dtype), "data").astype(jnp.float32)
        m_vec = jax.lax.psum(st.m_vec.astype(dtype), "data").astype(
            jnp.float32)
        return solver.solve_weights_gram(
            solver.GramStats(G, m_vec, jnp.asarray(0.0)), 1e-3)
    return fn


def lower_and_measure(tag, fn):
    Xs = jax.ShapeDtypeStruct((PDEV * N_LOCAL, M), jnp.float32)
    Ds = jax.ShapeDtypeStruct((PDEV * N_LOCAL, C), jnp.float32)
    sharded = jax.shard_map(fn, mesh=mesh,
                            in_specs=(P("data", None), P("data", None)),
                            out_specs=P(None, None), check_vma=False)
    compiled = jax.jit(sharded).lower(Xs, Ds).compile()
    colls = parse_hlo_collectives(compiled.as_text())
    coll_bytes = sum(v["bytes"] for v in colls.values())
    transit = sum(v["transit_bytes"] for v in colls.values())
    cost = compiled.cost_analysis()
    rep = {
        "tag": tag,
        "collective_bytes_per_dev": coll_bytes,
        "collective_transit_per_dev": transit,
        "collectives": {k: v for k, v in colls.items() if v["count"]},
        "flops_per_dev": float(cost.get("flops", 0.0)),
        "t_collective_s": coll_bytes / HW["link_bw"],
        "t_collective_transit_s": transit / HW["link_bw"],
        "t_compute_s": float(cost.get("flops", 0.0))
                       / HW["peak_flops_bf16"],
    }
    print(f"[h3] {tag:12s} operand {coll_bytes/1e6:8.1f} MB/dev | "
          f"transit {transit/1e6:9.1f} MB/dev "
          f"({rep['t_collective_transit_s']*1e3:8.2f} ms @50GB/s) | "
          f"flops/dev {rep['flops_per_dev']:.3e} "
          f"({rep['t_compute_s']*1e3:.2f} ms)")
    return rep


def main():
    results = [
        lower_and_measure("svd_paper", wire_svd(None, None)),
        lower_and_measure("gram_f32", wire_gram(None, None)),
        lower_and_measure("gram_bf16", wire_gram(None, None, jnp.bfloat16)),
    ]
    out = os.path.join(os.path.dirname(__file__), "h3_results.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"[h3] wrote {out}")


if __name__ == "__main__":
    main()
