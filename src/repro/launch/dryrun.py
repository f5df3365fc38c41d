import os
import sys

if __name__ == "__main__":
    # Run as a program (and so every per-combo child it spawns): a
    # CPU-only tool on 512 simulated host devices (``--devices`` for a
    # test-scale count), kept off any accelerator, which belongs to one
    # process at a time. This MUST run before jax is imported — jax locks
    # the host device count at first init. Importing the module (tests)
    # changes neither, so importers keep their own devices.
    os.environ["JAX_PLATFORMS"] = "cpu"
    _n = sys.argv[sys.argv.index("--devices") + 1] \
        if "--devices" in sys.argv else "512"
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_n}"

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Dict, Optional, Tuple  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro import configs                                   # noqa: E402
from repro.launch import mesh as mesh_lib                   # noqa: E402
from repro.models import build_model, param_count           # noqa: E402
from repro.roofline import (HW, parse_hlo_collectives,     # noqa: E402
                            roofline_report)
from repro.sharding import specs as sh                      # noqa: E402
from repro.train import init_train_state, make_train_step   # noqa: E402


# --------------------------------------------------------------- inputs
def input_specs(cfg, shape, kind: str) -> Dict[str, jax.ShapeDtypeStruct]:
    """ShapeDtypeStruct stand-ins for every model input — weak-type-correct,
    shardable, zero allocation."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    f32 = jnp.float32
    if kind == "decode":
        out = {"tokens": jax.ShapeDtypeStruct((B, 1), i32)}
    else:
        out = {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
        if kind == "train":
            out["labels"] = jax.ShapeDtypeStruct((B, S), i32)
    if cfg.modality == "audio":
        out["encoder_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.encoder_len, cfg.d_model), f32)
    elif cfg.modality == "vlm" and kind != "decode":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.num_image_tokens, cfg.d_model), f32)
    return out


def batch_in_shardings(specs_dict, mesh):
    baxes = mesh_lib.batch_axes(mesh)

    def spec(s):
        b = s.shape[0]
        first = baxes if b % sh.axis_size(mesh, baxes) == 0 else None
        return NamedSharding(mesh, P(first, *([None] * (len(s.shape) - 1))))

    return {k: spec(v) for k, v in specs_dict.items()}


_CACHE_RULES = [
    (r"/(k|v|ck|cv)$", (None, "batch", None, "kv_heads", None)),
    (r"/ssm$",         (None, "batch", "ssm_heads", None, None)),
    (r"/conv$",        (None, "batch", None, None)),
    (r"len$",          None),
]


def cache_shardings(cache_shapes, mesh, rules, cache_rules=None):
    import re
    cache_rules = cache_rules or _CACHE_RULES

    def spec_of(path, leaf):
        pstr = "/" + "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                              for k in path)
        for pat, logical in cache_rules:
            if re.search(pat, pstr):
                if logical is None:
                    return NamedSharding(mesh, P())
                logical = logical[-leaf.ndim:] if leaf.ndim <= len(logical) \
                    else (None,) * (leaf.ndim - len(logical)) + logical
                return NamedSharding(
                    mesh, sh.logical_to_spec(mesh, rules, logical,
                                             leaf.shape))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(spec_of, cache_shapes)


# ---------------------------------------------------------------- runner
def combo_supported(cfg, shape) -> Tuple[bool, str]:
    if shape.name == "long_500k" and not cfg.supports_long_decode():
        return False, ("full quadratic attention, no sliding-window "
                       "variant — skipped per DESIGN.md §5")
    return True, ""


def _bf16_params(tree):
    """Serving-weight dtype: bf16 storage for all float params."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16)
        if s.dtype == jnp.float32 else s, tree)


def _lower_one(cfg, shape, kind, mesh, rules, cache_rules=None,
               serve_bf16=False):
    """Lower + compile one (cfg, shape, kind) on the mesh. Returns
    (lowered, compiled, t_lower, t_compile)."""
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    bspecs = input_specs(cfg, shape, kind)
    bshard = batch_in_shardings(bspecs, mesh)
    t0 = time.time()
    with sh.use_rules(mesh, rules):
        if kind == "train":
            state_shape = jax.eval_shape(
                lambda: init_train_state(model, key))
            pspecs = sh.named_shardings(state_shape, mesh, rules)
            step = make_train_step(model)
            lowered = jax.jit(
                step, in_shardings=(pspecs, bshard),
            ).lower(state_shape, bspecs)
        elif kind == "prefill":
            params_shape = jax.eval_shape(model.init, key)
            if serve_bf16:
                params_shape = _bf16_params(params_shape)
            pspecs = sh.named_shardings(params_shape, mesh, rules)

            def prefill_fn(params, batch):
                return model.prefill(params, batch, shape.seq_len)

            lowered = jax.jit(
                prefill_fn, in_shardings=(pspecs, bshard),
            ).lower(params_shape, bspecs)
        else:  # decode
            params_shape = jax.eval_shape(model.init, key)
            if serve_bf16:
                params_shape = _bf16_params(params_shape)
            pspecs = sh.named_shardings(params_shape, mesh, rules)
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len))
            cspecs = cache_shardings(cache_shape, mesh, rules,
                                     cache_rules)
            lowered = jax.jit(
                model.decode_step,
                in_shardings=(pspecs, cspecs, bshard["tokens"]),
            ).lower(params_shape, cache_shape, bspecs["tokens"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    return lowered, compiled, t_lower, t_compile


def _cost_of(compiled) -> Dict[str, float]:
    """Per-device cost terms (XLA cost_analysis reports per-partition
    values with the 2mnk dot convention — calibrated, see EXPERIMENTS.md)."""
    cost = compiled.cost_analysis()
    colls = parse_hlo_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": sum(v["bytes"] for v in colls.values()),
            "coll_transit": sum(v["transit_bytes"] for v in colls.values()),
            "collectives": colls}


def extrapolated_cost(cfg, shape, kind, mesh, rules,
                      cache_rules=None, serve_bf16=False
                      ) -> Dict[str, float]:
    """True per-device cost via 1-period/2-period unrolled variants.

    XLA cost_analysis counts while-loop (lax.scan) bodies ONCE, so the full
    scanned module under-reports by ~n_periods×. We compile tiny unrolled
    variants A (1 period) and B (2 periods) and extrapolate linearly:
    cost(N) = A + (N-1)·(B-A). Exact for everything outside the SSD
    inter-chunk scan (negligible FLOPs) and the MoE group scan (disabled in
    unrolled variants).
    """
    from repro.models.transformer import stack_period
    period = stack_period(cfg)
    np_full = cfg.n_layers // period
    variants = []
    for k in (1, 2):
        kw = dict(n_layers=k * period, unroll_layers=True)
        if cfg.modality == "audio":
            kw["encoder_layers"] = k   # enc scan scales with the same k
        cfg_k = dataclasses.replace(cfg, **kw)
        _, compiled, _, _ = _lower_one(cfg_k, shape, kind, mesh, rules,
                                       cache_rules, serve_bf16)
        variants.append(_cost_of(compiled))
    a, b = variants

    def ext(key):
        delta = b[key] - a[key]
        if delta < 0:        # fusion noise between variants: fall back to
            delta = b[key] / 2.0   # the 2-period module's per-period mean
        return max(a[key], 0.0) + (np_full - 1) * delta

    return {"flops": ext("flops"), "bytes": ext("bytes"),
            "coll_bytes": ext("coll_bytes"),
            "coll_transit": ext("coll_transit"),
            "per_period": {k: b[k] - a[k]
                           for k in ("flops", "bytes", "coll_bytes")}}


def lower_combo(arch: str, shape_name: str, *, multi_pod: bool = False,
                mesh=None, rules_overrides=None, cache_rules=None,
                cfg_overrides=None, verbose: bool = True,
                cost_extrapolate: bool = True, serve_bf16: bool = False):
    cfg = configs.get(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = configs.get_shape(shape_name)
    ok, reason = combo_supported(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": reason}

    mesh = mesh or mesh_lib.make_production_mesh(multi_pod=multi_pod)
    baxes = mesh_lib.batch_axes(mesh)
    rules = {**sh.DEFAULT_RULES, "batch": baxes,
             **(rules_overrides or {})}
    kind = shape.kind

    # 1) full-model lowering: proves the sharding config compiles, gives the
    #    memory analysis and the collective schedule of the real module.
    lowered, compiled, t_lower, t_compile = _lower_one(
        cfg, shape, kind, mesh, rules, cache_rules, serve_bf16)
    try:
        mem = compiled.memory_analysis()
        mem_info = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        mem_info = {"error": str(e)}
    raw = _cost_of(compiled)

    # 2) cost model: extrapolated per-device flops/bytes/collective bytes
    if cost_extrapolate:
        ext = extrapolated_cost(cfg, shape, kind, mesh, rules,
                                cache_rules, serve_bf16)
    else:
        ext = {k: raw[k] for k in ("flops", "bytes", "coll_bytes",
                                   "coll_transit")}

    chips = mesh.devices.size
    n_active = param_count(cfg, active_only=True)
    if kind == "train":
        model_flops = 6 * n_active * shape.global_batch * shape.seq_len
    elif kind == "prefill":
        model_flops = 2 * n_active * shape.global_batch * shape.seq_len
    else:
        model_flops = 2 * n_active * shape.global_batch  # one token each

    # cost_analysis values are per-device; report() wants whole-job totals
    report = roofline_report(flops=ext["flops"] * chips,
                             bytes_accessed=ext["bytes"] * chips,
                             collective_bytes=ext["coll_bytes"] * chips,
                             chips=chips, model_flops=model_flops)
    t_coll_transit = ext["coll_transit"] / HW["link_bw"]
    result = {
        "t_collective_transit_s": t_coll_transit,
        "arch": arch, "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "mesh_axes": list(mesh.axis_names),
        "chips": chips,
        "kind": kind,
        "params_total": param_count(cfg),
        "params_active": n_active,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_info,
        "collectives": raw["collectives"],   # schedule of the real module
        "raw_scan_counted_once": {k: raw[k]
                                  for k in ("flops", "bytes", "coll_bytes")},
        **report,
    }
    if verbose:
        mb = (mem_info.get("peak_bytes") or 0) / 1e9
        print(f"[dryrun] {arch} × {shape_name} × {result['mesh']}: "
              f"compute {report['t_compute_s']:.3e}s  "
              f"memory {report['t_memory_s']:.3e}s  "
              f"collective {report['t_collective_s']:.3e}s  "
              f"→ {report['dominant']}-bound  (peak {mb:.2f} GB/dev, "
              f"lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    return result


def run_one(args):
    result = lower_combo(args.arch, args.shape, multi_pod=args.multi_pod)
    os.makedirs(args.out, exist_ok=True)
    tag = "multipod" if args.multi_pod else "pod"
    path = os.path.join(args.out, f"{args.arch}_{args.shape}_{tag}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] wrote {path}")
    return 0 if ("skipped" in result or result.get("t_compute_s") is not None) \
        else 1


def run_all(args):
    """Sweep every (arch × shape); subprocess-per-combo for isolation."""
    failures = []
    for arch in configs.ARCH_NAMES + ["smollm-135m-swa"]:
        for shape_name in configs.INPUT_SHAPES:
            cfg = configs.get(arch)
            ok, reason = combo_supported(cfg, configs.get_shape(shape_name))
            tag = "multipod" if args.multi_pod else "pod"
            path = os.path.join(args.out, f"{arch}_{shape_name}_{tag}.json")
            if not ok:
                os.makedirs(args.out, exist_ok=True)
                with open(path, "w") as f:
                    json.dump({"arch": arch, "shape": shape_name,
                               "skipped": reason}, f, indent=1)
                print(f"[dryrun] SKIP {arch} × {shape_name}: {reason}")
                continue
            if args.resume and os.path.exists(path):
                print(f"[dryrun] exists, skipping {path}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape_name, "--out", args.out]
            if args.multi_pod:
                cmd.append("--multi-pod")
            print(f"[dryrun] >>> {arch} × {shape_name} ({tag})", flush=True)
            rc = subprocess.run(cmd).returncode
            if rc != 0:
                failures.append((arch, shape_name))
                print(f"[dryrun] FAILED {arch} × {shape_name}")
    if failures:
        print(f"[dryrun] {len(failures)} failures: {failures}")
        return 1
    print("[dryrun] all combos OK")
    return 0


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", choices=sorted(configs.REGISTRY))
    ap.add_argument("--shape", choices=sorted(configs.INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--resume", action="store_true",
                    help="with --all: skip combos whose JSON already exists")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--devices", type=int, default=512,
                    help="host device override (consumed before jax init)")
    args = ap.parse_args()
    if args.all:
        sys.exit(run_all(args))
    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --all)")
    try:
        sys.exit(run_one(args))
    except Exception:
        traceback.print_exc()
        sys.exit(1)


if __name__ == "__main__":
    main()
