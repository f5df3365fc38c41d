"""One federation round on the chip, checked against a float64 solve.

``python chip_smoke.py`` (repo root) runs this module's :func:`main`. It
drives the paper's round through the entry points a user calls —
``FederationEngine.run`` on the gram wire with the logistic activation —
at the paper's full HIGGS size: 11,000,000 × 28 generated from a seed,
split 70/30, the ~7.7 M training rows dealt IID to P = 100 clients
(m = 29 with the bias, c = 2). Every committed ``W`` is compared with a
plain float64 numpy eq.-3 solve over the union of the training rows at
the same λ. The phases:

* ``a`` — per-client loop, ``backend="pallas"``: the k = c kernel once
  per client;
* ``b`` — fused fleet, ``backend="pallas"``: one ``gram_stats_fleet``
  dispatch for the whole federation;
* ``c`` — fused fleet, ``backend="xla"`` (the Python API's default);
* ``d`` — masked fused round (``privacy="secagg"``): the x64 limb ring;
* ``mesh`` — only with ``--chips 4``: the mesh transport's gram round
  (``run_mesh_arrays``), one shard of the rows per device.

A phase passes when ``‖W − W₆₄‖_F / ‖W₆₄‖_F ≤ 1e-3``, its test accuracy
is within 0.1 points of the reference's, and — on a TPU, for the Pallas
phases — its compiled client program holds the kernel
(``tpu_custom_call``). Each phase prints one line; compile and wall
seconds there are bring-up readings, not benchmark numbers. Any failure
raises, so the script exits non-zero before its last line, which is
``{"ok": true, "device": {...}}`` only when every phase passed. Without a
TPU it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

LAM = 1e-3
N_CLIENTS = 100
N_CLASSES = 2
MAX_REL_ERR = 1e-3
MAX_ACC_GAP = 1e-3            # 0.1 accuracy points, as a fraction

# phase name → (what it drives, FederationEngine keyword arguments)
PHASES = {
    "a": ("per-client loop, pallas", dict(backend="pallas")),
    "b": ("fused fleet, pallas", dict(backend="pallas", fused=True)),
    "c": ("fused fleet, xla", dict(backend="xla", fused=True)),
    "d": ("masked fused, pallas",
          dict(backend="pallas", fused=True, privacy="secagg")),
    "mesh": ("mesh transport, pallas",
             dict(backend="pallas", transport="mesh")),
}
KERNEL_PHASES = ("a", "b", "mesh")   # where the kernel must be compiled in


class SmokeFailure(AssertionError):
    """A phase missed its bound."""


def load_higgs(scale: float = 1.0, seed: int = 0):
    """HIGGS-shaped data, split 70/30: ``(X_tr, y_tr), (X_te, y_te)``."""
    from ..data import synthetic
    X, y = synthetic.generate("higgs", scale=scale, seed=seed)
    return synthetic.train_test_split(X, y, seed=seed)


def _soft_targets(y, c: int) -> np.ndarray:
    """The repo's label encoding (0.05 / 0.95 one-hot), in float64."""
    return np.eye(c)[y] * 0.9 + 0.05


def reference_solve(X, y, c: int = N_CLASSES, lam: float = LAM):
    """Float64 centralized eq.-3 solve, plain numpy: for every class k,
    ``(Xᵀ F_k² X + λI) w_k = Xᵀ (F_k² d̄_k)`` with the bias column first,
    ``d̄ = logit(D)`` and ``F = diag(f'(d̄)) = diag(D(1 − D))``. Any number
    of classes ``c`` (the tiered FEMNIST-shape test solves 62); one dense
    ``n × m`` pass per class, so it is for test sizes."""
    X = np.asarray(X, np.float64)
    Xb = np.concatenate([np.ones((X.shape[0], 1)), X], axis=1)
    D = _soft_targets(np.asarray(y), c)
    dbar = np.log(D / (1.0 - D))
    fp = D * (1.0 - D)
    eye = np.eye(Xb.shape[1])
    W = np.empty((Xb.shape[1], c))
    for k in range(c):
        XF = Xb * fp[:, k:k + 1]
        W[:, k] = np.linalg.solve(XF.T @ XF + lam * eye,
                                  Xb.T @ (fp[:, k] ** 2 * dbar[:, k]))
    return W


def reference_accuracy(W64, X, y) -> float:
    Xb = np.concatenate([np.ones((X.shape[0], 1)), np.asarray(X, np.float64)],
                        axis=1)
    return float(np.mean(np.argmax(Xb @ W64, axis=1) == y))


def client_program(engine, parts_X, parts_d):
    """The phase's compiled client program (stats only), lowered at the
    phase's real shapes: one client's shard on the per-client loop and
    the mesh, the stacked pow2 bucket on the fused gears."""
    import jax
    import jax.numpy as jnp
    from ..core.engine import _bucket_bound
    wire = engine.wire
    f32 = jnp.float32
    m, c = parts_X[0].shape[1], parts_d[0].shape[1]
    if engine.transport == "mesh":
        n = -(-sum(int(x.shape[0]) for x in parts_X) // len(jax.devices()))
        return jax.jit(wire.local_stats).lower(
            jax.ShapeDtypeStruct((n, m), f32),
            jax.ShapeDtypeStruct((n, c), f32)).compile()
    if engine.batch_clients:
        P = len(parts_X)
        bound = _bucket_bound(max(int(x.shape[0]) for x in parts_X))
        return jax.jit(wire.fleet_stats).lower(
            jax.ShapeDtypeStruct((P, bound, m), f32),
            jax.ShapeDtypeStruct((P, bound, c), f32),
            jax.ShapeDtypeStruct((P,), jnp.int32)).compile()
    n = int(parts_X[0].shape[0])
    return jax.jit(wire.local_stats).lower(
        jax.ShapeDtypeStruct((n, m), f32),
        jax.ShapeDtypeStruct((n, c), f32)).compile()


def _device_bytes() -> Optional[Dict[str, int]]:
    import jax
    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None
    return {k: int(stats[k]) for k in ("bytes_in_use", "peak_bytes_in_use")
            if k in stats}


def run_phase(name: str, parts_X, parts_d, X_test, y_test, W64,
              acc_ref: float) -> Dict:
    """One phase: build the engine, compile its client program, run the
    round twice (cold, then steady), check ``W`` and accuracy."""
    import jax
    from ..core import predict_labels
    from ..core.engine import FederationEngine
    what, kw = PHASES[name]
    engine = FederationEngine(wire="gram", act="logistic", lam=LAM, **kw)

    t0 = time.perf_counter()
    compiled = client_program(engine, parts_X, parts_d)
    compile_s = time.perf_counter() - t0
    kernel = "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    program_bytes = None if mem is None else {
        "argument": int(mem.argument_size_in_bytes),
        "output": int(mem.output_size_in_bytes),
        "temp": int(mem.temp_size_in_bytes)}

    def one_round():
        t = time.perf_counter()
        if engine.transport == "mesh":
            rep = engine.run_mesh_arrays(np.concatenate(parts_X),
                                         np.concatenate(parts_d))
        else:
            rep = engine.run(parts_X, parts_d)
        jax.block_until_ready(rep.W)
        return rep, time.perf_counter() - t

    _, cold_s = one_round()
    rep, steady_s = one_round()
    W = np.asarray(rep.W, np.float64)
    rel_err = float(np.linalg.norm(W - W64) / np.linalg.norm(W64))
    acc = float(np.mean(np.asarray(predict_labels(rep.W, X_test)) == y_test))
    out = {"phase": name, "what": what, "rel_err_W": rel_err,
           "acc": acc, "acc_ref": acc_ref, "compile_s": compile_s,
           "cold_s": cold_s, "steady_s": steady_s,
           "dispatches": int(rep.dispatches), "tpu_custom_call": kernel,
           "program_bytes": program_bytes, "device_bytes": _device_bytes()}
    print(f"phase {name}: {json.dumps(out)}", flush=True)
    if not np.all(np.isfinite(W)) or rel_err > MAX_REL_ERR:
        raise SmokeFailure(f"phase {name}: relative W error {rel_err} > "
                           f"{MAX_REL_ERR}")
    if abs(acc - acc_ref) > MAX_ACC_GAP:
        raise SmokeFailure(f"phase {name}: accuracy {acc} vs reference "
                           f"{acc_ref}")
    if jax.default_backend() == "tpu" and name in KERNEL_PHASES \
            and not kernel:
        raise SmokeFailure(f"phase {name}: no tpu_custom_call in the "
                           "compiled client program")
    return out


def run(phases: Sequence[str], *, scale: float = 1.0,
        n_clients: int = N_CLIENTS, seed: int = 0) -> List[Dict]:
    """Generate the data, solve the reference, run ``phases`` in order."""
    from ..core import activations as acts
    from ..data import partition
    t0 = time.perf_counter()
    (X_tr, y_tr), (X_te, y_te) = load_higgs(scale, seed)
    parts = partition.iid(X_tr, y_tr, n_clients, seed=seed)
    parts_X = [p[0] for p in parts]
    parts_d = [np.asarray(acts.encode_labels(p[1], N_CLASSES))
               for p in parts]
    print(f"data: train {X_tr.shape} test {X_te.shape}, {n_clients} "
          f"clients of {min(len(p[1]) for p in parts)}-"
          f"{max(len(p[1]) for p in parts)} rows, m={X_tr.shape[1] + 1} "
          f"c={N_CLASSES} ({time.perf_counter() - t0:.1f} s)", flush=True)
    t0 = time.perf_counter()
    W64 = reference_solve(X_tr, y_tr)
    acc_ref = reference_accuracy(W64, X_te, y_te)
    print(f"reference: float64 eq.-3 solve, test accuracy {acc_ref} "
          f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return [run_phase(name, parts_X, parts_d, X_te, y_te, W64, acc_ref)
            for name in phases]


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="one federation round on the chip vs float64")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-transport round over four "
                         "devices")
    args = ap.parse_args(argv)
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's backend is {backend!r}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2
    run(["mesh"] if args.chips == 4 else ["a", "b", "c", "d"])
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0
