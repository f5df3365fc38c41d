"""What the harness and the drivers share: files found by name, the
engine from the cell's files, the statistics' shapes, and the rows read
back in host blocks for the float64 reference."""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from typing import Iterator, List, Sequence, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
BLOCK_ROWS = 1 << 20


@functools.lru_cache(maxsize=None)
def by_name(kind: str, name: str):
    """``chipbench/<kind>/<name>.py``: a driver, a metric's reader, a
    generator or a partition, named in a cell's files. Loaded once per
    process, so that the programs it caches are traced once."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no {kind} named {name!r} ({path} is missing)")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def engine(config: dict, workload: dict, tracer):
    """A ``FederationEngine`` with the keyword arguments a deployment's
    user passes: the configuration's wire, activation and λ, and the
    workload's own (``engine``), which pin the gear."""
    from repro.core.engine import FederationEngine
    return FederationEngine(wire=config["wire"], act=config["activation"],
                            lam=float(config["lam"]), trace=tracer,
                            **workload.get("engine", {}))


class SolvedStats:
    """The statistics of the engine's last solve on the host side: those
    its committed ``W`` was solved from.

    The hook sits on the engine's wire object and holds one reference,
    to the last solve's statistics (a driver drops it before each step,
    so that it adds nothing to the device's peak); the solve itself runs
    unchanged. A solve traced inside a
    compiled program leaves nothing to read (``stats`` stays ``None``,
    and the check reads infinity).
    """

    def __init__(self, engine):
        import jax
        wire = engine.wire
        solve = wire.solve
        self.stats = None

        def recording(stats, lam=1e-3):
            if not isinstance(stats[0], jax.core.Tracer):
                self.stats = stats
            return solve(stats, lam)
        object.__setattr__(wire, "solve", recording)


def check(W, solved: SolvedStats, parts_X: Sequence, parts_d: Sequence,
          lam: float) -> dict:
    """The numbers that decide ``correct``: the float64 reference over
    the clients' rows, read back one block at a time."""
    from chipbench import reference
    ref = reference.stats(host_blocks(parts_X, parts_d))
    return reference.check(W, solved.stats, ref, lam)


def shape(config: dict) -> Tuple[int, int, int]:
    """``(m, k, c)``: columns with the bias, Gram rows of F, classes."""
    m, c = int(config["features"]) + 1, int(config["classes"])
    return m, (1 if config["activation"] == "identity" else c), c


def host_blocks(parts_X: Sequence, parts_d: Sequence,
                rows: int = BLOCK_ROWS) -> Iterator[Tuple[np.ndarray,
                                                          np.ndarray]]:
    """The clients' rows read back in blocks of about ``rows`` rows."""
    import jax
    group: List[int] = []
    n = 0
    for i, X in enumerate(parts_X):
        group.append(i)
        n += int(X.shape[0])
        if n >= rows or i == len(parts_X) - 1:
            xs = jax.device_get([parts_X[j] for j in group])
            ds = jax.device_get([parts_d[j] for j in group])
            yield np.concatenate(xs), np.concatenate(ds)
            group, n = [], 0
