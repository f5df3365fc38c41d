"""Federations made from ``--seed``: rows on the device, partitions by rule.

A configuration names its generator (``generator``: a file
``generators/<name>.py`` with a ``Source(config, seed)`` whose
``rows(index, n, stream)`` gives one block of ``(X, D, y)`` on the
device), and a workload its partition (``partition``: a file
``partitions/<name>.py`` with ``split(source, n_total, P, workload)``
giving the clients' ``X`` and ``D`` shards). A name without its file is
an error.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from chipbench.common import by_name

LOW, HIGH = 0.05, 0.95


def seeds(seed: int) -> Tuple[int, np.random.Generator]:
    """A 32-bit JAX seed and a numpy generator from any whole number."""
    state = np.random.SeedSequence(int(seed)).generate_state(4)
    return int(state[0]), np.random.default_rng(state[1:])


def encode(y: np.ndarray, classes: int) -> np.ndarray:
    """The program's targets for the logistic: one-hot into (LOW, HIGH)."""
    return (np.eye(classes, dtype=np.float32)[y] * np.float32(HIGH - LOW)
            + np.float32(LOW))


def federation(config: dict, wl: dict, seed: int):
    """``(source, parts_X, parts_d)`` of the cell, on the device."""
    src = by_name("generators", config["generator"]).Source(config, seed)
    n_total = int(wl.get("rows", config["rows_train"]))
    parts_X, parts_d = by_name("partitions", wl["partition"]).split(
        src, n_total, int(wl["clients"]), wl)
    return src, parts_X, parts_d
