"""``round``: ``FederationEngine.run`` once per step, back to back.

Set-up draws the federation on the device and runs the round until every
program it needs is compiled. Each step is one round, from shards
resident on the device to a committed ``W`` (``block_until_ready``).
The check compares the ``W`` of the window's last round, and the merged
statistics it was solved from, with the float64 reference over every
client's rows.
"""
from __future__ import annotations

import numpy as np

from chipbench import common, datagen, work

WARM_ROUNDS = 2
# what can go wrong underneath (chipbench/faults.py)
FAULTS = ("half_batch", "altered_answer")


class Driver:
    unit = "round"

    def __init__(self, config: dict, workload: dict, seed: int, tracer):
        import jax
        self._jax = jax
        self.lam = float(config["lam"])
        _, self.parts_X, self.parts_d = datagen.federation(config, workload,
                                                           seed)
        self.engine = common.engine(config, workload, tracer)
        self.solved = common.SolvedStats(self.engine)
        ns = [int(X.shape[0]) for X in self.parts_X]
        m, k, c = common.shape(config)
        self.work = {"kernel": work.stats_work(ns, m, k, c),
                     "flops": work.round_flops(ns, m, k, c)}
        self.reports = []
        for _ in range(WARM_ROUNDS):
            self.step()
        self.reports.clear()

    def step(self) -> None:
        self.solved.stats = None     # the last round's, freed as before
        rep = self.engine.run(self.parts_X, self.parts_d)
        self._jax.block_until_ready(rep.W)
        self.reports.append(rep)

    def result_W(self) -> np.ndarray:
        return np.asarray(self.reports[-1].W, np.float64)

    def live_parts(self):
        return self.parts_X, self.parts_d

    def check(self) -> dict:
        W = self.result_W()
        self.engine = None
        return common.check(W, self.solved, *self.live_parts(), self.lam)
