"""``events``: one ``FederationEngine.run_events`` call per single-event tick.

Set-up draws the federation, admits every client into a
``FederationLedger`` (one tick of joins), and applies one event of each
kind so that every program is compiled. Each step is then one event
(join, leave or revise) from the call to a re-solved ``W``, on the
populated ledger, in a closed loop.

The event mix comes from the workload's ``events``:

* kinds are dealt in blocks that hold the mix exactly (``revise: 2,
  leave: 1, join: 1`` in four), each block in an order drawn from the
  seed, so that every seed has the same mix in another order;
* a join re-admits a departed client; with none departed it is a revise;
  a leave that would take the federation under ``min_active`` is a
  revise;
* clients are drawn by a Zipf law of exponent ``zipf_s`` over ids ranked
  in an order drawn from the seed, among those the kind applies to;
* a revise replaces the client's oldest ``revise_fraction`` of rows with
  fresh rows from the seed, so that the shard keeps its shape.

The check compares the ``W`` of the window's last event, and the
ledger's statistics it was solved from, with the float64 reference over
the live clients' current rows.
"""
from __future__ import annotations

import numpy as np

from chipbench import common, datagen

# what can go wrong underneath (chipbench/faults.py)
FAULTS = ("altered_answer", "unchanged_state")


class EventStream:
    def __init__(self, spec: dict, P: int, rng: np.random.Generator):
        self.rng = rng
        self.block = [k for k, n in spec["block"].items() for _ in range(n)]
        self.queue: list = []
        rank = rng.permutation(P)
        self.weight = (rank + 1.0) ** -float(spec["zipf_s"])
        self.min_active = int(spec["min_active"])

    def _kind(self) -> str:
        if not self.queue:
            self.queue = list(self.rng.permutation(self.block))
        return str(self.queue.pop())

    def pick(self, ids) -> int:
        ids = np.asarray(sorted(ids))
        w = self.weight[ids]
        return int(self.rng.choice(ids, p=w / w.sum()))

    def next(self, active, departed):
        kind = self._kind()
        if kind == "join" and not departed:
            kind = "revise"
        if kind == "leave" and len(active) <= self.min_active:
            kind = "revise"
        return kind, self.pick(departed if kind == "join" else active)


class Driver:
    unit = "event"

    def __init__(self, config: dict, workload: dict, seed: int, tracer):
        import jax
        import jax.numpy as jnp
        from repro.core.ledger import FederationLedger
        from repro.core.scenario import Timeline, TimelineEvent
        self._jax = jax
        self._Timeline, self._Event = Timeline, TimelineEvent
        self.lam = float(config["lam"])
        spec = workload["events"]
        self.src, parts_X, parts_d = datagen.federation(config, workload,
                                                        seed)
        self.parts_X, self.parts_d = list(parts_X), list(parts_d)
        self.engine = common.engine(config, workload, tracer)
        self.solved = common.SolvedStats(self.engine)
        self.ledger = FederationLedger(self.engine.wire, lam=self.lam)
        self.engine.run_events(self.parts_X, self.parts_d, Timeline(),
                               ledger=self.ledger)
        n = int(self.parts_X[0].shape[0])
        self.cut = int(n * float(spec["revise_fraction"]))
        cut = self.cut
        self._shift = jax.jit(lambda A, B: jnp.concatenate([A[cut:], B]))
        self.stream = EventStream(spec, len(self.parts_X), self.src.rng)
        self.n_revised = 0
        self.reports = []
        self.work = {}
        first = self.stream.pick(self.ledger.clients)
        for kind in ("revise", "leave", "join"):
            self._apply(kind, first)
        self.reports.clear()

    def _revise(self, X, d, tick: int):
        Xn, Dn, _ = self.src.rows(self.n_revised, self.cut, stream="revise")
        self.n_revised += 1
        X, d = self._shift(X, Xn), self._shift(d, Dn)
        self.parts_X[self._cid], self.parts_d[self._cid] = X, d
        return X, d

    def _apply(self, kind: str, cid: int) -> None:
        self._cid = cid
        ev = self._Event(self.ledger.tick + 1, kind, cid)
        self.solved.stats = None     # the last event's, freed as before
        reps = self.engine.run_events(self.parts_X, self.parts_d,
                                      self._Timeline(events=(ev,)),
                                      ledger=self.ledger,
                                      revise_fn=self._revise)
        self._jax.block_until_ready(reps[-1].W)
        self.reports.append(reps[-1])

    def step(self) -> None:
        self._apply(*self.stream.next(self.ledger.clients,
                                      sorted(self.ledger.departed)))

    def result_W(self) -> np.ndarray:
        return np.asarray(self.reports[-1].W, np.float64)

    def live_parts(self):
        live = self.ledger.clients
        return ([self.parts_X[i] for i in live],
                [self.parts_d[i] for i in live])

    def check(self) -> dict:
        W = self.result_W()
        self.engine = None
        return common.check(W, self.solved, *self.live_parts(), self.lam)
