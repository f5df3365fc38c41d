"""FederationEngine: one federated round = wire × transport × scenario.

The paper's single-round claim used to be reproduced three separate times
(in-process ``core/federated.py``, mesh-collective ``core/sharded.py``,
streaming-edge ``core/streaming.py``), each with per-wire variants. The
engine composes the axes instead (DESIGN.md §7):

* **wire**      — the sufficient-statistics representation
  (``core/wire.py``: ``"svd"`` | ``"gram"`` | any :class:`~.wire.Wire`),
* **transport** — how statistics travel to the coordinator:

  - ``"local"``  : P in-process clients, tree or sequential merge
    (subsumes ``fed_fit`` / ``fed_fit_timed``),
  - ``"mesh"``   : clients on a mesh axis, the merge as collectives via
    ``Wire.mesh_reduce`` inside ``shard_map`` (subsumes
    ``fed_fit_sharded*``),
  - ``"stream"`` : chunk-folding edge clients that upload once (the
    ``core/streaming.py`` clients as a transport),

* **scenario**  — who participates and when (``core/scenario.py``:
  partition strategy, dropout, late-join admission, stragglers).

The local transport's client phase has three gears (DESIGN.md §8):

* the **per-client loop** (default) — one dispatch per participant,
* ``batch_clients=True`` — participants are grouped into power-of-two
  sample-count *buckets*, each bucket zero-padded and stacked into one
  ``Wire.local_stats_batch`` dispatch (compile count O(log n-spread)
  instead of O(distinct shapes)); per-client statistics still
  materialize, so the merge/solve is byte-for-byte the loop path's — on
  the gram wire the returned ``W`` bit-matches the loop (tested),
* ``fused=True`` — per-client statistics never materialize: each bucket
  runs a single jitted stats → leading-axis-merge program with donated
  input buffers, and a round with one bucket and no late joiners is ONE
  compiled program ending in the solve. Fastest, but the leading-axis
  merge reorders float additions, so parity with the loop is to rounding
  (not bitwise).

Beyond the single round, :meth:`FederationEngine.run_events` drives a
:class:`~.scenario.Timeline` of join/leave/revise events against a
persisted :class:`~.ledger.FederationLedger` — one report per tick,
with only the *changed* clients recomputing local statistics
(DESIGN.md §9).

A fourth axis, **privacy** (``privacy/policy.py``, DESIGN.md §10),
composes with the in-process transports: ``privacy="secagg"`` masks
every upload with pairwise pads over the exact dyadic-integer encoding
(the coordinator phase then runs on the :class:`~..privacy.MaskedWire`
and only ever decodes aggregates — ``W`` bit-matches the unmasked
exact-aggregation solve), ``privacy="dp"`` clips client rows and
perturbs the aggregate once per release, ``"secagg+dp"`` distributes
the noise across clients under the masks. The client-side steps (clip,
noise share, mask) are timed into ``client_times`` so privacy overhead
shows up in the §4.1 metrics. Privacy composes with EVERY transport
and gear: the fused path runs each bucket's masked round as one jitted
stats → noise-share → encode → mask → ring-merge program
(``privacy/limbs.py`` — a uniform masked round stays one client-phase
dispatch), and the mesh transport masks on-device before its
collective, psumming int64 limb arrays whose interior pads cancel
exactly (``MaskedWire.mesh_reduce``). The only closed cells of the
wire × transport × privacy matrix are svd × secagg (the Iwen–Ong
merge is not additive — ``PrivacyCellUnsupported``, DESIGN.md §10).

Every run returns a :class:`RoundReport` with the paper's §4.1 metrics —
train time (slowest client + coordinator), Σ CPU, Wh from process-CPU
metering (``energy/meter.py``) — plus the per-wire upload bytes and the
roles that were played. Model correctness under scenarios is exact: the
returned ``W`` is the direct solve over the participating clients' union
(bit-matching for the local transport with sequential merge — tested).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from . import activations as acts
from .contribution import (SelectSpec, accuracy_frontier,
                           contribution_summary, greedy_select,
                           loo_scores)
from .faults import (CoordinatorKilled, FaultPlan, RoundFaults,
                     RoundJournal, UploadRejected, empty_faults_report,
                     inject_corrupt, validate_upload)
from .ledger import FederationLedger
from .scenario import ClientRoles, Scenario, Timeline
from .topology import ExactFold, Topology, failover, simulate_round
from .util import add_bias, as_2d, enable_x64
from .wire import Wire, _WireBase, get_wire
from ..energy import EnergyMeter, watt_hours
from ..energy.meter import J_PER_BYTE
from ..obs.trace import NULL_TRACER

TRANSPORTS = ("local", "mesh", "stream")


@dataclasses.dataclass
class RoundReport:
    """Everything one federated round produced (paper §4.1 metrics).

    * ``train_time``  = slowest client clock (measured compute + that
      client's simulated straggler delay) + coordinator — real FL wall
      time,
    * ``cpu_time``    = Σ measured client compute + coordinator — the
      paper's energy proxy; simulated delays are idle waiting and never
      count here,
    * ``cpu_seconds`` = measured process CPU for the whole round
      (``EnergyMeter``), from which ``wh`` derives,
    * ``wire_bytes``  = Σ upload bytes over participants for this wire
      (on the mesh transport the devices are the uploading clients, so
      this counts one upload per device),
    * ``dispatches``  = client-phase compiled-call dispatches: one per
      participant on the per-client loop, one per shape bucket on the
      batched/fused paths, one collective on the mesh — the §4.1
      dispatch-overhead axis the fleet path collapses,
    * ``W_first``     = the model after the on-time group only (present
      iff the scenario had late joiners; the final ``W`` admits them).

    On the mesh transport per-client compute happens inside the
    collective phase (counted in ``coordinator_time``); ``client_times``
    then carry only the scenario's simulated straggler delays.
    """
    W: jnp.ndarray
    client_times: List[float]
    coordinator_time: float
    wire_bytes: int
    roles: ClientRoles
    n_samples: int
    cpu_seconds: float = 0.0
    rounds: int = 1
    dispatches: int = 0
    W_first: Optional[jnp.ndarray] = None
    # event-driven (run_events) rounds: the ledger tick this report
    # closes and the clients whose statistics were recomputed for it
    tick: int = 0
    changed: Sequence[int] = ()
    # privacy bookkeeping (PrivacyRun.summary() — mode, σ, (ε, δ)
    # spent, masked upload bytes); None when the policy is "none"
    privacy: Optional[dict] = None
    # coordinator residency (DESIGN.md §11): max wire-stats bytes the
    # coordinator process held resident at any instant of the fold —
    # O(P) on the flat paths, O(tiers·agg_bytes) under a Topology; on
    # ledger ticks it is the registry (exact unlearning's price)
    peak_coordinator_bytes: int = 0
    # hierarchical rounds: tier shape, fold codec, and the simulated
    # latency model's tiered-vs-flat wall/joule comparison
    hierarchy: Optional[dict] = None
    # fault subsystem bookkeeping (core/faults.py): quarantines with
    # per-client reasons, retry pricing, tier failovers, journal
    # recoveries, and the quorum commit — present with all-clear
    # values on fault-free runs so downstream JSON consumers get a
    # stable schema
    faults: dict = dataclasses.field(default_factory=empty_faults_report)
    # contribution-scored selection rounds (core/contribution.py,
    # DESIGN.md §13): exact per-client LOO scores, the utility
    # ranking, the selected cohort with its byte/joule spend, and —
    # in frontier mode — the accuracy-per-joule prefix curve; None
    # when the scenario has no select axis
    contribution: Optional[dict] = None

    @property
    def client_clocks(self) -> List[float]:
        """Per-participant wall clocks: measured compute + simulated delay."""
        delays = self.roles.delays
        return [t + delays[i] for t, i in
                zip(self.client_times, self.roles.participants)]

    @property
    def train_time(self) -> float:
        clocks = self.client_clocks
        return (max(clocks) if clocks else 0.0) + self.coordinator_time

    @property
    def cpu_time(self) -> float:
        return sum(self.client_times) + self.coordinator_time

    @property
    def wh(self) -> float:
        return watt_hours(self.cpu_seconds)

    def to_dict(self, *, include_model: bool = False) -> dict:
        """JSON-safe rendering: every value a pure-Python type.

        The nested ``faults``/``hierarchy``/``contribution``/
        ``privacy`` dicts are built by subsystems that handle numpy
        numbers, so :func:`_py` re-coerces recursively here — the one
        place the whole report is guaranteed serializable
        (round-tripped in tests/test_obs.py). ``W``/``W_first`` stay
        out unless ``include_model``: a report is telemetry, the
        model is a payload.
        """
        out = {
            "client_times": [float(t) for t in self.client_times],
            "coordinator_time": float(self.coordinator_time),
            "wire_bytes": int(self.wire_bytes),
            "roles": {
                "on_time": [int(i) for i in self.roles.on_time],
                "late": [int(i) for i in self.roles.late],
                "dropped": [int(i) for i in self.roles.dropped],
                "delays": [float(t) for t in self.roles.delays],
            },
            "n_samples": int(self.n_samples),
            "cpu_seconds": float(self.cpu_seconds),
            "rounds": int(self.rounds),
            "dispatches": int(self.dispatches),
            "tick": int(self.tick),
            "changed": [int(i) for i in self.changed],
            "privacy": _py(self.privacy),
            "peak_coordinator_bytes": int(self.peak_coordinator_bytes),
            "hierarchy": _py(self.hierarchy),
            "faults": _py(self.faults),
            "contribution": _py(self.contribution),
            "train_time": float(self.train_time),
            "cpu_time": float(self.cpu_time),
            "wh": float(self.wh),
        }
        if include_model:
            out["W"] = np.asarray(self.W).tolist()
            out["W_first"] = None if self.W_first is None else \
                np.asarray(self.W_first).tolist()
        return out


def _py(v):
    """Recursively coerce numpy/JAX scalars, arrays, tuples, and dict
    keys to pure-Python (json.dumps-clean) values. Dict keys become
    strings — JSON objects only have string keys, so int-cid maps
    (e.g. ``faults["quarantined"]``) must stringify for the output to
    survive a dumps/loads round trip unchanged."""
    if isinstance(v, dict):
        return {str(_py(k)): _py(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_py(x) for x in v]
    if isinstance(v, (bool, int, float, str, type(None))):
        return v
    if getattr(v, "ndim", None) == 0 and hasattr(v, "item"):
        return _py(v.item())
    if hasattr(v, "tolist"):
        return v.tolist()
    return v


class FederationEngine:
    """Single-round federated fitting over composable axes.

    Parameters mirror the historical entry points: ``act``/``lam`` as in
    ``fed_fit``, ``tree`` selects the local merge topology, ``backend``
    is the gram wire's client-pass selector (``None`` = Pallas on TPU,
    XLA elsewhere), ``chunks`` is the per-client chunk count for the
    stream transport, ``mesh``/``axis`` configure the mesh transport
    (default: a 1-D mesh over all local devices). ``warmup=True`` runs an
    untimed compile pass before the timed client loop so ``client_times``
    measure steady-state (see :func:`~.federated.fed_fit_timed`).

    ``batch_clients=True`` turns the local transport's client phase into
    the fleet-batched bucket dispatch (one ``Wire.local_stats_batch``
    call per power-of-two sample-count bucket, bit-identical fold —
    module docstring); ``fused=True`` (implies ``batch_clients``)
    additionally fuses stats → merge (→ solve, when a single bucket
    covers the round) into one jitted program per bucket with donated
    input buffers.
    """

    def __init__(self, wire: Any = "svd", transport: str = "local",
                 scenario: Optional[Scenario] = None, *,
                 act: str = "logistic", lam: float = 1e-3,
                 backend: Any = "xla", tree: bool = True, chunks: int = 4,
                 warmup: bool = False, mesh=None, axis: str = "data",
                 dtype: Any = jnp.float32, batch_clients: bool = False,
                 fused: bool = False, privacy: Any = None,
                 topology: Any = None, faults: Any = None,
                 quorum: float = 1.0, journal: Optional[str] = None,
                 select_eval: Optional[tuple] = None,
                 trace: Any = None):
        if transport not in TRANSPORTS:
            raise ValueError(f"unknown transport {transport!r} "
                             f"(expected one of {TRANSPORTS})")
        # flight recorder (obs/, DESIGN.md §14): hot paths trace
        # unconditionally through this handle — the NULL_TRACER's
        # span/event are constant no-ops, so tracing-off stays off
        self.trace = trace if trace is not None else NULL_TRACER
        self.wire: Wire = get_wire(wire, act=act, backend=backend,
                                   dtype=dtype)
        self.transport = transport
        self.scenario = scenario or Scenario()
        self.lam = lam
        self.tree = tree
        self.chunks = max(1, chunks)
        self.warmup = warmup
        self.mesh = mesh
        self.axis = axis
        self.fused = bool(fused) and hasattr(self.wire, "fleet_stats") \
            and hasattr(self.wire, "merge_axis")
        self.batch_clients = bool(batch_clients) or self.fused
        # hierarchical aggregation (core/topology.py, DESIGN.md §11):
        # a parsed Topology routes run() through the tier-tree fold
        self.topology = Topology.parse(topology)
        # fault subsystem (core/faults.py, DESIGN.md §12): injection
        # plan, quorum-commit threshold, and the round journal (WAL)
        self.fault_plan = FaultPlan.parse(faults)
        if not 0.0 < float(quorum) <= 1.0:
            raise ValueError(
                f"quorum={quorum} must be in (0, 1]: it is the "
                "sample-weighted fraction of uploads that commits "
                "the round")
        self.quorum = float(quorum)
        self.journal_path = journal
        self._fb: Optional[RoundFaults] = None
        plan_active = self.fault_plan is not None and \
            self.fault_plan.active
        if self.fault_plan is not None and self.fault_plan.aggfail \
                and self.topology is None:
            raise ValueError(
                "the fault plan names aggfail@tier..., but only "
                "hierarchical rounds (topology=...) have tier "
                "aggregators to fail")
        if self.journal_path and self.topology is None:
            raise ValueError(
                "journal=... needs a hierarchical round "
                "(topology=...): the write-ahead log commits "
                "per-tier aggregates")
        if self.journal_path and self.transport == "mesh":
            raise ValueError(
                "journal: the mesh collective materializes every "
                "edge aggregate in one dispatch — there is no "
                "per-tier commit point to log; use the local or "
                "stream transport")
        if self.transport == "mesh" and self.topology is None and \
                (plan_active or self.quorum < 1.0):
            raise ValueError(
                "fault injection and quorum commit need per-client "
                "upload boundaries, but the flat mesh collective is "
                "all-or-nothing; add topology=... so the mesh folds "
                "per-edge, or use an in-process transport")
        # budgeted client selection (core/contribution.py): the
        # scenario's select axis, scored coordinator-side against the
        # caller-held eval split passed as select_eval=(X_eval, y_eval)
        self.select = SelectSpec.parse(self.scenario.select)
        self.select_eval = select_eval
        if self.select is not None and self.transport == "mesh" and \
                self.topology is None:
            raise ValueError(
                "client selection needs per-client upload boundaries, "
                "but the flat mesh collective is all-or-nothing; add "
                "topology=... so clients fold per-edge, or use an "
                "in-process transport")
        self._fused_cache = {}
        # imported here, not at module top: privacy/* imports the core
        # package, so a module-level import would cycle through a
        # half-initialized repro.privacy during `import repro.privacy`
        from ..privacy.policy import PrivacyPolicy
        self.privacy = PrivacyPolicy.parse(privacy)
        # per-client-pool-size PrivacyRun cache: successive runs over
        # the same pool reuse one mask session, so a ledger built by an
        # earlier run_events call stays consistent with later pads
        self._priv_runs = {}
        self._priv = None

    # ------------------------------------------------------- privacy
    def _begin_privacy(self, P: int):
        """Activate the policy for a run over a ``P``-client pool (on
        the mesh transport the pool is the device axis — the devices
        are the uploading clients). A wire × privacy combination the
        matrix rules out raises the typed
        :class:`~..privacy.policy.PrivacyCellUnsupported` here, with
        the cell named after this engine's transport."""
        if not self.privacy.active:
            self._priv = None
            return None
        if P not in self._priv_runs:
            self._priv_runs[P] = self.privacy.begin(
                P, self.wire, transport=self.transport)
        self._priv = self._priv_runs[P]
        return self._priv

    def _cw(self):
        """Coordinator-side wire: the masked adapter under secagg."""
        return self._priv.coord_wire if self._priv is not None \
            else self.wire

    def _encode_stats(self, stats, time_by):
        """Client-side privacy step (DP noise share, pairwise mask),
        timed into ``client_times`` so privacy overhead is visible in
        the §4.1 metrics like any other client compute."""
        if self._priv is not None:
            if stats:
                # session-wide pad derivation happens once, untimed —
                # it is not any single client's work
                self._priv.prepare(next(iter(stats.values())))
            for i in list(stats):
                t0 = time.perf_counter()
                with self.trace.span("mask.encode", track="client",
                                     cid=int(i)):
                    stats[i] = self._priv.client_encode(i, stats[i])
                time_by[i] = time_by.get(i, 0.0) + \
                    (time.perf_counter() - t0)
        return stats

    # ------------------------------------------------------------ faults
    def _apply_faults(self, roles: ClientRoles, parts_X,
                      parts_d) -> ClientRoles:
        """Fault injection + upload admission + quorum commit.

        Runs right after the scenario deals roles and BEFORE anything
        folds (or the privacy cohort is announced), so every
        downstream path — loop, batched, fused, stream, hierarchical,
        plain or masked — sees a cohort that already excludes
        quarantined clients; removal-before-fold is what makes the
        committed solve bit-identical to a round that never saw them.

        Retry/timeout/backoff pricing lands on ``roles.delays`` (wall)
        and the fault ledger's byte/joule counters; the quorum commit
        moves the slowest sample-weighted tail of the on-time group
        into ``late``, so the existing ``W_first`` machinery IS the
        quorum-committed model on every path (late arrivals then
        merge in revise-style for the final ``W``).
        """
        plan, q = self.fault_plan, self.quorum
        if (plan is None or not plan.active) and q >= 1.0 \
                and not self.journal_path:
            return roles
        fb = RoundFaults(plan, quorum=q)
        self._fb = fb
        delays = list(roles.delays)
        on_time, late = list(roles.on_time), list(roles.late)
        dropped = set(roles.dropped)
        m_in = parts_X[0].shape[1] if len(parts_X) else 0
        c = parts_d[0].shape[1] if len(parts_d) else 1
        if plan is not None and plan.active:
            seen: set = set()
            for cid in list(roles.participants):
                n_att, ok = plan.attempts(cid)
                if n_att > 1:
                    fb.retried[cid] = n_att - 1
                    wait = plan.backoff_delay(cid, n_att)
                    fb.retry_s += wait
                    delays[cid] += wait
                    self.trace.event("fault.retry", cid=int(cid),
                                     attempts=int(n_att),
                                     wait_s=float(wait))
                    if cid not in plan.crash:
                        # a crashed device transmits nothing; every
                        # other retry resends the full upload
                        fb.retry_bytes += (n_att - 1) * \
                            self._cw().stats_bytes(
                                int(parts_X[cid].shape[0]), m_in, c)
                if not ok:
                    reason = "crash" if cid in plan.crash \
                        else ("timeout" if cid in plan.timeout
                              else "flaky")
                    fb.quarantine(cid, reason)
                    self.trace.event("fault.quarantine", cid=int(cid),
                                     reason=reason)
                    continue
                if cid in plan.corrupt:
                    st = inject_corrupt(
                        self.wire.local_stats(parts_X[cid],
                                              parts_d[cid]),
                        seed=plan.seed)
                    try:
                        validate_upload(cid, st, seen=seen)
                    except UploadRejected as e:
                        fb.quarantine(cid, e.reason)
                        self.trace.event("fault.quarantine",
                                         cid=int(cid), reason=e.reason)
                    continue
                seen.add(cid)
                if cid in plan.replay:
                    # the client's upload arrives a second time: the
                    # duplicate is rejected, the first copy folds
                    try:
                        validate_upload(
                            cid, self.wire.local_stats(parts_X[cid],
                                                       parts_d[cid]),
                            seen=seen)
                    except UploadRejected:
                        fb.replays_rejected.append(cid)
            # flat-WAN retry pricing; hierarchical rounds re-price the
            # retries per-link through simulate_round below
            fb.retry_j = fb.retry_bytes * J_PER_BYTE
            if fb.quarantined:
                bad = set(fb.quarantined)
                on_time = [i for i in on_time if i not in bad]
                late = [i for i in late if i not in bad]
                dropped |= bad
                if not on_time:
                    raise ValueError(
                        "the fault plan quarantined every on-time "
                        "client; a round needs at least one admitted "
                        "upload to solve")
        fb.n_committed = len(on_time)
        fb.committed_ids = list(on_time)
        if q < 1.0 and len(on_time) > 1:
            weights = {i: max(int(parts_X[i].shape[0]), 0)
                       for i in on_time}
            total = sum(weights.values())
            # commit the earliest-arriving prefix (ties by client id)
            # whose sample share reaches the quorum; the rest defer
            order = sorted(on_time, key=lambda i: (delays[i], i))
            committed, acc = [], 0
            for i in order:
                committed.append(i)
                acc += weights[i]
                if total and acc / total >= q:
                    break
            deferred = [i for i in order if i not in set(committed)]
            if deferred:
                on_time = sorted(committed)
                late = sorted(deferred) + late
            fb.committed_frac = (acc / total) if total else 1.0
            fb.n_committed = len(on_time)
            fb.n_deferred = len(deferred)
            fb.committed_ids = list(on_time)
            fb.deferred_ids = list(deferred)
            self.trace.event("quorum.commit", target=float(q),
                             frac=float(fb.committed_frac),
                             n_committed=len(on_time),
                             n_deferred=len(deferred))
        return ClientRoles(on_time=tuple(sorted(on_time)),
                           late=tuple(late),
                           dropped=tuple(sorted(dropped)),
                           delays=tuple(delays))

    # --------------------------------------------------------- selection
    def _apply_selection(self, roles: ClientRoles, parts_X, parts_d):
        """Contribution-scored client selection (DESIGN.md §13).

        Runs right after fault admission: every admitted participant
        computes and uploads its statistics ONCE (the scoring pass IS
        the round's client phase — ``_phase_stats``, so the batched
        bucket gears and the privacy encode apply as usual), the
        coordinator folds them into a :class:`FederationLedger` and
        scores each client by the exact leave-one-out downdate, then
        the greedy selector keeps the cohort the ``select`` spec
        admits. Unselected clients move to ``dropped`` — every
        downstream fold then commits a model over exactly the selected
        clients (which is what makes the committed ``W`` bit-match a
        from-scratch solve over that cohort).

        Under secagg the ledger runs on the masked wire: the LOO
        downdate is a ring subtract and the base wire only ever solves
        decoded aggregates of ≥ 2 clients (``min_selected``/
        ``min_prefix`` = 2 — a decoded singleton aggregate would be
        that client's plaintext; spy-tested). ``frontier`` additionally
        solves every ≥-min prefix of the utility ranking.

        Returns ``(filtered_roles, phase)`` where ``phase`` is ``None``
        when no select axis is active, else a dict carrying the scoring
        pass's stats/times/dispatches for reuse by
        :meth:`_commit_selected` plus the ``RoundReport.contribution``
        payload.
        """
        if self.select is None:
            return roles, None
        if self.select_eval is None:
            raise ValueError(
                f"scenario select={self.scenario.select!r} needs "
                "coordinator-side eval data to score against: pass "
                "select_eval=(X_eval, y_eval) to FederationEngine "
                "(fedtrain carves it from the train split)")
        X_eval, y_eval = self.select_eval
        priv = self._priv
        if priv is not None:
            # scoring uploads come from EVERY admitted participant —
            # the cohort the noise shares must scale to
            priv.cohort = len(roles.participants)
        stats, time_by, dispatches = self._phase_stats(
            parts_X, parts_d, roles.participants)
        t0 = time.perf_counter()
        with self.trace.span("score.pass",
                             n_clients=len(roles.participants)):
            masked = priv is not None and priv.masked
            ledger = FederationLedger(self._cw(), lam=self.lam,
                                      act=self.wire.act)
            for i in roles.participants:
                ledger.join(i, stats[i])
            report = loo_scores(ledger, X_eval, y_eval, lam=self.lam,
                                tracer=self.trace)
            min_sel = 2 if masked else 1
            if masked and len(roles.participants) < 2:
                raise ValueError(
                    "selection under secagg needs >= 2 participants: a "
                    "decoded single-client aggregate would be that "
                    "client's plaintext")
            sel = greedy_select(report, self.select,
                                min_selected=min_sel)
            if self.select.kind == "frontier":
                sel = dataclasses.replace(
                    sel, frontier=accuracy_frontier(
                        ledger, report, X_eval, y_eval, lam=self.lam,
                        min_prefix=min_sel))
            keep = set(sel.selected)
            # a round needs an on-time upload for its first solve: if
            # the budget admitted only late joiners, promote the
            # best-ranked on-time client into the cohort
            if roles.on_time and not keep & set(roles.on_time):
                best = next(c for c in sel.order
                            if c in set(roles.on_time))
                keep.add(best)
                sel = dataclasses.replace(
                    sel, selected=tuple(sorted(keep)),
                    spent_bytes=sel.spent_bytes
                    + report.by_cid()[best].upload_bytes,
                    spent_j=sel.spent_j + report.by_cid()[best].d_joules)
        score_s = time.perf_counter() - t0
        roles_sel = ClientRoles(
            on_time=tuple(i for i in roles.on_time if i in keep),
            late=tuple(i for i in roles.late if i in keep),
            dropped=tuple(sorted(set(roles.dropped)
                                 | (set(roles.participants) - keep))),
            delays=roles.delays)
        phase = {
            "stats": stats, "time_by": time_by,
            "dispatches": dispatches,
            "uploaders": tuple(roles.participants),
            "score_s": score_s,
            "contribution": contribution_summary(report, sel,
                                                 score_s=score_s),
        }
        return roles_sel, phase

    def _commit_selected(self, parts_X, parts_d, roles,
                         phase) -> RoundReport:
        """Commit the selected cohort, reusing the scoring uploads.

        The scoring pass already materialized every participant's
        (possibly masked) statistics, so the committed round folds the
        SAME uploads over the selected roles — no second client phase.
        ``wire_bytes`` counts every scoring upload (all admitted
        participants transmitted — selection saves future rounds'
        bytes, and the frontier prices exactly that trade); the
        unselected clients' measured compute is reported in
        ``contribution["scoring_client_s"]`` since ``client_times``
        must align with the committed participants. The fused gear
        degrades to this stats-materializing path when selection is
        active: per-client statistics must exist to be scored.
        """
        stats, time_by = phase["stats"], phase["time_by"]
        wire_bytes = sum(self._cw().wire_bytes(stats[i])
                         for i in phase["uploaders"])
        W, W_first, coordinator_time = self._coordinator(stats, roles)
        contribution = dict(phase["contribution"])
        contribution["scoring_client_s"] = float(
            sum(time_by[i] for i in phase["uploaders"]
                if i not in set(roles.participants)))
        return RoundReport(
            W=W, client_times=[time_by[i] for i in roles.participants],
            coordinator_time=coordinator_time + phase["score_s"],
            wire_bytes=wire_bytes, roles=roles,
            n_samples=sum(int(parts_X[i].shape[0])
                          for i in roles.participants),
            W_first=W_first, dispatches=phase["dispatches"],
            contribution=contribution,
            # every scoring upload materialized before the fold
            peak_coordinator_bytes=wire_bytes)

    # ------------------------------------------------------------ entry
    def run(self, parts_X: Sequence, parts_d: Sequence) -> RoundReport:
        """One round over pre-partitioned client data."""
        with self.trace.span("round", transport=self.transport,
                             n_clients=len(parts_X),
                             fused=self.fused) as rsp:
            with self.trace.span("round.prep"):
                if len(parts_X) != len(parts_d):
                    raise ValueError(
                        f"parts_X has {len(parts_X)} client shards but "
                        f"parts_d has {len(parts_d)}: every client needs "
                        "one feature shard and one target shard")
                parts_d = [as_2d(d) for d in parts_d]
                for i, (X, d) in enumerate(zip(parts_X, parts_d)):
                    nx, nd = int(np.shape(X)[0]), int(d.shape[0])
                    if nx != nd:
                        raise ValueError(
                            f"client {i}: X has {nx} rows but d has {nd}"
                            " — features and targets must pair rowwise")
            self._fb = None
            if self.topology is not None:
                # hierarchical round: the uploading units are the
                # client shards on EVERY transport here — under a
                # topology the mesh axis carries sibling edge
                # aggregators, not clients
                self._begin_privacy(len(parts_X))
                with EnergyMeter() as em:
                    report = self._run_hierarchical(parts_X, parts_d)
            else:
                if self.transport != "mesh":
                    # the mesh path's uploading units are the devices
                    # on the axis, not the data partitions —
                    # run_mesh_arrays begins its privacy run at the
                    # axis size
                    self._begin_privacy(len(parts_X))
                with EnergyMeter() as em:
                    if self.transport == "mesh":
                        report = self._run_mesh(parts_X, parts_d)
                    else:
                        report = self._run_inprocess(parts_X, parts_d)
            report.cpu_seconds = em.cpu_seconds
            if self._priv is not None:
                report.privacy = self._priv.summary()
            if self._fb is not None:
                report.faults = self._fb.report()
            rsp.set(wire_bytes=int(report.wire_bytes),
                    dispatches=int(report.dispatches))
        return report

    def fit(self, parts_X: Sequence, parts_d: Sequence) -> jnp.ndarray:
        return self.run(parts_X, parts_d).W

    def run_dataset(self, X, y, n_clients: int,
                    n_classes: int = 2) -> RoundReport:
        """Partition a labelled dataset per the scenario, then run."""
        parts = self.scenario.make_parts(X, y, n_clients)
        return self.run([p[0] for p in parts],
                        [acts.encode_labels(p[1], n_classes)
                         for p in parts])

    # ------------------------------------------------- event-driven rounds
    def run_events(self, parts_X: Sequence, parts_d: Sequence,
                   timeline, *, ledger: Optional[FederationLedger] = None,
                   delta: bool = True, revise_fn=None
                   ) -> List[RoundReport]:
        """Multi-round federation under a join/leave/revise event stream.

        Each tick of ``timeline`` (a :class:`~.scenario.Timeline` or its
        spec string) becomes one round: events apply to ``ledger`` as
        signed merges, then the coordinator solves — one
        :class:`RoundReport` per tick, ``report.tick``/``report.changed``
        carrying the event bookkeeping. Only *changed* clients (joins
        and revisions) recompute local statistics, fleet-batched through
        the bucket path when ``batch_clients``; with ``delta=False``
        every tick instead recomputes and re-folds ALL active clients
        (the full re-aggregation baseline ``benchmarks/ledger_bench.py``
        prices against — same coordinator algebra, so ``W`` bit-matches
        the delta path on the gram wire).

        The engine's scenario composes: dropped clients never auto-join,
        late-joiners auto-join at tick 1 instead of 0 (explicitly
        scheduled clients follow the timeline alone). ``revise`` events
        re-publish a client's statistics over revised data —
        ``revise_fn(X, d, tick)`` (default: drop the oldest quarter,
        a deletion-request drill) updates the client's shard in place
        for all later rounds. Pass a restored ``ledger`` to continue a
        checkpointed federation: ticks ≤ ``ledger.tick`` are skipped —
        the registry already carries those events' statistics (the
        skipped ticks' ``revise_fn`` *data* mutations are not replayed,
        so a continued run that revises the same client again drills
        against the original shard).
        """
        if self.transport == "mesh":
            raise ValueError("run_events needs an in-process transport "
                             "(local|stream); mesh rounds are one-shot")
        if (self.fault_plan is not None and self.fault_plan.active) \
                or self.quorum < 1.0 or self.journal_path:
            raise ValueError(
                "fault injection / quorum / journal apply to one-shot "
                "rounds (run): the event-driven ledger path models "
                "churn as explicit timeline events instead")
        if self.select is not None:
            raise ValueError(
                "scenario select=... applies to one-shot rounds (run): "
                "the event-driven ledger path models membership as "
                "explicit timeline events — score its registry "
                "directly with core.contribution.loo_scores instead")
        with self.trace.span("round.prep", n_clients=len(parts_X)):
            timeline = Timeline.parse(timeline) \
                if isinstance(timeline, str) else timeline
            P = len(parts_X)
            if len(parts_d) != P:
                raise ValueError("parts_X and parts_d length mismatch")
            priv = self._begin_privacy(P)
            if priv is not None:
                # ledger membership changes after upload, so distributed
                # noise shares fall back to the session universe (the
                # cached run may carry a one-shot round's cohort) — see
                # PrivacyRun.client_encode; shards are clipped per tick
                # inside the metered client phase (_phase_stats)
                priv.cohort = None
            data = {i: (parts_X[i], as_2d(parts_d[i])) for i in range(P)}
            if ledger is None:
                ledger = FederationLedger(self._cw(), lam=self.lam)
            elif priv is not None and priv.masked and \
                    getattr(ledger.wire, "session", None) is not priv.session:
                # a masked federation's ledger must fold THIS run's ring
                # elements — a float ledger (or one keyed to another
                # session's pads) would silently de-anonymize or corrupt
                raise ValueError(
                    "privacy=secagg needs a ledger on this run's masked "
                    "wire; pass ledger=None (the engine creates it) or "
                    "reuse the ledger from a previous run_events call of "
                    "this engine over the same client pool")
            elif ledger.clients and max(ledger.clients) >= P:
                # a restored federation must fit the current client pool —
                # otherwise active clients would have no data to recompute
                raise ValueError(
                    f"ledger has active clients up to id "
                    f"{max(ledger.clients)} but only {P} shards were given; "
                    "repartition with at least as many clients as the "
                    "checkpointed federation")
            if revise_fn is None:
                revise_fn = _default_revise
            # `seen` (active ∪ departed) guards auto-admission: a continued
            # run admits genuinely new clients at its first tick but never
            # re-admits ones whose departure was an explicit event
            sc_roles = self.scenario.roles(P)
            schedule = timeline.schedule(P, roles=sc_roles,
                                         joined=ledger.seen,
                                         start=ledger.tick + 1)
            ledger.tracer = self.trace
        reports = []
        for t, events in schedule:
            if t <= ledger.tick:
                continue               # restored ledger: already applied
            with self.trace.span("round", tick=int(t),
                                 transport=self.transport,
                                 n_events=len(events)), \
                    EnergyMeter() as em:
                rep = self._run_tick(data, t, events, ledger, delta,
                                     revise_fn, sc_roles.delays)
            rep.cpu_seconds = em.cpu_seconds
            if priv is not None:
                rep.privacy = priv.summary()
            ledger.tick = t
            reports.append(rep)
        return reports

    def _run_tick(self, data, t, events, ledger, delta, revise_fn,
                  delays) -> RoundReport:
        for ev in events:              # data revisions first: the round
            if ev.kind == "revise":    # republishes over revised shards
                X, d = data[ev.client]
                data[ev.client] = revise_fn(X, d, t)
        changed = sorted({ev.client for ev in events
                          if ev.kind in ("join", "revise")})
        if not delta:
            # full re-aggregation baseline: every active client (the
            # post-event membership) recomputes and re-uploads
            active_after = set(ledger.clients)
            for ev in events:
                if ev.kind == "join":
                    active_after.add(ev.client)
                elif ev.kind == "leave":
                    active_after.discard(ev.client)
            recompute = sorted(active_after | set(changed))
        else:
            recompute = changed
        pX = {i: data[i][0] for i in recompute}
        pD = {i: data[i][1] for i in recompute}
        stats, time_by, dispatches = self._phase_stats(pX, pD, recompute)
        t0 = time.perf_counter()
        with self.trace.span("ledger.apply", tick=int(t),
                             n_events=len(events),
                             n_changed=len(changed)):
            if delta:
                for ev in events:
                    if ev.kind == "join":
                        ledger.join(ev.client, stats[ev.client])
                    elif ev.kind == "revise":
                        ledger.revise(ev.client, stats[ev.client])
                    elif ev.kind == "leave":
                        ledger.leave(ev.client)
            else:
                # same signed-merge algebra, but every statistic
                # re-enters (the membership bookkeeping still goes
                # through the persistent ledger so checkpoints stay
                # valid)
                for cid in recompute:
                    if cid in ledger.registry:
                        ledger.revise(cid, stats[cid])
                    else:
                        ledger.join(cid, stats[cid])
                for ev in events:
                    if ev.kind == "leave":
                        ledger.leave(ev.client)
        # the engine's λ drives the solve (a restored ledger may carry
        # an older default; its lam only backs standalone ledger.solve())
        with self.trace.span("solve", tick=int(t)):
            if self._priv is not None and self._priv.policy.dp:
                # one release per tick: perturb a copy of the global
                # state (the ledger itself stays noiseless) and account
                # the spend
                gs = self._release(ledger.global_stats(), salt=t)
                W = ledger.wire.solve(gs, self.lam)
                jax.block_until_ready(W)
            else:
                W = ledger.solve(self.lam)
        coordinator_time = time.perf_counter() - t0
        uploaded = recompute if not delta else changed
        wire_bytes = sum(self._cw().wire_bytes(stats[i])
                         for i in uploaded)
        active = ledger.clients
        P = len(data)
        # the scenario's simulated straggler delays gate this tick too:
        # train_time = slowest participant clock, as on the round paths
        roles = ClientRoles(on_time=active, late=(),
                            dropped=tuple(sorted(set(range(P)) -
                                                 set(active))),
                            delays=tuple(delays))
        # the tick's faults report carries the ledger's standing
        # membership fallout — departures and evictions stay distinct
        # buckets (an evicted client was quarantined post-fold, never a
        # graceful leave; the schema test pins this apart)
        faults = empty_faults_report()
        faults["departed"] = sorted(int(c) for c in ledger.departed)
        faults["evicted"] = {int(c): ledger.evicted[c]
                             for c in sorted(ledger.evicted)}
        return RoundReport(
            W=W, client_times=[time_by.get(i, 0.0) for i in active],
            coordinator_time=coordinator_time, wire_bytes=wire_bytes,
            roles=roles, faults=faults,
            n_samples=sum(int(data[i][0].shape[0]) for i in active),
            dispatches=dispatches, tick=t, changed=tuple(changed),
            # on event-driven ticks the REGISTRY is the residency: exact
            # unlearning keeps every active client's statistics held, so
            # a tier tree cannot flatten this number (DESIGN.md §11)
            peak_coordinator_bytes=ledger.resident_bytes())

    # ------------------------------------------------- in-process paths
    def _client_stats(self, X, d):
        if self.transport != "stream" or self.chunks == 1 \
                or X.shape[0] == 0:
            # empty shards (over-partitioned data) take the batch path,
            # which handles n == 0 uniformly across wires
            return self.wire.local_stats(X, d)
        # stream transport: the chunk-folding edge client — each chunk's
        # statistics merge into the running aggregate, data is never
        # held whole (StreamingClient semantics as a transport)
        chunked = getattr(self.wire, "local_stats_chunked", None)
        if chunked is not None:
            # additive wires fold the chunk axis inside one lax.scan
            # program (O(c·m²) carry) instead of a Python merge loop
            return chunked(X, d, self.chunks)
        agg = None
        for idx in np.array_split(np.arange(X.shape[0]),
                                  min(self.chunks, X.shape[0])):
            st = self.wire.local_stats(X[idx], d[idx])
            agg = st if agg is None else self.wire.merge(agg, st)
        return agg

    def _fold(self, stats_list):
        cw = self._cw()
        return cw.merge_tree(stats_list) if self.tree else \
            cw.merge_many(stats_list)

    def _release(self, agg, salt: int):
        """Pre-solve privacy step: central-DP perturbation of (a copy
        of) the aggregate, and the (ε, δ) accounting — one spend per
        released model."""
        return agg if self._priv is None else \
            self._priv.finalize(agg, salt=salt)

    def _coordinator(self, stats, roles):
        """Shared merge → (first solve →) solve tail, timed."""
        cw = self._cw()
        t0 = time.perf_counter()
        with self.trace.span("merge", n_uploads=len(roles.on_time)) as sp:
            agg = sp.ready(self._fold([stats[i] for i in roles.on_time]))
        W_first = None
        if roles.late:
            # first solve from the on-time group — a usable model — then
            # admit the late joiners incrementally (paper §3.2)
            with self.trace.span("solve", first=True):
                W_first = cw.solve(self._release(agg, salt=1), self.lam)
                jax.block_until_ready(W_first)
            with self.trace.span("merge", n_uploads=len(roles.late)) as sp:
                for i in roles.late:
                    agg = cw.merge(agg, stats[i])
                sp.ready(agg)
        with self.trace.span("solve"):
            W = cw.solve(self._release(agg, salt=0), self.lam)
            jax.block_until_ready(W)
        return W, W_first, time.perf_counter() - t0

    def _run_inprocess(self, parts_X, parts_d) -> RoundReport:
        roles = self.scenario.roles(len(parts_X))
        roles = self._apply_faults(roles, parts_X, parts_d)
        roles, sel = self._apply_selection(roles, parts_X, parts_d)
        if sel is not None:
            # the scoring pass was the client phase; commit the
            # selected cohort over its (already encoded) uploads
            return self._commit_selected(parts_X, parts_d, roles, sel)
        if self._priv is not None:
            # the round's cohort is known up front (a real coordinator
            # announces it): distributed noise shares scale to the
            # participants that will actually sum, not the universe
            self._priv.cohort = len(roles.participants)
        if self.batch_clients and self.transport == "local":
            if self.fused:
                return self._run_fused(parts_X, parts_d, roles)
            return self._run_batched(parts_X, parts_d, roles)
        stats, time_by, dispatches = self._phase_stats(
            parts_X, parts_d, roles.participants)
        if self.warmup and roles.participants and \
                not (self._priv is not None and self._priv.masked):
            # merge + solve compile pass (the client pass warmed inside
            # _phase_stats) so the timed coordinator is steady-state;
            # skipped under masking — a ring merge of one client with
            # itself is a double upload, which the session rejects
            i0 = roles.participants[0]
            jax.block_until_ready(self.wire.solve(
                self.wire.merge(stats[i0], stats[i0]), self.lam))
        wire_bytes = sum(self._cw().wire_bytes(stats[i])
                         for i in roles.participants)
        W, W_first, coordinator_time = self._coordinator(stats, roles)
        return RoundReport(
            W=W, client_times=[time_by[i] for i in roles.participants],
            coordinator_time=coordinator_time,
            wire_bytes=wire_bytes, roles=roles,
            n_samples=sum(int(parts_X[i].shape[0])
                          for i in roles.participants),
            W_first=W_first, dispatches=dispatches,
            # the flat coordinator materializes every upload before the
            # fold — residency IS the round's wire bytes, O(P)
            peak_coordinator_bytes=wire_bytes)

    # -------------------------------------------- fleet-batched client phase
    def _buckets(self, parts_X, idxs):
        """Group client indices by power-of-two padded sample count.

        Compile count per round becomes O(log n-spread) — every client
        whose shard size shares a power-of-two ceiling lands in the same
        stacked shape — instead of O(distinct shard shapes) on the
        per-client loop (DESIGN.md §8).
        """
        buckets = {}
        for i in idxs:
            buckets.setdefault(_bucket_bound(int(parts_X[i].shape[0])),
                               []).append(i)
        return sorted(buckets.items())

    def _stack_bucket(self, parts_X, parts_d, idxs, bound):
        """Stack a bucket's shards into zero-padded (P_b, bound, ·) arrays.

        Pad rows are all-zero in X (the wire supplies the bias column as
        the validity mask) and carry the activation midpoint ``f(0)`` in
        D so ``f_inv`` stays finite — exactly the mesh transport's
        padding convention (:func:`pad_for_mesh`).
        """
        np_dtype = np.dtype(getattr(self.wire, "dtype", np.float32))
        m_in = parts_X[idxs[0]].shape[1]
        c = parts_d[idxs[0]].shape[1]
        with self.trace.span("bucket.stack", bound=int(bound),
                             n_clients=len(idxs)) as sp:
            mid = float(acts.get(self.wire.act).f(
                jnp.zeros((), jnp.float32)))
            Xs = np.zeros((len(idxs), bound, m_in), np_dtype)
            Ds = np.full((len(idxs), bound, c), mid, np_dtype)
            ns = np.zeros((len(idxs),), np.int32)
            for row, i in enumerate(idxs):
                n = int(parts_X[i].shape[0])
                Xs[row, :n] = np.asarray(parts_X[i], np_dtype)
                Ds[row, :n] = np.asarray(parts_d[i], np_dtype)
                ns[row] = n
            if self.trace.enabled:
                # pulled: the device-resident shards read back; built:
                # the stacks the next dispatch uploads
                pulled = sum(int(a.nbytes) for i in idxs
                             for a in (parts_X[i], parts_d[i])
                             if isinstance(a, jax.Array))
                sp.set(bytes=pulled + Xs.nbytes + Ds.nbytes + ns.nbytes)
        return Xs, Ds, ns

    def _out_attrs(self, P: int, bound: int, m_in: int, c: int,
                   fold: bool) -> dict:
        """``bucket.dispatch``'s ``bytes_out``: the statistics bytes the
        bucket program's fleet pass writes (per client, or once when it
        folds); nothing for a wire that cannot say."""
        count = getattr(self.wire, "fleet_out_bytes", None)
        if count is None or not self.trace.enabled:
            return {}
        return {"bytes_out": int(count(P, bound, m_in, c, fold=fold))}

    @staticmethod
    def _share_times(time_by, idxs, ns, dt):
        """Attribute one bucket dispatch's wall time by sample share
        (added onto any already-charged client time, e.g. clipping)."""
        total = int(ns.sum())
        for i, n in zip(idxs, ns):
            time_by[i] = time_by.get(i, 0.0) + \
                dt * (int(n) / total if total else 1 / len(idxs))

    def _phase_stats(self, parts_X, parts_d, idxs):
        """Client-phase statistics for ``idxs`` — one dispatch per shape
        bucket when ``batch_clients`` (local transport only: streaming
        clients keep their chunk-folding pass), else the per-client
        loop. Returns ``(stats, time_by, dispatches)`` keyed by client
        index.
        """
        stats, time_by, dispatches = {}, {}, 0
        if self._priv is not None and self._priv.policy.dp:
            # per-row clipping is client-side work: run it inside the
            # metered region and charge each client's clock for it
            # (the module docstring and privacy_bench both promise the
            # §4.1 metrics include it)
            clipped = {}
            for i in idxs:
                t0 = time.perf_counter()
                clipped[i] = self._priv.clip(parts_X[i])
                time_by[i] = time.perf_counter() - t0
            parts_X = clipped
        if not (self.batch_clients and self.transport == "local"):
            if self.warmup and idxs:
                # untimed compile pass at the first client's shapes, as
                # on the loop transport path, so client_times below
                # measure steady-state execution
                i0 = idxs[0]
                jax.block_until_ready(
                    self._client_stats(parts_X[i0], parts_d[i0]))
            for i in idxs:
                t0 = time.perf_counter()
                with self.trace.span("client.stats", track="client",
                                     cid=int(i)):
                    stats[i] = self._client_stats(parts_X[i],
                                                  parts_d[i])
                    with self.trace.span("client.wait", track="client"):
                        jax.block_until_ready(stats[i])
                time_by[i] = time_by.get(i, 0.0) + \
                    (time.perf_counter() - t0)
                dispatches += 1
            return self._encode_stats(stats, time_by), time_by, \
                dispatches
        for bound, b_idxs in self._buckets(parts_X, idxs):
            if bound == 0:
                # empty shards: per-client call (their statistics are
                # exactly zero but still count one upload, as on the loop)
                for i in b_idxs:
                    t0 = time.perf_counter()
                    with self.trace.span("client.stats",
                                         track="client", cid=int(i)):
                        stats[i] = self.wire.local_stats(parts_X[i],
                                                         parts_d[i])
                        with self.trace.span("client.wait",
                                             track="client"):
                            jax.block_until_ready(stats[i])
                    time_by[i] = time_by.get(i, 0.0) + \
                        (time.perf_counter() - t0)
                    dispatches += 1
                continue
            Xs, Ds, ns = self._stack_bucket(parts_X, parts_d, b_idxs,
                                            bound)
            if self.warmup:
                # compile this bucket's stacked shape once, untimed
                jax.block_until_ready(
                    self.wire.local_stats_batch(Xs, Ds, ns))
            t0 = time.perf_counter()
            with self.trace.span("bucket.dispatch", bound=int(bound),
                                 n_clients=len(b_idxs)):
                batch = self.wire.local_stats_batch(Xs, Ds, ns)
                with self.trace.span("client.wait"):
                    jax.block_until_ready(batch)
            # a wire riding _WireBase's default batch (a per-client loop
            # over the stack) really dispatches once per client — keep
            # the dispatch metric honest for custom wires
            native = type(self.wire).local_stats_batch \
                is not _WireBase.local_stats_batch
            dispatches += 1 if native else len(b_idxs)
            self._share_times(time_by, b_idxs, ns,
                              time.perf_counter() - t0)
            stats.update(zip(b_idxs, batch))
        return self._encode_stats(stats, time_by), time_by, dispatches

    def _run_batched(self, parts_X, parts_d, roles) -> RoundReport:
        stats, time_by, dispatches = self._phase_stats(
            parts_X, parts_d, roles.participants)
        if self.warmup and roles.participants and \
                not (self._priv is not None and self._priv.masked):
            i0 = roles.participants[0]
            jax.block_until_ready(self.wire.solve(
                self.wire.merge(stats[i0], stats[i0]), self.lam))
        wire_bytes = sum(self._cw().wire_bytes(stats[i])
                         for i in roles.participants)
        W, W_first, coordinator_time = self._coordinator(stats, roles)
        return RoundReport(
            W=W, client_times=[time_by[i] for i in roles.participants],
            coordinator_time=coordinator_time, wire_bytes=wire_bytes,
            roles=roles,
            n_samples=sum(int(parts_X[i].shape[0])
                          for i in roles.participants),
            W_first=W_first, dispatches=dispatches,
            # per-client statistics materialize before the fold, as on
            # the loop path: residency = the round's upload bytes
            peak_coordinator_bytes=wire_bytes)

    # ------------------------------------------------------ fused round
    def _fused_fn(self, with_solve: bool):
        """stats → leading-axis merge (→ solve) as ONE jitted program.

        The merge is the wire's folded fleet pass (``fleet_stats(...,
        fold=True)``): on the gram wire's Pallas kernel the bucket's
        clients accumulate in place, so the program never holds the
        per-client statistics stack. The stacked client buffers are
        donated (no-op on CPU, where XLA does not implement donation) —
        at P=1000 the (P, n_max, m) stack is the round's dominant
        allocation and the program may reuse it in place.
        """
        if with_solve not in self._fused_cache:
            wire, lam = self.wire, self.lam

            def prog(Xs, Ds, ns):
                agg = wire.fleet_stats(Xs, Ds, ns, fold=True)
                return wire.solve(agg, lam) if with_solve else agg

            donate = (0, 1) if jax.default_backend() != "cpu" else ()
            self._fused_cache[with_solve] = jax.jit(
                prog, donate_argnums=donate)
        return self._fused_cache[with_solve]

    def _masked_fused_fn(self, share: float):
        """One bucket's masked round as ONE jitted program: fleet stats
        → (per-client σ/√cohort noise shares, secagg+dp) → exact limb
        encode → pairwise pads (lazy ring add) → ring sum over the
        client axis → carry-normalize. Per-client statistics exist only
        as traced intermediates; the program's sole output is the
        bucket's masked ring aggregate, which the host wraps via
        ``SecAggSession.from_flat``. Runs under x64 (the limb ops are
        int64); the f32 statistics themselves are unchanged by x64 —
        JAX's weak typing keeps explicitly-dtyped programs bit-stable
        (pinned by the conformance suite).
        """
        key = ("masked", share)
        if key not in self._fused_cache:
            from ..privacy import limbs as _limbs
            wire, priv = self.wire, self._priv
            words = priv.session.words
            noisy = priv.policy.dp

            def prog(Xs, Ds, ns, pads, keys):
                st = wire.fleet_stats(Xs, Ds, ns)
                if noisy:
                    st = priv.noise_shares_stacked(st, keys, share)
                enc = _limbs.encode_tree(wire.secagg_encode(st), words,
                                         stacked=True)
                return _limbs.carry_limbs(
                    _limbs.sum_limbs(_limbs.add_limbs(enc, pads)))

            donate = (0, 1) if jax.default_backend() != "cpu" else ()
            self._fused_cache[key] = jax.jit(prog, donate_argnums=donate)
        return self._fused_cache[key]

    def _run_fused(self, parts_X, parts_d, roles) -> RoundReport:
        priv = self._priv
        time_by = {i: 0.0 for i in roles.participants}
        if priv is not None and priv.policy.dp:
            # per-row clipping is client-side work, timed per client as
            # on the loop path; the fused programs then consume the
            # clipped shards
            parts_X = list(parts_X)
            for i in roles.participants:
                t0 = time.perf_counter()
                parts_X[i] = priv.clip(parts_X[i])
                time_by[i] = time.perf_counter() - t0
        on_buckets = [b for b in self._buckets(parts_X, roles.on_time)
                      if b[0] > 0]
        late_buckets = [b for b in self._buckets(parts_X, roles.late)
                        if b[0] > 0]
        # empty shards contribute exactly-zero statistics: they never
        # enter a fused program, only the (analytic) upload accounting —
        # except under masking, where even a zero upload carries pads
        # that must cancel in the aggregate (handled below)
        m_in = parts_X[0].shape[1] if len(parts_X) else 0
        c = parts_d[0].shape[1] if len(parts_d) else 1
        wire_bytes = sum(
            self._cw().stats_bytes(int(parts_X[i].shape[0]), m_in, c)
            for i in roles.participants)
        dispatches = 0

        def run_bucket(fn, idxs, bound):
            nonlocal dispatches
            Xs, Ds, ns = self._stack_bucket(parts_X, parts_d, idxs, bound)
            if self.warmup:
                jax.block_until_ready(
                    fn(*self._stack_bucket(parts_X, parts_d, idxs,
                                           bound)))
            t0 = time.perf_counter()
            with self.trace.span("bucket.dispatch", bound=int(bound),
                                 n_clients=len(idxs), fused=True,
                                 **self._out_attrs(len(idxs), bound, m_in,
                                                   c, fold=True)):
                out = fn(Xs, Ds, ns)
                with self.trace.span("client.wait"):
                    jax.block_until_ready(out)
            dispatches += 1
            self._share_times(time_by, idxs, ns,
                              time.perf_counter() - t0)
            return out

        if priv is not None and priv.masked:
            return self._run_fused_masked(
                parts_X, parts_d, roles, on_buckets, late_buckets,
                time_by, wire_bytes)

        # a scenario with late joiners must produce W_first even if every
        # late shard is empty (late_buckets drops bound-0 shards), so the
        # one-shot fusion keys on the roles, not the bucket list; an
        # active dp policy releases host-side (noise + accounting), so
        # the solve cannot fuse into the program
        one_shot = len(on_buckets) == 1 and not roles.late \
            and priv is None
        if one_shot:
            # the whole round — every client's pass, the merge, and the
            # solve — is one compiled dispatch
            bound, idxs = on_buckets[0]
            W = run_bucket(self._fused_fn(True), idxs, bound)
            W_first, coordinator_time = None, 0.0
            peak = 0    # per-client stats and the aggregate live only
            #             as traced intermediates of the one dispatch
        else:
            partial = self._fused_fn(False)
            on_aggs = [run_bucket(partial, idxs, bound)
                       for bound, idxs in on_buckets]
            late_aggs = [run_bucket(partial, idxs, bound)
                         for bound, idxs in late_buckets]
            # every bucket aggregate is host-resident before the fold
            peak = sum(self.wire.wire_bytes(a)
                       for a in on_aggs + late_aggs)
            t0 = time.perf_counter()
            with self.trace.span("merge", n_uploads=len(on_aggs)) as sp:
                agg = self.wire.merge_many(on_aggs) if on_aggs else None
                W_first = None
                if agg is None:
                    # every on-time shard was empty: fall back to their
                    # (zero) per-client statistics so the solve still
                    # runs
                    agg = self._fold([self.wire.local_stats(parts_X[i],
                                                            parts_d[i])
                                      for i in roles.on_time])
                sp.ready(agg)
            if roles.late:
                with self.trace.span("solve", first=True):
                    W_first = self.wire.solve(
                        self._release(agg, salt=1), self.lam)
                    jax.block_until_ready(W_first)
                with self.trace.span("merge",
                                     n_uploads=len(late_aggs)) as sp:
                    for st in late_aggs:
                        agg = self.wire.merge(agg, st)
                    sp.ready(agg)
            with self.trace.span("solve"):
                W = self.wire.solve(self._release(agg, salt=0),
                                    self.lam)
                jax.block_until_ready(W)
            coordinator_time = time.perf_counter() - t0
        return RoundReport(
            W=W, client_times=[time_by[i] for i in roles.participants],
            coordinator_time=coordinator_time, wire_bytes=wire_bytes,
            roles=roles,
            n_samples=sum(int(parts_X[i].shape[0])
                          for i in roles.participants),
            W_first=W_first, dispatches=dispatches,
            peak_coordinator_bytes=peak)

    def _run_fused_masked(self, parts_X, parts_d, roles, on_buckets,
                          late_buckets, time_by, wire_bytes
                          ) -> RoundReport:
        """The fused round under masking: one jitted masked program per
        bucket (``_masked_fused_fn``), the ordinary MaskedWire
        merge/solve tail on the per-bucket ring aggregates. A uniform
        masked round (one bucket, no late joiners, no empty shards) is
        ONE client-phase dispatch, exactly like the unprivate fused
        path — ring addition is order-independent, so ``W`` bit-matches
        the masked loop path.
        """
        priv, cw = self._priv, self._cw()
        sess = priv.session
        i0 = roles.participants[0] if roles.participants else 0
        # bind the template + pad cache from a zero-row shard (shape
        # bookkeeping, untimed — see PrivacyRun.prepare); zero-row
        # local_stats is the same empty-shard path every transport uses
        template = self.wire.local_stats(
            np.asarray(parts_X[i0])[:0], np.asarray(parts_d[i0])[:0])
        priv.prepare(template)
        from ..privacy.limbs import check_fleet_headroom
        check_fleet_headroom(len(roles.participants))
        share = priv.share_sigma(template) if priv.policy.dp else 0.0
        fn = self._masked_fused_fn(share)
        dispatches = 0

        def run_masked_bucket(idxs, bound):
            nonlocal dispatches
            Xs, Ds, ns = self._stack_bucket(parts_X, parts_d, idxs,
                                            bound)
            pads = sess.flat_pad_sums(idxs)
            keys = priv.share_keys(idxs) if priv.policy.dp else \
                np.zeros((len(idxs), 2), np.uint32)
            with enable_x64():
                if self.warmup:
                    # fresh stack: the program may have donated buffers
                    # (warmup reuses the same keys — its output is
                    # discarded, never released)
                    jax.block_until_ready(fn(
                        *self._stack_bucket(parts_X, parts_d, idxs,
                                            bound), pads, keys))
                t0 = time.perf_counter()
                with self.trace.span("bucket.dispatch",
                                     bound=int(bound),
                                     n_clients=len(idxs), fused=True,
                                     masked=True):
                    out = fn(Xs, Ds, ns, pads, keys)
                    with self.trace.span("client.wait"):
                        jax.block_until_ready(out)
            dispatches += 1
            self._share_times(time_by, idxs, ns,
                              time.perf_counter() - t0)
            return sess.from_flat(np.asarray(out),
                                  frozenset(int(i) for i in idxs))

        def mask_empties(idxs):
            # empty shards still publish: their zero statistics carry
            # pads (and noise shares) the aggregate needs to cancel —
            # a real per-client dispatch, timed and counted
            nonlocal dispatches
            out = []
            for i in idxs:
                t0 = time.perf_counter()
                with self.trace.span("mask.encode", track="client",
                                     cid=int(i), empty=True):
                    st = self.wire.local_stats(parts_X[i], parts_d[i])
                    out.append(priv.client_encode(int(i), st))
                time_by[i] = time_by.get(i, 0.0) + \
                    (time.perf_counter() - t0)
                dispatches += 1
            return out

        on_aggs = [run_masked_bucket(idxs, bound)
                   for bound, idxs in on_buckets]
        on_aggs += mask_empties(
            [i for i in roles.on_time
             if int(parts_X[i].shape[0]) == 0])
        late_aggs = [run_masked_bucket(idxs, bound)
                     for bound, idxs in late_buckets]
        late_aggs += mask_empties(
            [i for i in roles.late if int(parts_X[i].shape[0]) == 0])
        # every masked bucket/empty-shard aggregate (a fixed-size ring
        # element) is host-resident before the fold
        peak = (len(on_aggs) + len(late_aggs)) * sess.upload_bytes
        t0 = time.perf_counter()
        with self.trace.span("merge", n_uploads=len(on_aggs)) as sp:
            agg = sp.ready(cw.merge_many(on_aggs))
        W_first = None
        if roles.late:
            with self.trace.span("solve", first=True):
                W_first = cw.solve(self._release(agg, salt=1),
                                   self.lam)
                jax.block_until_ready(W_first)
            with self.trace.span("merge", n_uploads=len(late_aggs)) as sp:
                for st in late_aggs:
                    agg = cw.merge(agg, st)
                sp.ready(agg)
        with self.trace.span("solve"):
            W = cw.solve(self._release(agg, salt=0), self.lam)
            jax.block_until_ready(W)
        coordinator_time = time.perf_counter() - t0
        return RoundReport(
            W=W, client_times=[time_by[i] for i in roles.participants],
            coordinator_time=coordinator_time, wire_bytes=wire_bytes,
            roles=roles,
            n_samples=sum(int(parts_X[i].shape[0])
                          for i in roles.participants),
            W_first=W_first, dispatches=dispatches,
            peak_coordinator_bytes=peak)

    # ------------------------------------------------ hierarchical round
    def _hier_mode(self) -> str:
        """The tier-exchange fold codec (DESIGN.md §11): ``masked``
        (secagg policies — ring adds, interior pads cancel per tier),
        ``exact`` (the dyadic-integer ring — bit-identical re-tiering),
        or ``float`` (plain ``Wire.merge`` — allclose re-tiering)."""
        topo = self.topology
        if self._priv is not None and self._priv.masked:
            return "masked"
        capable = False
        if topo.exact != "off":
            try:
                self.wire.secagg_encode()
                capable = True
            except (AttributeError, NotImplementedError, TypeError):
                capable = False
        if topo.exact == "on" and not capable:
            raise ValueError(
                "topology exact=on needs a wire with an exact additive "
                f"encoding, but wire "
                f"{getattr(self.wire, 'name', self.wire)!r} has none "
                "(the Iwen-Ong factor merge is not additive); use "
                "exact=off for the float fold")
        return "exact" if capable else "float"

    def _exact_fused_fn(self, words: int):
        """One edge bucket's exact group fold as ONE jitted program:
        fleet stats → exact dyadic limb encode → ring sum over the
        member axis → carry-normalize. The unmasked twin of
        ``_masked_fused_fn`` (no pads, no noise shares): its output is
        the group's ring aggregate — the unit tiers exchange, whose
        integer adds are order-independent, so any re-tiering decodes
        to the bit-identical flat exact fold. Runs under x64 (int64
        limbs); the f32 statistics are unchanged by it (weak typing,
        pinned by the conformance suite)."""
        key = ("exact", words)
        if key not in self._fused_cache:
            from ..privacy import limbs as _limbs
            wire = self.wire

            def prog(Xs, Ds, ns):
                st = wire.fleet_stats(Xs, Ds, ns)
                enc = _limbs.encode_tree(wire.secagg_encode(st), words,
                                         stacked=True)
                return _limbs.carry_limbs(_limbs.sum_limbs(enc))

            donate = (0, 1) if jax.default_backend() != "cpu" else ()
            self._fused_cache[key] = jax.jit(prog, donate_argnums=donate)
        return self._fused_cache[key]

    def _hier_mesh_groups(self, parts_X, parts_d, tree, subset, mode,
                          words, time_by, warmed):
        """ALL of ``subset``'s edge groups as ONE sharded dispatch:
        sibling edge aggregators ride the mesh axis (each device runs a
        whole group's fused fold), groups padded to a uniform
        (gsize, bound) stack and the group count padded to divide the
        axis with all-zero dummy groups (dropped on return). Returns
        ``({edge_idx: aggregate}, n_dispatches)``.

        Unlike the host tree walk this materializes every sibling's
        aggregate at once — peak residency is n_groups·agg_bytes, the
        devices-for-memory trade the mesh makes (the bench's flat-in-P
        row therefore runs the local transport)."""
        import contextlib
        from jax.sharding import PartitionSpec as P
        wire = self.wire
        mesh = self.mesh or make_client_mesh(axis=self.axis)
        Dn = mesh.shape[self.axis]
        groups = []
        for e, ids in enumerate(tree.levels[0]):
            members = [i for i in ids if i in subset
                       and int(parts_X[i].shape[0]) > 0]
            if members:
                groups.append((e, members))
        if not groups:
            return {}, 0
        gsize = max(len(m) for _, m in groups)
        bound = max(_bucket_bound(int(parts_X[i].shape[0]))
                    for _, m in groups for i in m)
        G = -(-len(groups) // Dn) * Dn
        np_dtype = np.dtype(getattr(wire, "dtype", np.float32))
        i00 = groups[0][1][0]
        m_in, c = parts_X[i00].shape[1], parts_d[i00].shape[1]
        mid = float(acts.get(wire.act).f(jnp.zeros((), jnp.float32)))
        Xs = np.zeros((G, gsize, bound, m_in), np_dtype)
        Ds = np.full((G, gsize, bound, c), mid, np_dtype)
        ns = np.zeros((G, gsize), np.int32)
        for g, (_, members) in enumerate(groups):
            for row, i in enumerate(members):
                n = int(parts_X[i].shape[0])
                Xs[g, row, :n] = np.asarray(parts_X[i], np_dtype)
                Ds[g, row, :n] = np.asarray(parts_d[i], np_dtype)
                ns[g, row] = n

        if mode == "exact":
            from ..privacy import limbs as _limbs

            def group_prog(Xg, Dg, ng):
                st = wire.fleet_stats(Xg, Dg, ng)
                enc = _limbs.encode_tree(wire.secagg_encode(st), words,
                                         stacked=True)
                return _limbs.carry_limbs(_limbs.sum_limbs(enc))

            out_specs = P(self.axis, None, None)
            ctx = enable_x64()
        else:
            def group_prog(Xg, Dg, ng):
                return wire.fleet_stats(Xg, Dg, ng, fold=True)

            template = jax.eval_shape(
                jax.vmap(group_prog),
                jax.ShapeDtypeStruct(Xs.shape, Xs.dtype),
                jax.ShapeDtypeStruct(Ds.shape, Ds.dtype),
                jax.ShapeDtypeStruct(ns.shape, ns.dtype))
            out_specs = jax.tree_util.tree_map(
                lambda s: P(self.axis, *([None] * (len(s.shape) - 1))),
                template)
            ctx = contextlib.nullcontext()
        fn = jax.shard_map(
            jax.vmap(group_prog), mesh=mesh,
            in_specs=(P(self.axis, None, None, None),
                      P(self.axis, None, None, None),
                      P(self.axis, None)),
            out_specs=out_specs, check_vma=False)
        with ctx:
            wk = ("hier-mesh", mode, G, gsize, bound)
            if self.warmup and wk not in warmed:
                warmed.add(wk)
                jax.block_until_ready(fn(Xs, Ds, ns))
            t0 = time.perf_counter()
            with self.trace.span("collective", mode=mode,
                                 n_groups=len(groups)):
                out = fn(Xs, Ds, ns)
                jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        flat_members = [i for _, m in groups for i in m]
        flat_ns = np.asarray([int(parts_X[i].shape[0])
                              for i in flat_members])
        self._share_times(time_by, flat_members, flat_ns, dt)
        result = {}
        for g, (e, _) in enumerate(groups):
            if mode == "exact":
                result[e] = np.asarray(out[g])
            else:
                result[e] = jax.tree_util.tree_map(lambda lf: lf[g], out)
        return result, 1

    def _run_hierarchical(self, parts_X, parts_d) -> RoundReport:
        """One round over ``self.topology``'s tier tree (DESIGN.md §11).

        The in-process engine plays every role: each edge aggregator's
        fold runs as the fleet-batched pow2-bucket FUSED program over
        its members (their stat passes and the edge fold are one
        dispatch — timed into ``client_times`` by sample share), and
        tier merges stream depth-first through :meth:`TierTree.fold`,
        so the coordinator process never holds more than one open
        aggregate per tier plus the group being folded
        (``peak_coordinator_bytes`` meters it). On the stream transport
        members chunk-fold individually; on the mesh transport sibling
        edge aggregators share one sharded dispatch
        (:meth:`_hier_mesh_groups`).

        Late joiners fold through a second tree pass whose root merges
        into the on-time root after ``W_first`` — dropout of a whole
        edge group simply yields no aggregate for that leaf. The
        simulated latency model (:func:`~.topology.simulate_round`)
        prices the same round's uploads tiered vs flat into
        ``report.hierarchy``; ``wire_bytes`` counts the tiered plan
        (client uploads + one uplink per non-root aggregator).
        """
        import contextlib
        topo = self.topology
        P = len(parts_X)
        roles = self.scenario.roles(P)
        roles = self._apply_faults(roles, parts_X, parts_d)
        # selection scores in one flat coordinator-side pass, then the
        # tier fold below runs over the selected cohort only (its
        # client phase recomputes — the tiered fold is the committed
        # round; the scoring pass's dispatches/bytes are accounted in
        # report.contribution and dispatches)
        roles, sel = self._apply_selection(roles, parts_X, parts_d)
        priv = self._priv
        if priv is not None:
            priv.cohort = len(roles.participants)
        tree = topo.tree(P)
        mode = self._hier_mode()
        if mode == "masked" and self.transport == "mesh":
            raise ValueError(
                "masked hierarchical rounds need an in-process "
                "transport (local|stream): the mesh's sibling-"
                "aggregator collective would materialize every group's "
                "masked pool at once with no tier to cancel pads in")
        plan, fb = self.fault_plan, self._fb
        if plan is not None and plan.aggfail:
            # tier-aggregator failover: the failed aggregator's
            # children are adopted by a sibling and re-folded there —
            # the exact/masked codecs are re-tiering invariant, so the
            # recovered solve bit-matches the no-failure fold
            for t_, g_ in plan.aggfail:
                tree, moved = failover(tree, t_, g_)
                fb.failed_over.append(f"tier{t_}:g{g_}")
                fb.refolds += moved
                self.trace.event("fault.failover", tier=int(t_),
                                 group=int(g_), refolds=int(moved))
        journal = None
        if self.journal_path:
            if mode == "float":
                raise ValueError(
                    "the round journal needs an exact tier codec "
                    "(gram wire, exact or masked fold): float "
                    "aggregates have no bit-stable digits to commit")
            journal = RoundJournal(self.journal_path, mode=mode)
        time_by = {i: 0.0 for i in roles.participants}
        if priv is not None and priv.policy.dp:
            # per-row clipping is client-side work, timed per client
            parts_X = list(parts_X)
            for i in roles.participants:
                t0 = time.perf_counter()
                parts_X[i] = priv.clip(parts_X[i])
                time_by[i] = time.perf_counter() - t0
        i0 = roles.participants[0] if roles.participants else 0
        m_in = parts_X[i0].shape[1] if P else 0
        c = parts_d[i0].shape[1] if P else 1
        folder = sess = None
        share = 0.0
        cw = self._cw()
        if mode != "float":
            # the codecs size their rings from one (empty) client's
            # statistics; the float fold needs no template pass
            template = self.wire.local_stats(
                np.asarray(parts_X[i0])[:0], np.asarray(parts_d[i0])[:0])
        if mode == "exact":
            folder = ExactFold(self.wire, template)
            agg_bytes = folder.agg_bytes
        elif mode == "masked":
            priv.prepare(template)
            sess = priv.session
            from ..privacy.limbs import check_fleet_headroom
            # any single tier ring-sums at most one group (≤ fanout ≤
            # the lazy-carry headroom); host merges carry-normalize
            check_fleet_headroom(tree.max_group)
            share = priv.share_sigma(template) if priv.policy.dp else 0.0
            agg_bytes = sess.upload_bytes
        else:
            # one AGGREGATE's wire size (svd factor rank caps at m)
            agg_bytes = self.wire.stats_bytes(m_in + 1, m_in, c)
        meter = _PeakMeter()
        dispatches = 0
        merge_s = 0.0
        merges = 0
        warmed = set()

        def size_of(a):
            if mode == "exact":
                return folder.agg_bytes
            if mode == "masked":
                return sess.upload_bytes
            return self.wire.wire_bytes(a)

        def tier_add(a, b):
            if mode == "exact":
                return folder.add(a, b)
            if mode == "masked":
                return cw.merge(a, b)
            return self.wire.merge(a, b)

        def merge_fn(level, acc, sub):
            nonlocal merge_s, merges
            sa, sb = size_of(acc), size_of(sub)
            t0 = time.perf_counter()
            with self.trace.span("tier.fold", tier=int(level),
                                 bytes=int(sa + sb)) as sp:
                # traced, the span ends on the aggregate, not on the
                # enqueue of its device add
                out = sp.ready(tier_add(acc, sub))
            merge_s += time.perf_counter() - t0
            merges += 1
            meter.pop(sa)
            meter.pop(sb)
            meter.push(size_of(out))
            return out

        def run_bucket(b_idxs, bound):
            """One pow2 shape bucket of one edge group, one dispatch."""
            nonlocal dispatches
            Xs, Ds, ns = self._stack_bucket(parts_X, parts_d, b_idxs,
                                            bound)
            extra = ()
            if mode == "exact":
                fn, ctx = self._exact_fused_fn(folder.words), \
                    enable_x64()
            elif mode == "masked":
                fn, ctx = self._masked_fused_fn(share), enable_x64()
                keys = priv.share_keys(b_idxs) if priv.policy.dp else \
                    np.zeros((len(b_idxs), 2), np.uint32)
                extra = (sess.flat_pad_sums(b_idxs), keys)
            else:
                fn, ctx = self._fused_fn(False), contextlib.nullcontext()
            with ctx:
                wk = (mode, bound, len(b_idxs))
                if self.warmup and wk not in warmed:
                    warmed.add(wk)
                    jax.block_until_ready(fn(*self._stack_bucket(
                        parts_X, parts_d, b_idxs, bound), *extra))
                t0 = time.perf_counter()
                with self.trace.span("bucket.dispatch",
                                     bound=int(bound),
                                     n_clients=len(b_idxs),
                                     fused=True, mode=mode,
                                     **self._out_attrs(
                                         len(b_idxs), bound, m_in, c,
                                         fold=mode == "float")):
                    out = fn(Xs, Ds, ns, *extra)
                    with self.trace.span("client.wait"):
                        jax.block_until_ready(out)
            dispatches += 1
            self._share_times(time_by, b_idxs, ns,
                              time.perf_counter() - t0)
            if mode == "exact":
                return np.asarray(out)
            if mode == "masked":
                return sess.from_flat(np.asarray(out),
                                      frozenset(int(i) for i in b_idxs))
            return out

        def client_stat(i):
            """One member's individual pass (stream transport's chunk
            fold, or a masked empty shard's pad-carrying upload), then
            the codec's per-client encode — timed like the loop path."""
            nonlocal dispatches
            if self.warmup and ("client",) not in warmed:
                warmed.add(("client",))
                jax.block_until_ready(
                    self._client_stats(parts_X[i], parts_d[i]))
            t0 = time.perf_counter()
            with self.trace.span("client.stats", track="client",
                                 cid=int(i), mode=mode):
                st = self._client_stats(parts_X[i], parts_d[i])
                with self.trace.span("client.wait", track="client"):
                    jax.block_until_ready(st)
                if mode == "exact":
                    st = folder.encode(st)
                elif mode == "masked":
                    st = priv.client_encode(int(i), st)
            time_by[i] = time_by.get(i, 0.0) + \
                (time.perf_counter() - t0)
            dispatches += 1
            return st

        stream = self.transport == "stream"

        if self.transport == "mesh":
            def make_leaf(subset):
                nonlocal dispatches
                pre, nd = self._hier_mesh_groups(
                    parts_X, parts_d, tree, subset, mode,
                    folder.words if mode == "exact" else 0,
                    time_by, warmed)
                dispatches += nd
                for a in pre.values():
                    meter.push(size_of(a))

                def leaf(e, ids):
                    return pre.pop(e, None)
                return leaf
        else:
            def make_leaf(subset):
                def leaf(e, ids):
                    members = [i for i in ids if i in subset]
                    acc = None

                    def take(sub):
                        nonlocal acc
                        meter.push(size_of(sub))
                        acc = sub if acc is None else \
                            merge_fn(0, acc, sub)

                    if stream:
                        for i in members:
                            if mode != "masked" and \
                                    int(parts_X[i].shape[0]) == 0:
                                continue    # exactly-zero statistics
                            take(client_stat(i))
                        return acc
                    for bound, b_idxs in self._buckets(parts_X,
                                                       members):
                        if bound > 0:
                            take(run_bucket(b_idxs, bound))
                    if mode == "masked":
                        # empty shards still publish under masking:
                        # their zero statistics carry pads (and noise
                        # shares) the tier aggregate needs to cancel
                        for i in members:
                            if int(parts_X[i].shape[0]) == 0:
                                take(client_stat(i))
                    return acc
                return leaf

        def journaled(passname, leaf):
            """WAL wrapper for one tree pass: completed edge
            aggregates commit their exact digit (or still-masked
            ring) snapshot before the fold moves on; a resumed round
            skips straight past recovered edges. ``die=N`` raises
            :class:`CoordinatorKilled` after the Nth fresh commit is
            durable — the canonical mid-fold kill."""
            if journal is None:
                return leaf

            def wrapped(e, ids):
                key = f"{passname}-e{e}"
                hit = journal.lookup(key)
                if hit is not None:
                    limbs, jids = hit
                    self._fb.recovered += 1
                    self.trace.event("fault.recovered", edge=int(e),
                                     key=key)
                    agg = sess.from_flat(
                        np.asarray(limbs, np.int64), jids) \
                        if mode == "masked" else np.asarray(limbs)
                    meter.push(size_of(agg))
                    return agg
                agg = leaf(e, ids)
                if agg is not None:
                    if mode == "masked":
                        journal.commit(key, sess.to_flat(agg),
                                       ids=agg.ids)
                    else:
                        journal.commit(key, np.asarray(agg))
                    self.trace.event("journal.commit", edge=int(e),
                                     key=key)
                    if plan is not None and \
                            0 < plan.die <= journal.commits:
                        raise CoordinatorKilled(journal.commits,
                                                journal.path)
                return agg

            return wrapped

        root = tree.fold(journaled("on", make_leaf(set(roles.on_time))),
                         merge_fn)
        if root is None:
            # every on-time shard was empty: the round still solves,
            # over the exactly-zero aggregate
            root = folder.zero() if mode == "exact" else \
                self.wire.merge_stream(
                    self.wire.local_stats(parts_X[i], parts_d[i])
                    for i in roles.on_time)
            meter.push(size_of(root))
        coord_s = 0.0

        def solve_root(agg, salt):
            nonlocal coord_s
            t0 = time.perf_counter()
            with self.trace.span("solve", first=salt == 1, mode=mode):
                stats = folder.decode(agg) if mode == "exact" else agg
                wire = cw if mode == "masked" else self.wire
                W = wire.solve(self._release(stats, salt=salt),
                               self.lam)
                jax.block_until_ready(W)
            coord_s += time.perf_counter() - t0
            return W

        W_first = None
        if roles.late:
            # first solve from the on-time tree — a usable model — then
            # the late joiners fold through their own tree pass and
            # merge in at the root (paper §3.2, re-tiered)
            W_first = solve_root(root, salt=1)
            late_root = tree.fold(
                journaled("late", make_leaf(set(roles.late))), merge_fn)
            if late_root is not None:
                root = merge_fn(tree.tiers, root, late_root)
        W = solve_root(root, salt=0)

        if mode == "masked":
            client_bytes = {i: sess.upload_bytes
                            for i in roles.participants}
        else:
            client_bytes = {
                i: self.wire.stats_bytes(int(parts_X[i].shape[0]),
                                         m_in, c)
                for i in roles.participants}
        client_ready = {i: time_by.get(i, 0.0) + roles.delays[i]
                        for i in roles.participants}
        retries = {i: n for i, n in fb.retried.items()
                   if i in client_ready} if fb is not None else {}
        sim = simulate_round(tree, topo, client_ready=client_ready,
                             client_bytes=client_bytes,
                             agg_bytes=agg_bytes,
                             merge_cost=merge_s / max(merges, 1),
                             j_per_byte=J_PER_BYTE,
                             retries=retries or None,
                             refolds=fb.refolds if fb is not None
                             else 0)
        if fb is not None:
            # per-link pricing supersedes _apply_faults' flat-WAN
            # estimate: retried client uploads ride the LAN tier here
            fb.retry_bytes = int(sim["retry_bytes"])
            fb.retry_j = float(sim["retry_j"])
        hierarchy = {"fanout": topo.fanout, "tiers": topo.tiers,
                     "mode": mode, "n_groups": tree.n_edges,
                     "agg_bytes": int(agg_bytes),
                     "peak_bound_bytes": int(topo.fanout * agg_bytes),
                     **sim}
        if sel is not None:
            # the flat scoring pass's compute/dispatches ride the same
            # report: selection happened before the tiered commit
            dispatches += sel["dispatches"]
            coord_s += sel["score_s"]
            for i, dt in sel["time_by"].items():
                time_by[i] = time_by.get(i, 0.0) + dt
        return RoundReport(
            W=W, client_times=[time_by[i] for i in roles.participants],
            coordinator_time=merge_s + coord_s,
            wire_bytes=int(sim["bytes_tiered"]), roles=roles,
            n_samples=sum(int(parts_X[i].shape[0])
                          for i in roles.participants),
            W_first=W_first, dispatches=dispatches,
            peak_coordinator_bytes=meter.peak, hierarchy=hierarchy,
            contribution=None if sel is None else sel["contribution"])

    # -------------------------------------------------------- mesh path
    def _mesh_masked(self, mesh, wire, X, D, Pn):
        """The masked collective: every device noise-shares (secagg+dp),
        ring-encodes and pads its own statistics inside the shard, then
        :meth:`MaskedWire.mesh_reduce` psums the limb arrays — interior
        pads cancel on-device exactly as they do host-side, so the
        replicated aggregate is the same ring element the loop path's
        coordinator holds. The host wraps it (``from_flat``), unmasks
        and solves. Runs under x64 for the int64 limb algebra; the f32
        statistics are unchanged by it (weak typing, pinned by the
        conformance suite)."""
        from ..privacy import limbs as _limbs
        from jax.sharding import PartitionSpec as P
        from ..launch.mesh import masked_round_specs
        priv, cw, axis, lam = self._priv, self._cw(), self.axis, self.lam
        sess = priv.session
        template = wire.local_stats(X[:0], D[:0])
        priv.prepare(template)
        _limbs.check_fleet_headroom(Pn)
        share = priv.share_sigma(template) if priv.policy.dp else 0.0
        dp = priv.policy.dp
        pads = sess.flat_pad_sums(list(range(Pn)))
        keys = priv.share_keys(range(Pn)) if dp else \
            np.zeros((Pn, 2), np.uint32)

        def shard_fn(Xs, Ds, pad, keyd):
            st = wire.local_stats(Xs, Ds)
            if dp:
                st = priv._noise(st, share,
                                 jax.random.wrap_key_data(keyd[0]))
            return cw.mesh_reduce(cw.device_encode(st, pad[0]), axis)

        in_specs, out_specs = masked_round_specs(self.axis)
        fn = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        with enable_x64():
            if self.warmup:
                # untimed compile pass; it reuses this round's noise
                # keys, which is safe — its output is discarded, never
                # released, and the timed pass redraws nothing (the
                # per-key Gaussian is deterministic)
                jax.block_until_ready(fn(X, D, pads, keys))
            t0 = time.perf_counter()
            with self.trace.span("collective", devices=int(Pn),
                                 masked=True):
                out = fn(X, D, pads, keys)
                jax.block_until_ready(out)
        agg = sess.from_flat(np.asarray(out), frozenset(range(Pn)))
        W = cw.solve(self._release(agg, salt=0), lam)
        jax.block_until_ready(W)
        return W, time.perf_counter() - t0

    def _mesh_masked_host(self, wire, X, D):
        """The masked round when the mesh axis has ONE device: the
        whole dataset is that device's shard, pads are vacuous (a
        single-member session derives no pairs), and the on-device
        limb-encode + psum would cost a full ring program to reduce
        nothing. Run the host secagg path instead — same key stream,
        same session, so ``W`` bit-matches the collective's (tested);
        DESIGN.md §10 documents the crossover."""
        priv, cw, lam = self._priv, self._cw(), self.lam
        template = wire.local_stats(X[:0], D[:0])
        priv.prepare(template)
        if self.warmup:
            jax.block_until_ready(wire.local_stats(X, D))
        t0 = time.perf_counter()
        with self.trace.span("collective", devices=1, masked=True):
            st = wire.local_stats(X, D)
            jax.block_until_ready(st)
            agg = priv.client_encode(0, st)
        with self.trace.span("solve"):
            W = cw.solve(self._release(agg, salt=0), lam)
            jax.block_until_ready(W)
        return W, time.perf_counter() - t0

    def _run_mesh(self, parts_X, parts_d) -> RoundReport:
        # One collective phase: dropout and partitioning apply (only the
        # participants' union enters the solve); late joiners are admitted
        # within the same collective — there is no cheaper "first solve"
        # on a mesh, the round *is* the collective.
        roles = self.scenario.roles(len(parts_X))
        X = jnp.concatenate([jnp.asarray(parts_X[i])
                             for i in roles.participants], axis=0)
        D = jnp.concatenate([parts_d[i] for i in roles.participants],
                            axis=0)
        return self.run_mesh_arrays(X, D, roles=roles)

    def run_mesh_arrays(self, X, D,
                        roles: Optional[ClientRoles] = None) -> RoundReport:
        """Mesh round over already-concatenated data (one client/device).

        With an active privacy policy the devices on the axis are the
        uploading clients (pool size = axis size): under masking each
        device noise-shares (secagg+dp), ring-encodes and pads its own
        statistics *before* the collective, so the psum only ever sees
        ring elements whose interior pads cancel exactly — the decoded
        ``W`` bit-matches the host loop's masked round. Central DP
        reduces plaintext statistics on-device as usual and perturbs
        the replicated aggregate once, host-side, at release.
        """
        mesh = self.mesh or make_client_mesh(axis=self.axis)
        Pn = mesh.shape[self.axis]
        X, D = jnp.asarray(X), as_2d(D)
        priv = self._begin_privacy(Pn)
        if priv is not None:
            priv.cohort = Pn
            if priv.policy.dp:
                # per-row clip before the bias column exists (the loop
                # path clips raw client rows the same way); row-local,
                # so clipping the concatenation is the per-device clip
                X = priv.clip(X)
        n = int(X.shape[0])
        wire = self.wire
        if getattr(wire, "add_bias", None) is True and \
                dataclasses.is_dataclass(wire):
            # pre-add the bias host-side (data-parallel safe) so pad rows
            # can be all-zero including their bias entry — see pad_for_mesh
            X = add_bias(jnp.asarray(X, getattr(wire, "dtype", X.dtype)))
            wire = dataclasses.replace(wire, add_bias=False)
        elif n % Pn and getattr(wire, "add_bias", None) is not False:
            # a custom wire without a toggleable bias column: we cannot
            # guarantee zero-contribution padding, so require divisibility
            # (add_bias=False wires are safe — all-zero pad rows stay
            # all-zero through their local_stats)
            raise ValueError(
                f"{n} samples do not divide the {Pn}-way mesh axis and "
                f"wire {getattr(wire, 'name', wire)!r} has no add_bias "
                "field to make zero-padding exact; pad or trim the data")
        X, D = pad_for_mesh(X, D, Pn, wire.act)
        lam, axis = self.lam, self.axis

        from jax.sharding import NamedSharding, PartitionSpec as P
        # one row shard per device, placed before the collective program
        # runs (it would otherwise start from one device's full copy)
        rows = NamedSharding(mesh, P(axis, None))
        X, D = jax.device_put(X, rows), jax.device_put(D, rows)
        if priv is not None and priv.masked:
            from ..privacy.policy import prefer_host_secagg
            if prefer_host_secagg(Pn):
                # degenerate collective (axis size 1): nothing to psum,
                # so the limb-encode program would be pure overhead —
                # take the host secagg path, which is bit-identical
                # here (crossover documented in DESIGN.md §10)
                W, coordinator_time = self._mesh_masked_host(wire, X, D)
            else:
                W, coordinator_time = self._mesh_masked(
                    mesh, wire, X, D, Pn)
        elif priv is not None and priv.policy.dp:
            # plaintext on-device reduce (noise is central, added once
            # at release): the collective returns the replicated
            # aggregate statistics; noise + accounting + solve happen
            # host-side, inside the timed coordinator phase
            template = wire.local_stats(X[:0], D[:0])
            out_specs = jax.tree_util.tree_map(
                lambda lf: P(*([None] * np.ndim(lf))), template)

            def shard_fn(Xs, Ds):
                return wire.mesh_reduce(wire.local_stats(Xs, Ds), axis)

            fn = jax.shard_map(shard_fn, mesh=mesh,
                               in_specs=(P(self.axis, None),
                                         P(self.axis, None)),
                               out_specs=out_specs, check_vma=False)
            if self.warmup:
                jax.block_until_ready(fn(X, D))
            t0 = time.perf_counter()
            with self.trace.span("collective", devices=int(Pn)):
                agg = fn(X, D)
                jax.block_until_ready(agg)
            with self.trace.span("solve"):
                W = wire.solve(self._release(agg, salt=0), lam)
                jax.block_until_ready(W)
            coordinator_time = time.perf_counter() - t0
        else:
            key = ("mesh", wire, mesh)
            if key not in self._fused_cache:
                def shard_fn(Xs, Ds):
                    st = wire.local_stats(Xs, Ds)
                    return wire.solve(wire.mesh_reduce(st, axis), lam)

                # cached per engine, so a repeated round reuses the
                # compiled collective program
                self._fused_cache[key] = jax.jit(jax.shard_map(
                    shard_fn, mesh=mesh,
                    in_specs=(P(axis, None), P(axis, None)),
                    out_specs=P(None, None), check_vma=False))
            fn = self._fused_cache[key]
            if self.warmup:
                # untimed compile pass at the real shapes, as on the
                # other transports, so the timed collective is
                # steady-state
                jax.block_until_ready(fn(X, D))
            t0 = time.perf_counter()
            with self.trace.span("collective", devices=int(Pn)):
                W = fn(X, D)
                jax.block_until_ready(W)
            coordinator_time = time.perf_counter() - t0
        if roles is None:
            roles = ClientRoles(on_time=tuple(range(Pn)), late=(),
                                dropped=(), delays=(0.0,) * Pn)
        # per-client compute happens inside the collective (it lands in
        # coordinator_time), so measured client compute is zero here; the
        # participants' simulated straggler delays still gate the round
        # via RoundReport.client_clocks — train_time = slowest delay +
        # collective phase, while cpu_time stays pure compute
        client_times = [0.0] * len(roles.participants)
        # on this transport the mesh devices are the uploading clients:
        # wire_bytes counts one upload per device at the true (unpadded)
        # per-device sample count — pad rows are never sent anywhere;
        # under masking the coordinator wire prices the fixed-size ring
        # upload instead of the plaintext statistics
        n_local = -(-n // Pn)
        bytes_wire = self._cw() if (priv is not None and priv.masked) \
            else wire
        wire_bytes = Pn * bytes_wire.stats_bytes(n_local, X.shape[1],
                                                 D.shape[1])
        return RoundReport(W=W, client_times=client_times,
                           coordinator_time=coordinator_time,
                           wire_bytes=wire_bytes, roles=roles,
                           n_samples=n, dispatches=1,
                           # the collective reduces on-device: the host
                           # only ever holds ONE replicated aggregate
                           peak_coordinator_bytes=bytes_wire.stats_bytes(
                               n_local, X.shape[1], D.shape[1]))


class _PeakMeter:
    """Live coordinator wire-stats residency (bytes): ``push`` when an
    aggregate materializes host-side, ``pop`` when the fold consumes
    it; ``peak`` backs ``RoundReport.peak_coordinator_bytes``. Counts
    wire-stats OBJECTS only — stacked client data and XLA transients
    are inputs, not coordinator state (DESIGN.md §11)."""

    def __init__(self):
        self.cur = 0
        self.peak = 0

    def push(self, n: int) -> None:
        self.cur += int(n)
        if self.cur > self.peak:
            self.peak = self.cur

    def pop(self, n: int) -> None:
        self.cur -= int(n)


def _default_revise(X, d, tick: int):
    """Default revision drill: drop the client's oldest quarter.

    Simulates a batched deletion request (the GDPR case the ledger's
    exact downdate exists for); the surviving rows republish as the
    client's new statistics.
    """
    cut = int(X.shape[0]) // 4
    return X[cut:], d[cut:]


def _bucket_bound(n: int) -> int:
    """Power-of-two ceiling of a shard's sample count (0 for empty)."""
    if n <= 0:
        return 0
    b = 1
    while b < n:
        b <<= 1
    return b


def make_client_mesh(n_clients_axis: Optional[int] = None,
                     axis: str = "data"):
    """A 1-D mesh over all local devices for simulated-client sharding."""
    n = n_clients_axis or len(jax.devices())
    return jax.make_mesh((n,), (axis,), axis_types=(AxisType.Auto,))


def pad_for_mesh(X, D, Pn: int, act: str = "logistic"):
    """Zero-pad ``(X, D)`` so the sample axis divides the mesh axis.

    ``X`` must already carry its bias column (the engine pre-adds it and
    runs the wire with ``add_bias=False``): pad rows are then *fully*
    zero — a row whose bias were re-added as 1 would contribute
    ``f'(d̄)²`` to the Gram's bias entries. With the whole row zero, the
    contribution to both wires' statistics is exactly zero: ``m_vec``
    and ``G`` gain zero terms, and the SVD factors only gain zero
    singular directions orthogonal to ``m_vec``. Targets pad with the
    activation midpoint ``f(0)`` so ``f_inv`` stays finite.
    """
    pad = (-X.shape[0]) % Pn
    if not pad:
        return X, D
    mid = acts.get(act).f(jnp.zeros((), dtype=D.dtype))
    X = jnp.concatenate(
        [X, jnp.zeros((pad, X.shape[1]), X.dtype)], axis=0)
    D = jnp.concatenate(
        [D, jnp.full((pad, D.shape[1]), mid, D.dtype)], axis=0)
    return X, D
