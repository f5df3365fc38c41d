"""Hierarchical aggregation topology: edge → regional → global tiers.

The flat coordinator materializes every participant's statistics before
folding — O(P·c·m²) resident bytes, the memory wall that caps the
engine near P≈10³. But the merge algebra is associative (and, on the
gram wire, *exact* over the dyadic-integer ring of PRs 4–6), so the
fold can be re-bracketed into a tree of aggregators with NO change to
the solved ``W``:

* **edge** aggregators (tier 0) each fold ≤ ``fanout`` clients through
  the fleet-batched pow2-bucket fused program — one dispatch per shape
  bucket, per-client statistics never materialize host-side,
* **regional / global** tiers fold ≤ ``fanout`` child aggregates each,
  streamingly: at any instant the coordinator process holds one open
  aggregate per tier plus the group being folded — O(tiers·c·m²)
  resident, *flat in P* (``RoundReport.peak_coordinator_bytes`` is the
  measured number, asserted ≤ fanout·agg_bytes in the hierarchy bench).

Three fold codecs, chosen by wire × privacy (DESIGN.md §11):

* **exact** (gram, default): tiers exchange ring elements of the exact
  dyadic-integer encoding (``privacy/limbs.py``) — integer adds are
  order-independent, so the tiered solve is **bit-identical** to the
  flat exact fold (the ledger's ``ExactAccumulator`` / secagg decode),
  for any tree shape and any dropout pattern,
* **masked** (secagg modes): each edge runs the masked fused program;
  tier merges are ring adds under which *interior* pads cancel
  per-tier, and the *boundary* pads of the final participant set are
  re-derived once at the tier root (``SecAggSession.unmask``),
* **float** (svd wire, or ``exact=off``): plain ``Wire.merge`` up the
  tree — associative to rounding, parity with the flat fold is
  allclose-through-solve, not bitwise (the Iwen–Ong merge has no exact
  integer encoding).

:class:`Topology` also carries a simulated **latency model** (per-link
RTT + bandwidth, client→edge links on a cheaper LAN/short-radio tier,
aggregator links on the WAN) so the hierarchy's wall-clock and
uplink-joule win over the flat coordinator is *measured* per round
(``RoundReport.hierarchy``), not assumed — the cross-device regime of
Green Federated Learning (Yousefpour et al.) and *Can Federated
Learning Save The Planet?* (Qiu et al.).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .scenario import parse_kv_fields

# largest group any tier may ring-sum in one lazy int64 pass — mirrors
# privacy.limbs.MAX_RING_SUMMANDS without importing the privacy package
# at module load (privacy imports core)
_MAX_FANOUT = 1 << 14

EXACT_MODES = ("auto", "on", "off")


@dataclasses.dataclass(frozen=True)
class Topology:
    """A tier tree plus its link model, ``Scenario``-style parseable.

    ``fanout``     — max children per aggregator (clients per edge),
    ``tiers``      — aggregator levels (1 = the flat coordinator;
                     3 = edge → regional → global). Capacity is
                     ``fanout**tiers`` clients,
    ``rtt``        — WAN round-trip latency per aggregator link (s),
    ``bw``         — WAN uplink bandwidth per link (bytes/s),
    ``jitter``     — relative per-link RTT jitter in [0, 1], drawn
                     deterministically per (seed, link),
    ``lan_factor`` — client→edge links are local: RTT × lan_factor,
                     bandwidth / lan_factor, J/byte × lan_factor
                     (an edge aggregator is *near* its clients — the
                     whole point of placing it there),
    ``exact``      — ``auto`` folds through the exact dyadic-integer
                     ring whenever the wire has a secagg encoding
                     (bit-identical re-tiering), ``on`` requires it,
                     ``off`` forces the float fold.
    """
    fanout: int = 64
    tiers: int = 3
    rtt: float = 0.05
    bw: float = 1e6
    jitter: float = 0.0
    lan_factor: float = 0.1
    seed: int = 0
    exact: str = "auto"

    def __post_init__(self):
        def bad(key, why):
            raise ValueError(
                f"bad topology item '{key}={getattr(self, key)}': {why}")
        if self.fanout < 2:
            bad("fanout", "an aggregator needs fanout >= 2")
        if self.fanout > _MAX_FANOUT:
            bad("fanout", f"fanout beyond {_MAX_FANOUT} exceeds the "
                "int64 lazy-carry ring headroom of one tier's fold")
        if self.tiers < 1:
            bad("tiers", "need at least one aggregation tier")
        if self.rtt < 0:
            bad("rtt", "rtt must be >= 0 seconds")
        if not self.bw > 0:
            bad("bw", "bw must be > 0 bytes/s")
        if not 0.0 <= self.jitter <= 1.0:
            bad("jitter", "jitter must be in [0, 1]")
        if not self.lan_factor > 0:
            bad("lan_factor", "lan_factor must be > 0")
        if self.exact not in EXACT_MODES:
            bad("exact", f"expected one of {EXACT_MODES}")

    @property
    def capacity(self) -> int:
        return self.fanout ** self.tiers

    @classmethod
    def parse(cls, spec) -> Optional["Topology"]:
        """``"fanout=64,tiers=3,rtt=0.05"`` → Topology; ``None``/``""``/
        ``"none"`` → ``None`` (flat coordinator — no hierarchy).
        Malformed items raise ``ValueError`` quoting the token
        (:func:`~.scenario.parse_kv_fields` — the PR 4 error grammar).
        """
        if spec is None or isinstance(spec, cls):
            return spec
        kw = parse_kv_fields(cls, spec, "topology")
        return cls(**kw) if kw or (spec and
                                   spec.strip().lower() != "none") \
            else None

    def tree(self, P: int) -> "TierTree":
        return TierTree.build(P, self.fanout, self.tiers)

    # ------------------------------------------------------ link model
    def link(self, level: int, parent: int, child: int
             ) -> Tuple[float, float, float]:
        """One uplink's ``(rtt_s, bytes_per_s, j_per_byte_factor)``.

        ``level`` 0 is a client→edge link (LAN/short-radio tier);
        higher levels are aggregator→aggregator WAN links. Jitter is
        deterministic per (seed, level, parent, child) so a round and
        its re-simulation agree exactly.
        """
        scale = 1.0
        if self.jitter:
            rng = np.random.default_rng(
                (self.seed, level, parent, child))
            scale = 1.0 + self.jitter * rng.random()
        if level == 0:
            return (self.rtt * self.lan_factor * scale,
                    self.bw / self.lan_factor, self.lan_factor)
        return (self.rtt * scale, self.bw, 1.0)


@dataclasses.dataclass(frozen=True)
class TierTree:
    """The concrete tree for one fleet: who folds whom.

    ``levels[0]`` is a tuple of edge groups (tuples of client ids);
    ``levels[k>0]`` groups child-aggregator indices of level ``k−1``.
    The top level is a single root group. ``build`` chunks contiguously
    (deployment would group by network proximity); tests exercise
    arbitrary groupings via the constructor + :meth:`validate`.
    """
    levels: Tuple[Tuple[Tuple[int, ...], ...], ...]

    @classmethod
    def build(cls, P: int, fanout: int, tiers: int) -> "TierTree":
        if P < 1:
            raise ValueError("tier tree needs at least one client")
        if P > fanout ** tiers:
            raise ValueError(
                f"{P} clients exceed the fanout={fanout}, tiers={tiers} "
                f"tree capacity of {fanout ** tiers}; raise fanout or "
                "add a tier")
        ids = list(range(P))
        levels = [tuple(tuple(ids[i:i + fanout])
                        for i in range(0, P, fanout))]
        for _ in range(1, tiers):
            prev = len(levels[-1])
            levels.append(tuple(
                tuple(range(i, min(i + fanout, prev)))
                for i in range(0, prev, fanout)))
        tree = cls(levels=tuple(levels))
        tree.validate()
        return tree

    def validate(self) -> None:
        if not self.levels or len(self.levels[-1]) != 1:
            raise ValueError("tier tree needs a single root group")
        for k in range(1, len(self.levels)):
            flat = [c for grp in self.levels[k] for c in grp]
            if sorted(flat) != list(range(len(self.levels[k - 1]))):
                raise ValueError(
                    f"tier {k} groups must partition the "
                    f"{len(self.levels[k - 1])} tier-{k - 1} nodes")

    # ------------------------------------------------------ properties
    @property
    def tiers(self) -> int:
        return len(self.levels)

    @property
    def n_clients(self) -> int:
        return sum(len(g) for g in self.levels[0])

    @property
    def n_edges(self) -> int:
        return len(self.levels[0])

    @property
    def max_group(self) -> int:
        """Largest fold any single aggregator performs (≤ fanout)."""
        return max(len(g) for lvl in self.levels for g in lvl)

    @property
    def n_aggregators(self) -> int:
        return sum(len(lvl) for lvl in self.levels)

    def edge_of(self, cid: int) -> int:
        for e, grp in enumerate(self.levels[0]):
            if cid in grp:
                return e
        raise ValueError(f"client {cid} is not in the tree")

    # ------------------------------------------------------- streaming
    def fold(self, leaf: Callable, merge: Callable):
        """Stream the tree bottom-up, one open aggregate per tier.

        ``leaf(edge_idx, client_ids) -> agg | None`` folds one edge
        group (None = no participant in the group — e.g. a whole edge
        aggregator dropped); ``merge(level, acc, sub) -> agg`` folds a
        completed child into its parent's open aggregate. Children are
        visited depth-first in tree order, so at any instant at most
        one aggregate per level is live — the O(tiers·agg_bytes)
        residency the hierarchy bench meters. Returns the root
        aggregate (None when every edge came back empty).
        """
        return self._fold_node(self.tiers - 1, 0, leaf, merge)

    def _fold_node(self, level, idx, leaf, merge):
        # not a recursive closure: that cycle would pin leaf/merge (the engine)
        if level == 0:
            return leaf(idx, self.levels[0][idx])
        acc = None
        for child in self.levels[level][idx]:
            sub = self._fold_node(level - 1, child, leaf, merge)
            if sub is not None:
                acc = sub if acc is None else merge(level, acc, sub)
        return acc


# --------------------------------------------------------------- failover
def failover(tree: TierTree, tier: int, group: int
             ) -> Tuple[TierTree, int]:
    """Reassign a failed aggregator's children to a sibling.

    ``aggfail@tier{tier}:g{group}`` recovery: the dead aggregator's
    group empties (an empty group folds to ``None``, which
    :meth:`TierTree.fold` already skips — no parent index remapping)
    and its children are adopted by the adjacent sibling at the same
    tier, which re-folds them. Because the exact codec's tier adds are
    order-independent integer ring sums, the re-tiered fold decodes to
    the bit-identical aggregate (PR 7's re-tiering invariance); the
    masked codec's boundary-pad recovery depends only on the
    participant id set, which failover never changes.

    Returns ``(new_tree, n_children_moved)`` — the move count prices
    the re-folded uplinks in :func:`simulate_round`.
    """
    if not 0 <= tier < tree.tiers:
        raise ValueError(
            f"aggfail@tier{tier}:g{group}: the tree has tiers "
            f"0..{tree.tiers - 1}")
    level = tree.levels[tier]
    if not 0 <= group < len(level):
        raise ValueError(
            f"aggfail@tier{tier}:g{group}: tier {tier} has groups "
            f"0..{len(level) - 1}")
    if len(level) < 2:
        raise ValueError(
            f"aggfail@tier{tier}:g{group}: the aggregator has no "
            "sibling at its tier to adopt its children (a dead root "
            "means restarting the round)")
    sibling = group + 1 if group + 1 < len(level) else group - 1
    moved = level[group]
    new_level = list(level)
    new_level[group] = ()
    new_level[sibling] = tuple(new_level[sibling]) + tuple(moved)
    levels = list(tree.levels)
    levels[tier] = tuple(new_level)
    new_tree = TierTree(levels=tuple(levels))
    new_tree.validate()
    return new_tree, len(moved)


# ------------------------------------------------------------ exact fold
class ExactFold:
    """Tier-exchange codec for the exact dyadic-integer group fold.

    Edge aggregators emit ``(n_elems, words)`` int64 limb arrays — the
    jitted ``fleet_stats → encode → ring-sum → carry`` program's output
    (the unmasked twin of the engine's masked fused program). Tier
    merges are lazy int64 limb adds (:meth:`add`, carry-normalized only
    when headroom runs low), and the root decodes ONCE back to the wire
    dtypes — operation for operation the ledger's
    ``ExactAccumulator.snapshot``, so the tiered aggregate bit-equals
    the flat exact fold of the same participants regardless of tree
    shape. Reuses :class:`~..privacy.secagg.SecAggSession`'s template/
    carry/decode machinery with a single-client session (no pads).
    """

    def __init__(self, wire, template):
        import jax
        from ..privacy.secagg import SecAggSession
        self._wire = wire
        self._session = SecAggSession(
            1, dtype=getattr(wire, "dtype", np.float32))
        encoded = wire.secagg_encode(template)
        self._session._bind(encoded)
        self._n_elems = sum(
            int(np.prod(np.shape(lf)))
            for lf in jax.tree_util.tree_leaves(encoded))

    @property
    def words(self) -> int:
        return self._session.words

    @property
    def agg_bytes(self) -> int:
        """Wire size of one tier-to-tier ring aggregate."""
        return self._session.upload_bytes

    def zero(self) -> np.ndarray:
        """The additive identity — what an all-empty subtree folds to."""
        return np.zeros((self._n_elems, self.words), np.int64)

    def encode(self, stats) -> np.ndarray:
        """One client's statistics → its ring element, host-side (the
        stream transport's per-client path; an edge bucket program
        emits the identical digits fused)."""
        from ..privacy import limbs as _limbs
        from .util import enable_x64
        with enable_x64():
            enc = _limbs.encode_tree(self._wire.secagg_encode(stats),
                                     self.words)
            return np.asarray(enc)

    def add(self, a: np.ndarray, b: np.ndarray) -> np.ndarray:
        return self._session._maybe_carry(a + b)

    def decode(self, flat: np.ndarray):
        """Ring aggregate → stats pytree in the template dtypes."""
        return self._session.unmask(self._session.from_flat(
            np.asarray(flat, np.int64), frozenset((0,))))


# --------------------------------------------------------- latency model
def simulate_round(tree: TierTree, topo: Topology, *,
                   client_ready: Dict[int, float],
                   client_bytes: Dict[int, int],
                   agg_bytes: int, merge_cost: float = 0.0,
                   j_per_byte: float = 2e-7,
                   retries: Optional[Dict[int, int]] = None,
                   refolds: int = 0) -> dict:
    """Simulated wall-clock + uplink joules: tiered vs flat, same round.

    ``client_ready`` maps each participant to the second its statistics
    are ready (measured compute + scenario delay); ``client_bytes`` to
    its upload size. Each aggregator's ingest is serialized over its
    own uplink (Σ bytes/bw after the slowest child's arrival — the
    single-receiver bottleneck the hierarchy exists to shard), plus
    ``merge_cost`` per child folded. The flat coordinator ingests every
    client over ONE WAN link; the tiered coordinator ingests ``fanout``
    aggregates, with client uploads on the cheap LAN tier. Joules price
    every uplink byte through the Savazzi-style J/byte radio model
    (LAN bytes at ``lan_factor`` of the WAN rate).

    ``retries`` maps a client to its count of *extra* upload attempts
    (fault plan retry/timeout): each resends the full upload over the
    client's own link, so its edge ingests (1 + retries) × bytes and
    the duplicate bytes are priced into the joule totals —
    retransmission is pure energy cost, the fault model's headline
    number. ``refolds`` counts child aggregates re-sent to a sibling
    after a tier-aggregator failover, each one more WAN agg uplink.
    The retry/refold surcharge is reported separately
    (``retry_bytes``/``retry_j``) as well as folded into the totals.
    """
    retries = retries or {}
    j = {"tiered": 0.0, "flat": 0.0, "retry": 0.0}
    b = {"tiered": 0, "flat": 0, "retry": 0}

    def edge_ready(e):
        ids = [i for i in tree.levels[0][e] if i in client_ready]
        if not ids:
            return None
        arrive, ingest = 0.0, 0.0
        for i in ids:
            rtt, bw, jf = topo.link(0, e, i)
            sends = 1 + retries.get(i, 0)
            arrive = max(arrive, client_ready[i] + rtt)
            ingest += sends * client_bytes[i] / bw
            j["tiered"] += sends * client_bytes[i] * j_per_byte * jf
            b["tiered"] += sends * client_bytes[i]
            if sends > 1:
                extra = (sends - 1) * client_bytes[i]
                j["retry"] += extra * j_per_byte * jf
                b["retry"] += extra
        return arrive + ingest + len(ids) * merge_cost

    def node_ready(level, idx):
        if level == 0:
            return edge_ready(idx)
        arrive, ingest, n = 0.0, 0.0, 0
        for child in tree.levels[level][idx]:
            sub = node_ready(level - 1, child)
            if sub is None:
                continue
            rtt, bw, jf = topo.link(level, idx, child)
            arrive = max(arrive, sub + rtt)
            ingest += agg_bytes / bw
            j["tiered"] += agg_bytes * j_per_byte * jf
            b["tiered"] += agg_bytes
            n += 1
        return arrive + ingest + n * merge_cost if n else None

    tiered = node_ready(tree.tiers - 1, 0)
    if tiered is not None and refolds:
        # failover re-folds: each moved child's aggregate is re-sent
        # over one more WAN uplink into the adopting sibling
        extra = refolds * agg_bytes
        tiered += refolds * (agg_bytes / topo.bw + merge_cost)
        j["tiered"] += extra * j_per_byte
        b["tiered"] += extra
        j["retry"] += extra * j_per_byte
        b["retry"] += extra
    # flat baseline: every client on its own WAN link into ONE receiver
    # (retried uploads resend over the same WAN link)
    arrive, ingest = 0.0, 0.0
    for i, t in client_ready.items():
        rtt, bw, _ = topo.link(1, 0, i)
        sends = 1 + retries.get(i, 0)
        arrive = max(arrive, t + rtt)
        ingest += sends * client_bytes[i] / bw
        j["flat"] += sends * client_bytes[i] * j_per_byte
        b["flat"] += sends * client_bytes[i]
    flat = arrive + ingest + len(client_ready) * merge_cost \
        if client_ready else None
    # pure-Python scalars only: this dict lands verbatim in
    # RoundReport.hierarchy and the BENCH JSON, and numpy byte counts
    # passed in via client_bytes would otherwise propagate into the
    # sums (JSON-safety contract, tested via RoundReport.to_dict)
    return {
        "sim_wall_tiered": None if tiered is None else float(tiered),
        "sim_wall_flat": None if flat is None else float(flat),
        "uplink_j_tiered": float(j["tiered"]),
        "uplink_j_flat": float(j["flat"]),
        "bytes_tiered": int(b["tiered"]), "bytes_flat": int(b["flat"]),
        "retry_bytes": int(b["retry"]), "retry_j": float(j["retry"]),
        "n_participants": len(client_ready),
        "n_aggregators": int(tree.n_aggregators),
    }
