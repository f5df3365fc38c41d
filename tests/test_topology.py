"""Hierarchical aggregation suite (ISSUE 7).

* ``Topology.parse`` / ``Scenario.parse`` reject malformed specs naming
  the offending token (the PR 4 error grammar),
* ``TierTree`` construction, capacity, partition validation, and the
  depth-first streaming ``fold`` (one open aggregate per tier),
* **re-tiering exactness**: a tiered gram-wire round bit-matches the
  flat ``merge_many``/one-tier solve for random tree shapes and
  fanouts — including dropout of a *whole* edge aggregator — because
  tier merges are order-independent integer-ring adds (deterministic
  seeded versions always run; hypothesis fuzzes shapes when installed),
* masked tiers (secagg) decode to the bitwise-same W as unmasked exact
  tiers: interior pads cancel per-tier, boundary pads re-derive at the
  root,
* the stream-transport tiered fold bit-equals the ledger's
  ``ExactAccumulator`` over the same per-client statistics,
* the svd wire rides the float codec: allclose-through-solve parity,
* ``RoundReport.peak_coordinator_bytes`` ≤ fanout·agg_bytes and flat
  in P,
* the latency model: deterministic re-simulation, byte accounting,
  LAN-discounted client links,
* the mesh seam (ISSUE 7 satellite): at axis size 1 the masked mesh
  round takes the host secagg path (``prefer_host_secagg``) and solves
  bitwise-identically to the forced collective.
"""
from contextlib import nullcontext

import numpy as np
import pytest

from repro.core import activations as acts
from repro.core.engine import FederationEngine
from repro.core.ledger import ExactAccumulator, FederationLedger
from repro.core.scenario import Scenario
from repro.core.topology import ExactFold, TierTree, Topology, \
    simulate_round
from repro.core.wire import get_wire
from repro.data import partition, synthetic

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dependency (pip install hypothesis)
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="optional dependency: property fuzzing "
    "needs hypothesis (pip install hypothesis)")


def _parts(P=12, n=600, m=10, seed=1):
    spec = synthetic.DatasetSpec("toy", n, m, 2)
    X, y = synthetic.generate(spec, seed=seed)
    parts = partition.iid(X, y, P, seed=seed)
    return ([p[0] for p in parts],
            [np.asarray(acts.encode_labels(p[1], 2)) for p in parts])


def _run(pX, pD, topology, wire="gram", transport="local", **kw):
    eng = FederationEngine(wire=wire, transport=transport,
                           topology=topology, **kw)
    return eng.run(pX, pD)


# ------------------------------------------------------------- parsing
def test_parse_defaults_and_none():
    assert Topology.parse(None) is None
    assert Topology.parse("") is None
    assert Topology.parse("none") is None
    t = Topology.parse("fanout=64,tiers=3")
    assert (t.fanout, t.tiers) == (64, 3)
    assert t.capacity == 64 ** 3
    assert Topology.parse(t) is t            # idempotent


def test_parse_names_offending_token():
    with pytest.raises(ValueError, match="fanout=x"):
        Topology.parse("fanout=x")
    with pytest.raises(ValueError, match="bad topology item 'fanoot=4'"):
        Topology.parse("fanoot=4")
    with pytest.raises(ValueError, match="topology item 'tiers'"):
        Topology.parse("tiers")


@pytest.mark.parametrize("spec, token", [
    ("fanout=1", "fanout=1"),                 # fanout < 2
    ("fanout=99999", "fanout=99999"),         # > lazy-carry headroom
    ("tiers=0", "tiers=0"),
    ("rtt=-1", "rtt=-1"),
    ("bw=0", "bw=0"),
    ("jitter=1.5", "jitter=1.5"),
    ("lan_factor=0", "lan_factor=0"),
    ("exact=maybe", "exact=maybe"),
])
def test_parse_rejects_out_of_range(spec, token):
    # no closing quote: float tokens echo canonicalized ('rtt=-1.0')
    with pytest.raises(ValueError, match=f"bad topology item '{token}"):
        Topology.parse(spec)


def test_scenario_parse_rejects_topology_keys():
    # topology keys are not availability keys — the error must say which
    # token broke, not silently accept a misplaced spec
    with pytest.raises(ValueError, match="bad scenario item 'fanout=64'"):
        Scenario.parse("dropout=0.1,fanout=64")
    with pytest.raises(ValueError, match="'tiers=3'"):
        Scenario.parse("tiers=3")


# ------------------------------------------------------------ tier tree
def test_tree_build_shapes():
    t = TierTree.build(13, fanout=4, tiers=3)
    assert t.n_clients == 13 and t.n_edges == 4 and t.tiers == 3
    assert t.levels[0][0] == (0, 1, 2, 3) and t.levels[0][3] == (12,)
    assert len(t.levels[-1]) == 1            # single root group
    assert t.max_group == 4
    assert t.n_aggregators == 4 + 1 + 1
    assert t.edge_of(12) == 3
    with pytest.raises(ValueError, match="not in the tree"):
        t.edge_of(13)


def test_tree_capacity_error():
    with pytest.raises(ValueError, match="exceed the fanout=4, tiers=2"):
        TierTree.build(17, fanout=4, tiers=2)
    TierTree.build(16, fanout=4, tiers=2)    # boundary fits


def test_tree_validate_rejects_bad_partition():
    with pytest.raises(ValueError, match="single root"):
        TierTree(levels=((tuple(), tuple()),)).validate()
    # tier 1 must partition the tier-0 nodes exactly
    with pytest.raises(ValueError, match="tier 1 groups must partition"):
        TierTree(levels=(((0, 1), (2, 3)), ((0, 0),))).validate()


def test_fold_streams_one_open_aggregate_per_tier():
    t = TierTree.build(8, fanout=2, tiers=3)
    live, peak = [0], [0]

    def leaf(e, ids):
        live[0] += 1
        peak[0] = max(peak[0], live[0])
        return sum(ids)

    def merge(level, acc, sub):
        live[0] -= 1                         # two aggregates become one
        return acc + sub

    assert t.fold(leaf, merge) == sum(range(8))
    # depth-first: never more than one open aggregate per level
    assert peak[0] <= t.tiers


def test_fold_skips_empty_edges():
    t = TierTree.build(8, fanout=2, tiers=3)
    # edges 0 and 1 entirely empty (a dropped edge aggregator)
    out = t.fold(lambda e, ids: None if e < 2 else sum(ids),
                 lambda level, acc, sub: acc + sub)
    assert out == sum(range(4, 8))
    assert t.fold(lambda e, ids: None, lambda l, a, s: a + s) is None


# ----------------------------------------------------------- ExactFold
def test_exactfold_codec_roundtrip_and_order_independence():
    wire = get_wire("gram")
    pX, pD = _parts(P=4, n=200)
    stats = [wire.local_stats(x, d) for x, d in zip(pX, pD)]
    folder = ExactFold(wire, stats[0])
    encs = [folder.encode(s) for s in stats]
    fwd = bwd = folder.zero()
    for e in encs:
        fwd = folder.add(fwd, e)
    for e in reversed(encs):
        bwd = folder.add(bwd, e)
    assert np.array_equal(fwd, bwd)          # ring adds commute bitwise
    # decode matches the ledger's exact flat fold bit for bit
    acc = ExactAccumulator(stats[0])
    for s in stats:
        acc.add(s)
    dec, ref = folder.decode(fwd), acc.snapshot()
    for a, b in zip((dec.G, dec.m_vec, dec.n), (ref.G, ref.m_vec, ref.n)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # wire limbs are uint32 (4 B); the resident work array is int64
    assert folder.agg_bytes * 2 == folder.zero().nbytes


# ------------------------------------------------- re-tiering exactness
def _assert_retier_bitmatch(P, fanout, tiers, seed=0, scenario=None):
    pX, pD = _parts(P=P, seed=seed)
    kw = {"scenario": scenario} if scenario else {}
    r = _run(pX, pD, f"fanout={fanout},tiers={tiers}", **kw)
    r_flat = _run(pX, pD, f"fanout={max(P, 2)},tiers=1", **kw)
    assert r.hierarchy["mode"] == "exact"
    assert np.array_equal(np.asarray(r.W), np.asarray(r_flat.W))
    return r, r_flat


@pytest.mark.parametrize("P, fanout, tiers", [
    (12, 4, 2), (16, 4, 2), (13, 2, 4), (9, 3, 3)])
def test_tiered_bitmatches_flat_solve(P, fanout, tiers):
    _assert_retier_bitmatch(P, fanout, tiers)


def test_tiered_bitmatches_flat_under_dropout_and_late_join():
    sc = Scenario(dropout=0.3, late_join=0.2, seed=4)
    r, r_flat = _assert_retier_bitmatch(12, 4, 2, scenario=sc)
    # the pre-admission model is exact too
    assert np.array_equal(np.asarray(r.W_first), np.asarray(r_flat.W_first))


def test_tiered_survives_whole_edge_dropout():
    """All of edge group 1 dropped: its leaf returns None and the fold
    must still bit-match the flat solve over the survivors."""
    from repro.core.scenario import ClientRoles
    P, fanout = 12, 4
    dropped = tuple(range(fanout, 2 * fanout))      # exactly edge 1
    roles = ClientRoles(
        on_time=tuple(i for i in range(P) if i not in dropped),
        late=(), dropped=dropped, delays=(0.0,) * P)
    pX, pD = _parts(P=P)
    keep = [i for i in range(P) if i not in dropped]

    class FixedScenario(Scenario):
        def roles(self, n, seed=None):
            return roles

    fixed = FixedScenario(seed=0)
    r = _run(pX, pD, f"fanout={fanout},tiers=2", scenario=fixed)
    r_flat = _run(pX, pD, f"fanout={P},tiers=1", scenario=fixed)
    assert np.array_equal(np.asarray(r.W), np.asarray(r_flat.W))
    wire = get_wire("gram")
    acc = ExactAccumulator(wire.local_stats(pX[keep[0]], pD[keep[0]]))
    for i in keep:
        acc.add(wire.local_stats(pX[i], pD[i]))
    W_ref = wire.solve(acc.snapshot(), 1e-3)
    assert np.array_equal(np.asarray(r.W), np.asarray(W_ref))


if HAVE_HYPOTHESIS:

    @needs_hypothesis
    @settings(max_examples=12, deadline=None)
    @given(P=st.integers(3, 20), fanout=st.integers(2, 6),
           extra_tiers=st.integers(0, 2), seed=st.integers(0, 5))
    def test_property_retier_bitmatch_random_trees(P, fanout,
                                                   extra_tiers, seed):
        import math
        tiers = max(1, math.ceil(math.log(P, fanout))) + extra_tiers
        _assert_retier_bitmatch(P, fanout, tiers, seed=seed)


# -------------------------------------------------------- masked tiers
def test_masked_tiers_bitmatch_exact_tiers():
    pX, pD = _parts(P=9)
    r_exact = _run(pX, pD, "fanout=3,tiers=2")
    r_masked = _run(pX, pD, "fanout=3,tiers=2", privacy="secagg")
    assert r_masked.hierarchy["mode"] == "masked"
    assert np.array_equal(np.asarray(r_masked.W), np.asarray(r_exact.W))


def test_masked_tiers_bitmatch_under_dropout():
    sc = Scenario(dropout=0.25, late_join=0.25, seed=7)
    pX, pD = _parts(P=8)
    r_exact = _run(pX, pD, "fanout=4,tiers=2", scenario=sc)
    r_masked = _run(pX, pD, "fanout=4,tiers=2", scenario=sc,
                    privacy="secagg")
    assert np.array_equal(np.asarray(r_masked.W), np.asarray(r_exact.W))
    assert np.array_equal(np.asarray(r_masked.W_first),
                          np.asarray(r_exact.W_first))


# ---------------------------------------------------- stream transport
def test_stream_tiers_bitmatch_exact_accumulator():
    """Stream tiers fold per-client stats — with chunks=1 those are the
    same digits the ledger's flat ExactAccumulator folds, so W
    bit-matches it (chunks>1 changes the *client* digits, not the
    tiering: see the re-tiering test below)."""
    pX, pD = _parts(P=10)
    r = _run(pX, pD, "fanout=4,tiers=2", transport="stream", chunks=1)
    wire = get_wire("gram")
    acc = ExactAccumulator(wire.local_stats(pX[0], pD[0]))
    for x, d in zip(pX, pD):
        acc.add(wire.local_stats(x, d))
    W_ref = wire.solve(acc.snapshot(), 1e-3)
    assert np.array_equal(np.asarray(r.W), np.asarray(W_ref))


def test_stream_tiers_retier_bitmatch_chunked():
    """Chunk-folded client digits re-tier exactly too."""
    pX, pD = _parts(P=10)
    kw = dict(transport="stream", chunks=3)
    r = _run(pX, pD, "fanout=4,tiers=2", **kw)
    r_flat = _run(pX, pD, "fanout=10,tiers=1", **kw)
    assert np.array_equal(np.asarray(r.W), np.asarray(r_flat.W))


# -------------------------------------------------------- float codec
def test_svd_wire_rides_float_codec():
    pX, pD = _parts(P=9)
    r = _run(pX, pD, "fanout=3,tiers=2", wire="svd")
    assert r.hierarchy["mode"] == "float"
    r_flat = FederationEngine(wire="svd").run(pX, pD)
    np.testing.assert_allclose(np.asarray(r.W), np.asarray(r_flat.W),
                               rtol=1e-4, atol=1e-5)


def test_exact_off_forces_float_and_on_rejects_svd():
    pX, pD = _parts(P=6)
    r = _run(pX, pD, "fanout=3,tiers=2,exact=off")
    assert r.hierarchy["mode"] == "float"
    with pytest.raises(ValueError, match="svd"):
        _run(pX, pD, "fanout=3,tiers=2,exact=on", wire="svd")


# ------------------------------------------------------ peak residency
def test_peak_flat_in_P_and_under_bound():
    peaks = []
    for P in (8, 16, 32):
        pX, pD = _parts(P=P, n=40 * P)
        r = _run(pX, pD, "fanout=4,tiers=3")
        h = r.hierarchy
        assert r.peak_coordinator_bytes <= h["peak_bound_bytes"]
        assert h["peak_bound_bytes"] == h["fanout"] * h["agg_bytes"]
        peaks.append(r.peak_coordinator_bytes)
    # O(tiers·fanout·agg_bytes), NOT O(P): 4× the clients, same peak
    assert max(peaks) <= 2 * min(peaks)


# ------------------------------------------------------- latency model
def test_simulate_round_deterministic_and_byte_accounting():
    topo = Topology(fanout=2, tiers=2, rtt=0.1, bw=1e4, jitter=0.5,
                    seed=3)
    tree = topo.tree(4)
    kw = dict(client_ready={i: 0.01 * i for i in range(4)},
              client_bytes={i: 1000 for i in range(4)},
              agg_bytes=5000, merge_cost=0.001, j_per_byte=1e-6)
    a, b = simulate_round(tree, topo, **kw), simulate_round(tree, topo,
                                                            **kw)
    assert a == b                            # jitter is seeded per link
    # tier links: 2 edge→root uploads of agg_bytes; clients on the LAN
    assert a["bytes_flat"] == 4 * 1000
    assert a["bytes_tiered"] == 4 * 1000 + 2 * 5000
    # LAN pricing: client bytes at lan_factor of the WAN J/byte
    lan_j = 4 * 1000 * 1e-6 * topo.lan_factor
    assert a["uplink_j_tiered"] == pytest.approx(lan_j + 2 * 5000 * 1e-6)
    assert a["uplink_j_flat"] == pytest.approx(4 * 1000 * 1e-6)
    assert a["n_participants"] == 4 and a["n_aggregators"] == 3


def test_simulate_round_flat_serializes_single_link():
    """The flat coordinator's ingest is serialized over ONE link — the
    bottleneck the hierarchy shards; at scale tiered must win."""
    topo = Topology(fanout=8, tiers=2, rtt=0.01, bw=1e5)
    P = 64
    tree = topo.tree(P)
    out = simulate_round(
        tree, topo, client_ready={i: 0.0 for i in range(P)},
        client_bytes={i: 10_000 for i in range(P)}, agg_bytes=10_000)
    assert out["sim_wall_tiered"] < out["sim_wall_flat"]


def test_link_jitter_deterministic_and_lan_tier():
    topo = Topology(fanout=4, tiers=2, jitter=0.3, seed=9)
    assert topo.link(1, 0, 2) == topo.link(1, 0, 2)
    assert topo.link(1, 0, 2) != topo.link(1, 0, 3)
    rtt0, bw0, jf0 = topo.link(0, 0, 1)
    rtt1, bw1, jf1 = topo.link(1, 0, 1)
    assert rtt0 < rtt1 and bw0 > bw1 and jf0 < jf1


def test_engine_rejects_overflowing_tree():
    pX, pD = _parts(P=10)
    with pytest.raises(ValueError, match="exceed the fanout=2, tiers=2"):
        _run(pX, pD, "fanout=2,tiers=2")


# ------------------------------------------------------------ mesh seam
def test_mesh_tiers_bitmatch_local_tiers():
    """Sibling edge groups sharded across the device axis produce the
    same ring digits as the local per-bucket programs."""
    pX, pD = _parts(P=12)
    r_mesh = _run(pX, pD, "fanout=4,tiers=2", transport="mesh")
    r_local = _run(pX, pD, "fanout=4,tiers=2")
    assert np.array_equal(np.asarray(r_mesh.W), np.asarray(r_local.W))


def test_mesh_axis1_masked_takes_host_path_bitexactly(monkeypatch):
    """ISSUE 7 satellite: at mesh axis size 1 the limb-encode collective
    buys nothing — the engine must fall back to the host secagg path,
    and the fallback must solve bitwise-identically to the collective
    it replaces (DESIGN.md §10 crossover)."""
    from repro.privacy import policy as pol
    assert pol.prefer_host_secagg(1) and pol.prefer_host_secagg(0)
    assert not pol.prefer_host_secagg(2)

    pX, pD = _parts(P=4)
    eng = lambda: FederationEngine(wire="gram", transport="mesh",
                                   privacy="secagg")
    r_host = eng().run(pX, pD)               # axis size 1 on CPU → host
    monkeypatch.setattr(pol, "prefer_host_secagg", lambda n: False)
    r_coll = eng().run(pX, pD)               # forced limb collective
    assert np.array_equal(np.asarray(r_host.W), np.asarray(r_coll.W))
    assert r_host.peak_coordinator_bytes == r_coll.peak_coordinator_bytes


# --------------------------------------------- satellite: streaming API
def test_merge_stream_is_left_fold():
    wire = get_wire("gram")
    pX, pD = _parts(P=5)
    stats = [wire.local_stats(x, d) for x, d in zip(pX, pD)]
    agg = wire.merge_stream(iter(stats))
    ref = stats[0]
    for s in stats[1:]:
        ref = wire.merge(ref, s)
    assert np.array_equal(np.asarray(agg.G), np.asarray(ref.G))
    assert wire.merge_stream(iter(())) is None


def test_ledger_resident_bytes_counts_registry():
    wire = get_wire("gram")
    pX, pD = _parts(P=4)
    ledger = FederationLedger(wire, lam=1e-3)
    assert ledger.resident_bytes() == 0
    for i, (x, d) in enumerate(zip(pX, pD)):
        ledger.join(i, wire.local_stats(x, d))
    per = wire.wire_bytes(next(iter(ledger.registry.values())))
    assert ledger.resident_bytes() >= 4 * per


# ------------------------------------------- FEMNIST's shape, float tiers
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_float_tiers_at_62_classes_match_float64_reference(backend):
    """LEAF FEMNIST's shape at a test size: 40 writers of LEAF's size law
    (lognormal, mean 226.83, sd 88.94 rows), 62 classes, m = 17 with the
    bias, behind fanout-8 edge aggregators, two tiers, the float fold
    (``exact=off``). On Pallas every edge bucket is one client-folding
    kernel pass; ``W`` matches the plain float64 eq.-3 solve."""
    from repro.launch import smoke
    rng = np.random.default_rng(62)
    sigma2 = np.log1p((88.94 / 226.83) ** 2)
    sizes = np.maximum(np.rint(rng.lognormal(
        np.log(226.83) - sigma2 / 2, np.sqrt(sigma2), 40)), 1).astype(int)
    X, y = synthetic.generate(
        synthetic.DatasetSpec("femnist-shape", int(sizes.sum()), 16, 62),
        seed=62)
    cuts = np.cumsum(sizes)[:-1]
    pX = np.split(X, cuts)
    pD = [np.asarray(acts.encode_labels(yy, 62)) for yy in np.split(y, cuts)]
    eng = FederationEngine(wire="gram", backend=backend,
                           topology="fanout=8,tiers=2,exact=off")
    rep = eng.run(pX, pD)
    assert rep.hierarchy["mode"] == "float"
    assert rep.hierarchy["n_groups"] == 5
    W64 = smoke.reference_solve(X, y, c=62, lam=eng.lam)
    W = np.asarray(rep.W, np.float64)
    # float32 statistics of 8,774 rows against float64: 1e-7 (Pallas) to
    # 3e-6 (XLA) on the CPU; 1e-4 leaves over an order of room and still
    # fails the round with one writer left out (1.4e-3)
    assert np.linalg.norm(W - W64) / np.linalg.norm(W64) <= 1e-4
