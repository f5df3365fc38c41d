"""Property suite for the jittable ring algebra (privacy/limbs.py).

The contract under test: the traced int64 limb ops are THE SAME
ℤ_{2^mod_bits} algebra as the host session's numpy encoder —
``encode → add → negate → carry-normalize → decode`` round-trips
bit-match ``SecAggSession``'s encoding across dtypes (f32 w=1280,
f64 w=2176), zero-padding, random pad subsets, and any summation
order. The lazy limbs may *decompose* differently (the device encoder
takes the IEEE bit pattern apart with integer ops to dodge XLA's
f32-subnormal flush-to-zero; the host scatters a frexp mantissa) —
equality is asserted where it is guaranteed: after carry
normalization, and on every decode.

Hypothesis fuzzing engages when the optional dependency is installed;
deterministic cases (including the subnormal/-0.0/extreme-exponent
corners that motivated the bitcast design) always run. The
multi-device mesh pad-cancellation collective needs forced host
devices, so it runs as a slow subprocess test like
tests/test_core_sharded.py.
"""
import os
import subprocess
import sys
import textwrap
from contextlib import nullcontext

import jax
import numpy as np
import pytest
from repro.core.util import enable_x64 as jax_enable_x64

from repro.core import activations as acts
from repro.core.wire import GramWire
from repro.privacy import SecAggSession
from repro.privacy.limbs import (MAX_RING_SUMMANDS, add_limbs,
                                 carry_limbs, check_fleet_headroom,
                                 encode_limbs, encode_tree, negate_limbs,
                                 require_x64, sum_limbs)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # optional dependency (pip install hypothesis)
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="optional dependency: property fuzzing "
    "needs hypothesis (pip install hypothesis)")

# the float corners the bitcast encoder exists for: f32 subnormals
# (flushed to zero by XLA's in-jit widening cast), signed zeros, the
# extreme normal exponents, and values whose mantissa spans 3 limbs
_CORNERS32 = np.array(
    [0.0, -0.0, 1.0, -1.0, 1e-45, -1e-45, 1.1754942e-38, -2.94e-39,
     1.17549435e-38, 3.4028235e38, -3.4028235e38, 0.1, -37.5,
     1.5e-44, 6.0e-39, 2.0 ** -126, -(2.0 ** -149)], np.float32)
_CORNERS64 = np.array(
    [0.0, -0.0, 1.0, -1.0, 5e-324, -5e-324, 2.2250738585072014e-308,
     1.7976931348623157e308, -1.7976931348623157e308, 0.1, -37.5,
     2.0 ** -1022, -(2.0 ** -1074), 1e-310], np.float64)


def _sess_for(arr_tree, dtype, P=4, seed=0):
    sess = SecAggSession(P, seed=seed, dtype=dtype)
    sess._bind(arr_tree)
    return sess


def _host_carried(sess, tree):
    enc = sess.encode(tree)
    flat = np.concatenate([l.reshape(-1, sess.words) for l in enc.limbs])
    return sess._carry(flat)


def _device_carried(sess, tree):
    with jax_enable_x64():
        flat = carry_limbs(encode_tree(tree, sess.words))
    return np.asarray(flat)


def _ctx(dtype):
    return jax_enable_x64() if dtype == np.float64 else nullcontext()


# ------------------------------------------------- encode equivalence
@pytest.mark.parametrize("dtype,corners", [(np.float32, _CORNERS32),
                                           (np.float64, _CORNERS64)])
def test_jitted_encode_bitmatches_host_on_corners(dtype, corners):
    """The FTZ corners: device carried limbs ≡ host carried limbs,
    and the decode round-trips every value bit-for-bit."""
    with _ctx(dtype):
        tree = (corners.copy(),)
        sess = _sess_for(tree, dtype)
        host = _host_carried(sess, tree)
        dev = _device_carried(sess, tree)
        assert np.array_equal(host, dev), \
            f"carried limbs diverge at rows {np.argwhere((host != dev).any(1))}"
        back = sess.decode(sess.from_flat(dev, frozenset((0,))))
        assert np.array_equal(np.asarray(back[0]), corners)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_jitted_encode_bitmatches_host_on_wire_stats(dtype):
    """Real GramStats trees (multi-leaf, multi-shape) encode
    identically on both paths."""
    rng = np.random.default_rng(3)
    with _ctx(dtype):
        wire = GramWire(dtype=dtype)
        X = (rng.normal(size=(17, 6)) * 40).astype(dtype)
        D = np.asarray(acts.encode_labels(rng.integers(0, 2, 17), 2),
                       dtype)
        stats = wire.local_stats(X, D)
        sess = _sess_for(stats, dtype)
        assert np.array_equal(_host_carried(sess, stats),
                              _device_carried(sess, stats))


def test_add_negate_roundtrip_is_exact_zero():
    """a ⊕ (⊖a) carry-normalizes to all-zero limbs — exact ring
    inverse, no residue."""
    rng = np.random.default_rng(1)
    tree = (rng.normal(size=(9, 4)).astype(np.float32) * 123,)
    sess = _sess_for(tree, np.float32)
    with jax_enable_x64():
        enc = encode_tree(tree, sess.words)
        out = np.asarray(carry_limbs(add_limbs(enc, negate_limbs(enc))))
    assert not out.any()


def test_ring_sum_order_independent_and_decodes_exact_sum():
    """Any summation order/grouping of P encodes (sequential fold,
    pairwise tree, stacked sum — the psum shape) yields the SAME
    carried limbs, and the decode equals the host's exact sum."""
    rng = np.random.default_rng(2)
    P = 6
    trees = [(rng.normal(size=(5, 3)).astype(np.float32) * 10 ** p,)
             for p in range(-3, 3)]
    sess = _sess_for(trees[0], np.float32, P=P)
    with jax_enable_x64():
        encs = [encode_tree(t, sess.words) for t in trees]
        stacked = np.stack([np.asarray(e) for e in encs])
        ref = np.asarray(carry_limbs(sum_limbs(stacked)))
        for perm in (range(P), reversed(range(P)),
                     np.random.default_rng(0).permutation(P)):
            perm = list(perm)
            acc = encs[perm[0]]
            for i in perm[1:]:
                acc = add_limbs(acc, encs[i])
            assert np.array_equal(np.asarray(carry_limbs(acc)), ref)
        # pairwise tree grouping (psum's reduction shape)
        t01 = add_limbs(encs[0], encs[1])
        t23 = add_limbs(encs[2], encs[3])
        t45 = add_limbs(encs[4], encs[5])
        tree_sum = add_limbs(add_limbs(t01, t23), t45)
        assert np.array_equal(np.asarray(carry_limbs(tree_sum)), ref)
    # the decoded ring sum == the host session's exact masked sum
    ups = [sess.mask_upload(p, trees[p]) for p in range(P)]
    agg = ups[0]
    for u in ups[1:]:
        agg = sess.merge_signed(agg, u)
    host_sum = sess.unmask(agg)
    dev_sum = sess.decode(sess.from_flat(ref, frozenset(range(P))))
    assert np.array_equal(np.asarray(dev_sum[0]), np.asarray(host_sum[0]))


@pytest.mark.parametrize("subset_seed", range(4))
def test_random_pad_subsets_cancel_on_device(subset_seed):
    """flat_pad_sums rows for a random participant subset, ring-summed
    on device with the subset's encodes, decode to exactly the
    subset's sum once the boundary pads are recovered host-side."""
    rng = np.random.default_rng(subset_seed)
    P = 5
    wire = GramWire()
    stats, sess = [], None
    for p in range(P):
        X = rng.normal(size=(6 + p, 3)).astype(np.float32)
        D = np.asarray(acts.encode_labels(
            rng.integers(0, 2, X.shape[0]), 2), np.float32)
        stats.append(wire.local_stats(X, D))
    sess = _sess_for(stats[0], np.float32, P=P, seed=subset_seed)
    sess._ensure_pad_sums()
    S = sorted(rng.choice(P, size=rng.integers(1, P + 1),
                          replace=False).tolist())
    pads = sess.flat_pad_sums(S)
    with jax_enable_x64():
        enc = np.stack([np.asarray(encode_tree(stats[i], sess.words))
                        for i in S])
        masked = add_limbs(enc, pads)
        agg = np.asarray(carry_limbs(sum_limbs(masked)))
    got = sess.unmask(sess.from_flat(agg, frozenset(S)))
    # host reference: the same subset masked and merged host-side
    ups = [sess.mask_upload(i, stats[i]) for i in S]
    ref_agg = ups[0]
    for u in ups[1:]:
        ref_agg = sess.merge_signed(ref_agg, u)
    ref = sess.unmask(ref_agg)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), f"subset {S}"


# --------------------------------------------------------- guard rails
def test_limb_ops_require_x64():
    with pytest.raises(RuntimeError, match="enable_x64"):
        require_x64()
    with pytest.raises(RuntimeError, match="int64"):
        encode_limbs(np.ones(3, np.float32), 40)
    with pytest.raises(RuntimeError, match="int64"):
        carry_limbs(np.zeros((3, 40), np.int64))
    with jax_enable_x64():
        require_x64()               # no raise inside the context


def test_fleet_headroom_guard():
    check_fleet_headroom(MAX_RING_SUMMANDS)
    with pytest.raises(ValueError, match="headroom"):
        check_fleet_headroom(MAX_RING_SUMMANDS + 1)


def test_encode_tree_shapes_and_empty():
    with jax_enable_x64():
        with pytest.raises(ValueError, match="empty"):
            encode_tree((), 40)
        flat = encode_tree((np.ones((2, 3), np.float32),
                            np.ones(4, np.float32)), 40)
        assert flat.shape == (10, 40)
        stacked = encode_tree((np.ones((5, 2, 3), np.float32),
                               np.ones((5, 4), np.float32)), 40,
                              stacked=True)
        assert stacked.shape == (5, 10, 40)


# ------------------------------------------------------- hypothesis fuzz
if HAVE_HYPOTHESIS:
    @needs_hypothesis
    @settings(max_examples=40, deadline=None)
    @given(st.lists(st.floats(width=32, allow_nan=False,
                              allow_infinity=False),
                    min_size=1, max_size=60),
           st.integers(0, 2 ** 16))
    def test_fuzz_encode_f32_bitmatches_host(vals, seed):
        tree = (np.asarray(vals, np.float32),)
        sess = _sess_for(tree, np.float32, seed=seed)
        assert np.array_equal(_host_carried(sess, tree),
                              _device_carried(sess, tree))
        back = sess.decode(sess.from_flat(
            _device_carried(sess, tree), frozenset((0,))))
        assert np.array_equal(np.asarray(back[0]), tree[0])

    @needs_hypothesis
    @settings(max_examples=25, deadline=None)
    @given(st.lists(st.floats(allow_nan=False, allow_infinity=False),
                    min_size=1, max_size=40),
           st.integers(0, 2 ** 16))
    def test_fuzz_encode_f64_bitmatches_host(vals, seed):
        with jax_enable_x64():
            tree = (np.asarray(vals, np.float64),)
            sess = _sess_for(tree, np.float64, seed=seed)
            assert np.array_equal(_host_carried(sess, tree),
                                  _device_carried(sess, tree))

    @needs_hypothesis
    @pytest.mark.slow          # heaviest fuzz: P encodes × permutations
    @settings(max_examples=20, deadline=None)
    @given(st.integers(2, 8), st.integers(1, 20),
           st.integers(0, 2 ** 16), st.data())
    def test_fuzz_ring_sum_permutation_invariance(P, n, seed, data):
        rng = np.random.default_rng(seed)
        trees = [(rng.normal(size=(n,)).astype(np.float32)
                  * 10.0 ** rng.integers(-6, 6),) for _ in range(P)]
        sess = _sess_for(trees[0], np.float32, P=P, seed=seed)
        with jax_enable_x64():
            encs = [np.asarray(encode_tree(t, sess.words))
                    for t in trees]
            ref = np.asarray(carry_limbs(sum_limbs(np.stack(encs))))
            perm = data.draw(st.permutations(range(P)))
            acc = encs[perm[0]]
            for i in perm[1:]:
                acc = add_limbs(acc, encs[i])
            assert np.array_equal(np.asarray(carry_limbs(acc)), ref)


# ------------------------------------- multi-device mesh collective
_MESH_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import activations as acts
    from repro.core.engine import (FederationEngine, make_client_mesh,
                                   pad_for_mesh)
    from repro.core.util import add_bias
    from repro.core.wire import GramWire
    from repro.privacy import SecAggSession

    assert len(jax.devices()) == 4
    rng = np.random.default_rng(0)
    n, m, c, Pn = 103, 7, 2, 4          # 103 % 4 != 0: pad rows in play
    X = rng.normal(size=(n, m)).astype(np.float32)
    D = np.asarray(acts.encode_labels(rng.integers(0, c, n), c))

    eng = FederationEngine("gram", transport="mesh", privacy="secagg",
                           mesh=make_client_mesh(4))
    parts = np.array_split(np.arange(n), 4)
    rep = eng.run([X[ix] for ix in parts], [D[ix] for ix in parts])
    assert rep.privacy["mode"] == "secagg"

    # host reference over the SAME device shards: bias pre-added,
    # zero-padded, add_bias=False wire — each device masked host-side,
    # interior pads cancelling in the host ring merge
    wire = dataclasses.replace(GramWire(), add_bias=False)
    Xb = np.asarray(add_bias(jnp.asarray(X)))
    Xp, Dp = pad_for_mesh(Xb, D, Pn, wire.act)
    sess = SecAggSession(Pn, seed=eng.privacy.seed)
    rows = len(Xp) // Pn
    agg = None
    for dev in range(Pn):
        sh = slice(dev * rows, (dev + 1) * rows)
        up = sess.mask_upload(dev, wire.local_stats(Xp[sh], Dp[sh]))
        agg = up if agg is None else sess.merge_signed(agg, up)
    W_ref = wire.solve(sess.unmask(agg), eng.lam)
    assert np.array_equal(np.asarray(rep.W), np.asarray(W_ref)), \\
        "4-device masked psum diverged from the host ring merge"
    print("MESH-MASKED-OK")
""")


@pytest.mark.slow
def test_mesh_masked_collective_multidevice_bitmatch():
    """4 forced host devices: the on-device limb psum (interior pads
    cancelling inside the collective) bit-matches the host-side masked
    merge over the same shards — subprocess, since device count is
    fixed at jax init."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         env.get("PYTHONPATH", "")])
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr
    assert "MESH-MASKED-OK" in out.stdout
