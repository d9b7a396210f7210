"""Row-sharding invariants (DESIGN.md §8) + the bit-packed routing wire.

The data-axis psum is exact because histograms, leaf statistics and
shared-root deltas are all plain sums over rows — any partition of the
sample axis, even and uneven alike, must reproduce the single-host values.
The checks here assert that *bit-identically*: inputs are drawn from an
exact-representable float grid (small multiples of a power of two, bounded
counts), so every partial sum is exact in float32 and the shard
decomposition cannot perturb a single bit regardless of association order.
The federated twin of these checks (real shard_map programs over a
(data, model) mesh) lives in federation/selftest.py.

Each invariant runs two ways: a deterministic parametrized sweep over shard
counts {1, 2, 4}, uneven splits and GOSS weight masks (always on, so the
tier-1 suite covers the contract even without hypothesis), and a hypothesis
property over the same space when the package is installed.

The id_partition bit-packing (federation/aggregator.py) rides along: the
pack/unpack round-trip, the carry-free psum-equals-OR property under
disjoint party ownership, and the shard-aware wire-model arithmetic.  So
does the federated round route: on a CPU party mesh (a subprocess with
forced host devices) it matches the per-row gather formulation bit for bit
and meters the same id_partition bytes.
"""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import histogram as hist_mod
from repro.federation import aggregator, protocol

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # tier-1 container has no hypothesis; sweeps still run
    HAVE_HYPOTHESIS = False

needs_hypothesis = pytest.mark.skipif(
    not HAVE_HYPOTHESIS, reason="hypothesis not installed"
)
SETTINGS = dict(max_examples=20, deadline=None)

#: weight grid: {0, 1} plain masks plus GOSS-style power-of-two
#: amplification factors — exact under float32 multiplication.
GOSS_WEIGHTS = np.array([0.0, 0.5, 1.0, 2.0, 4.0], np.float32)

#: deterministic sweep over the property space: (shards, goss, seed)
SWEEP = [(1, False, 0), (2, False, 1), (2, True, 2), (4, False, 3),
         (4, True, 4)]


def _exact_case(rng, n, d, T, B, goss):
    """Inputs on an exact float grid: g, h are multiples of 1/8 in
    [-16, 16], weights are powers of two (or 0/1 masks) — all partial
    float32 sums over <= a few hundred rows are exact, so summation
    order is provably irrelevant and equality checks can be bitwise."""
    binned = jnp.asarray(rng.integers(0, B, (n, d)), jnp.int32)
    g = jnp.asarray(rng.integers(-128, 129, n) / 8.0, jnp.float32)
    h = jnp.asarray(rng.integers(0, 129, n) / 8.0, jnp.float32)
    if goss:
        w = jnp.asarray(rng.choice(GOSS_WEIGHTS, (T, n)))
    else:
        w = jnp.asarray(rng.integers(0, 2, (T, n)), jnp.float32)
    return binned, g, h, w


def _uneven_bounds(rng, n, shards):
    """Random shard boundaries — deliberately uneven, no empty shards."""
    if shards == 1:
        return [0, n]
    cuts = np.sort(rng.choice(np.arange(1, n), size=shards - 1, replace=False))
    return [0, *cuts.tolist(), n]


def _check_sharded_histogram(n, d, T, nodes, shards, goss, seed):
    """Sum of per-shard round histograms == the single-host histogram,
    BIT-identical, for any shard count and uneven row split — the invariant
    the data-axis psum in the sharded backends relies on."""
    rng = np.random.default_rng(seed)
    B = 8
    binned, g, h, w = _exact_case(rng, n, d, T, B, goss)
    assign = jnp.asarray(rng.integers(0, nodes, (T, n)), jnp.int32)
    full = hist_mod.compute_round_histogram(binned, g, h, w, assign, nodes, B)
    bounds = _uneven_bounds(rng, n, shards)
    acc = jnp.zeros_like(full)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        acc = acc + hist_mod.compute_round_histogram(
            binned[lo:hi], g[lo:hi], h[lo:hi], w[:, lo:hi],
            assign[:, lo:hi], nodes, B,
        )
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(full))


def _check_sharded_leaf_stats(n, T, leaves, shards, goss, seed):
    """Per-shard leaf statistics (and with them the compaction liveness
    counts, which are the same reduction) psum to the single-host values
    bit-identically."""
    rng = np.random.default_rng(seed)
    _, g, h, w = _exact_case(rng, n, 1, T, 8, goss)
    assign = jnp.asarray(rng.integers(0, leaves, (T, n)), jnp.int32)
    full = hist_mod.round_leaf_stats(g, h, w, assign, leaves)
    bounds = _uneven_bounds(rng, n, shards)
    acc = jnp.zeros_like(full)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        acc = acc + hist_mod.round_leaf_stats(
            g[lo:hi], h[lo:hi], w[:, lo:hi], assign[:, lo:hi], leaves
        )
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(full))


def _check_sharded_root_delta(n, d, T, shards, seed):
    """The shared-root delta path (shared − masked-out delta, DESIGN.md §9)
    decomposes over row shards bit-identically: each shard's
    ``shared_s − delta_s`` covers exactly its local masked-out rows (the
    static budget bounds any shard's count), so the psum equals the
    single-host delta-path histogram."""
    rng = np.random.default_rng(seed)
    B = 8
    binned, g, h, w = _exact_case(rng, n, d, T, B, goss=False)
    zeros = jnp.zeros((T, n), jnp.int32)
    # budget = n covers every shard's masked-out rows (surplus slots are
    # weight-0 inert), mirroring boosting's n-capped delta budget
    full = hist_mod.compute_round_histogram(
        binned, g, h, w, zeros, 1, B, root_delta_rows=n
    )
    bounds = _uneven_bounds(rng, n, shards)
    acc = jnp.zeros_like(full)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        acc = acc + hist_mod.compute_round_histogram(
            binned[lo:hi], g[lo:hi], h[lo:hi], w[:, lo:hi],
            zeros[:, lo:hi], 1, B, root_delta_rows=n,
        )
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(full))


def _check_pack_roundtrip(n, T, seed):
    rng = np.random.default_rng(seed)
    x = rng.integers(0, 2, (T, n)).astype(np.int32)
    packed = aggregator.pack_bits(jnp.asarray(x))
    assert packed.dtype == jnp.uint8
    assert packed.shape == (T, -(-n // 8))
    np.testing.assert_array_equal(
        np.asarray(aggregator.unpack_bits(packed, n)), x
    )


def _check_pack_psum_is_or(n, parties, seed):
    """Each row's go-right bit has exactly one owning party, so the uint8
    byte-sum across parties equals the bitwise OR (no carries) — the
    property that lets the routing psum run on packed bitmaps."""
    rng = np.random.default_rng(seed)
    owner = rng.integers(0, parties, n)
    bits = rng.integers(0, 2, n).astype(np.int32)
    per_party = [
        jnp.asarray(np.where(owner == p, bits, 0)[None, :])
        for p in range(parties)
    ]
    packed_sum = sum(aggregator.pack_bits(x) for x in per_party)
    np.testing.assert_array_equal(
        np.asarray(packed_sum),
        np.asarray(aggregator.pack_bits(jnp.asarray(bits[None, :]))),
    )
    np.testing.assert_array_equal(
        np.asarray(aggregator.unpack_bits(packed_sum, n))[0], bits
    )


def _check_wire_arithmetic(n, shards, depth):
    """The wire model's id_partition term: ``shards`` per-shard bitmaps of
    ``ceil(n_shard/8)`` bytes each per level, with rows padded to the shard
    granularity — brute-force cross-check of the ceil arithmetic."""
    phases = protocol.wire_party_tree_cost(
        n, 2, 8, depth, "histogram", data_shards=shards
    )
    n_shard = -(-n // shards)
    per_level = shards * ((n_shard + 7) // 8)
    assert phases["id_partition"] == depth * per_level
    # the padded total never undercounts the unsharded bitmap, and the
    # byte overhead of sharding is < 1 byte per shard per level
    unsharded = protocol.wire_party_tree_cost(n, 2, 8, depth, "histogram")
    assert phases["id_partition"] >= unsharded["id_partition"]
    assert phases["id_partition"] - unsharded["id_partition"] <= depth * shards


ROUTE_PROBE = r"""
import json, sys
import jax, jax.numpy as jnp, numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import split
from repro.federation import aggregator, compress
from repro.launch.mesh import make_vfl_mesh

def per_row_gather_route_fn(meter):
    # the element-wise formulation: each (tree, row) gathers its node's
    # global feature and threshold, then its bin on the owning party
    def fn(binned_shard, assign, decision):
        n, d_party = binned_shard.shape
        p = jax.lax.axis_index("model")
        f_global = jnp.take_along_axis(decision.feature, assign, axis=1)
        thr = jnp.take_along_axis(decision.threshold, assign, axis=1)
        f_local = f_global - p * d_party
        owned = (f_local >= 0) & (f_local < d_party)
        fv = binned_shard[
            jnp.arange(n)[None, :], jnp.clip(f_local, 0, d_party - 1)]
        bits = jnp.where(owned & (f_global >= 0),
                         (fv > thr).astype(jnp.int32), 0)
        packed_local = aggregator.pack_bits(bits)
        meter.record("id_partition", packed_local)
        packed = jax.lax.psum(packed_local, "model")
        return assign * 2 + aggregator.unpack_bits(packed, n)
    return fn

def run(route_fn, mesh, binned, assign, dec):
    body = lambda b, a, f, t: route_fn(
        b, a, split.SplitDecision(feature=f, threshold=t, gain=None))
    spec = P(None, "data")
    prog = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("data", "model"), spec, P(), P()),
        out_specs=spec, check_vma=False))
    return np.asarray(prog(binned, assign, dec.feature, dec.threshold))

parties, shards = map(int, sys.argv[1:3])
mesh = make_vfl_mesh(parties, shards)
rng = np.random.default_rng(parties * 10 + shards)
B, d_party, n = 32, 5, 202
d = parties * d_party
out = []
for T, width in [(1, 1), (2, 2), (5, 4), (3, 64)]:
    binned = rng.integers(0, B, (n, d))
    binned[: n // 8] = B - 1
    binned[n // 8: n // 4] = 0
    feature = rng.integers(0, d, (T, width))   # either party's features
    threshold = rng.integers(0, B, (T, width))
    threshold.flat[::2] = rng.choice([0, B - 1], threshold.flat[::2].shape)
    unsplit = rng.random((T, width)) < 1 / 3
    feature[unsplit], threshold[unsplit] = -1, B
    dec = split.SplitDecision(feature=jnp.asarray(feature, jnp.int32),
                              threshold=jnp.asarray(threshold, jnp.int32),
                              gain=None)
    binned = jnp.asarray(binned, jnp.int32)
    assign = jnp.asarray(rng.integers(0, width, (T, n)), jnp.int32)
    m_new, m_old = compress.MessageMeter(), compress.MessageMeter()
    new = run(aggregator.federated_round_route_fn(meter=m_new), mesh,
              binned, assign, dec)
    old = run(per_row_gather_route_fn(m_old), mesh, binned, assign, dec)
    owners = {int(f) // d_party for f in feature.flat if f >= 0}
    out.append({"T": T, "width": width, "equal": bool((new == old).all()),
                "meter_new": m_new.entries, "meter_old": m_old.entries,
                "owners": sorted(owners), "unsplit": int(unsplit.sum()),
                "right": int((new % 2).sum())})
print(json.dumps(out))
"""


def _federated_route_cases(parties, shards):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={parties * shards}")
    env["PYTHONPATH"] = os.path.join(repo, "src")
    proc = subprocess.run(
        [sys.executable, "-c", ROUTE_PROBE, str(parties), str(shards)],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# deterministic sweeps — always run (tier-1 container has no hypothesis)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shards,goss,seed", SWEEP)
def test_sharded_round_histogram_bit_identical(shards, goss, seed):
    _check_sharded_histogram(n=357, d=5, T=3, nodes=4, shards=shards,
                             goss=goss, seed=seed)


@pytest.mark.parametrize("shards,goss,seed", SWEEP)
def test_sharded_leaf_stats_bit_identical(shards, goss, seed):
    _check_sharded_leaf_stats(n=301, T=3, leaves=8, shards=shards,
                              goss=goss, seed=seed)


@pytest.mark.parametrize("shards,seed", [(1, 0), (2, 1), (4, 2)])
def test_sharded_shared_root_delta_bit_identical(shards, seed):
    _check_sharded_root_delta(n=203, d=3, T=4, shards=shards, seed=seed)


@pytest.mark.parametrize("n", [1, 7, 8, 9, 64, 101])
def test_pack_bits_roundtrip(n):
    _check_pack_roundtrip(n=n, T=4, seed=n)


@pytest.mark.parametrize("parties,seed", [(2, 0), (4, 1)])
def test_pack_bits_psum_is_carry_free(parties, seed):
    _check_pack_psum_is_or(n=131, parties=parties, seed=seed)


@pytest.mark.parametrize("n,shards", [(1, 1), (701, 2), (1536, 4), (999, 8)])
def test_wire_id_partition_shard_arithmetic(n, shards):
    _check_wire_arithmetic(n=n, shards=shards, depth=3)


@pytest.mark.parametrize("parties,shards", [(2, 1), (2, 2)])
def test_federated_route_matches_per_row_gather(parties, shards):
    """The federated round route (row compares over each party's own
    columns) gives the per-row gather formulation's next indices bit for
    bit, with nodes split on either party's features and unsplit nodes,
    and meters the same id_partition payload per level."""
    for case in _federated_route_cases(parties, shards):
        assert case["equal"], case
        assert case["meter_new"] == case["meter_old"], case
        assert len(case["meter_new"]) == 1, case  # one bitmap per level
        # the case exercised both parties' features and unsplit nodes
        if case["width"] >= 4:
            assert case["owners"] == list(range(parties)), case
            assert case["unsplit"] > 0 and case["right"] > 0, case


# ---------------------------------------------------------------------------
# hypothesis properties — same invariants over the drawn space
# ---------------------------------------------------------------------------
if HAVE_HYPOTHESIS:

    @needs_hypothesis
    @settings(**SETTINGS)
    @given(n=st.integers(32, 400), d=st.integers(1, 6),
           T=st.sampled_from([1, 3]), nodes=st.sampled_from([1, 2, 4]),
           shards=st.sampled_from([1, 2, 4]), goss=st.booleans(),
           seed=st.integers(0, 2**16))
    def test_prop_sharded_histogram(n, d, T, nodes, shards, goss, seed):
        _check_sharded_histogram(n, d, T, nodes, shards, goss, seed)

    @needs_hypothesis
    @settings(**SETTINGS)
    @given(n=st.integers(32, 400), T=st.sampled_from([1, 3]),
           leaves=st.sampled_from([2, 4, 8]),
           shards=st.sampled_from([1, 2, 4]), goss=st.booleans(),
           seed=st.integers(0, 2**16))
    def test_prop_sharded_leaf_stats(n, T, leaves, shards, goss, seed):
        _check_sharded_leaf_stats(n, T, leaves, shards, goss, seed)

    @needs_hypothesis
    @settings(**SETTINGS)
    @given(n=st.integers(32, 300), d=st.integers(1, 4),
           T=st.sampled_from([2, 4]), shards=st.sampled_from([1, 2, 4]),
           seed=st.integers(0, 2**16))
    def test_prop_sharded_root_delta(n, d, T, shards, seed):
        _check_sharded_root_delta(n, d, T, shards, seed)

    @needs_hypothesis
    @settings(**SETTINGS)
    @given(n=st.integers(1, 200), T=st.sampled_from([1, 4]),
           seed=st.integers(0, 2**16))
    def test_prop_pack_roundtrip(n, T, seed):
        _check_pack_roundtrip(n, T, seed)

    @needs_hypothesis
    @settings(**SETTINGS)
    @given(n=st.integers(8, 200), parties=st.sampled_from([2, 4]),
           seed=st.integers(0, 2**16))
    def test_prop_pack_psum_is_or(n, parties, seed):
        _check_pack_psum_is_or(n, parties, seed)

    @needs_hypothesis
    @settings(**SETTINGS)
    @given(n=st.integers(1, 5000), shards=st.sampled_from([1, 2, 4, 8]),
           depth=st.integers(1, 5))
    def test_prop_wire_arithmetic(n, shards, depth):
        _check_wire_arithmetic(n, shards, depth)
