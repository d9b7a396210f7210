"""Per-party collectives of the VFL protocol, as jax.lax primitives.

Two aggregation modes (DESIGN.md §2, EXPERIMENTS.md §Perf):

* ``"histogram"`` — paper-faithful: every party ships its full per-shard
  histogram to the active party (Alg. 2 step 7). In SPMD this is an
  ``all_gather`` over the party axis; bytes = nodes * d_party * B * 3 per
  party per level.
* ``"argmax"`` — beyond-paper collective optimisation: each party evaluates
  its local best split and only the (gain, feature, threshold) candidates are
  exchanged; bytes = nodes * 3 per party per level, a ~d_party*B/1
  reduction of the dominant protocol message. Lossless: the global argmax of
  per-party argmaxes equals the argmax of the union (ties broken towards the
  lower party id, matching jnp.argmax's first-occurrence rule on the
  concatenated axis).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import histogram as hist_mod
from repro.core import split as split_mod
from repro.core.types import TreeConfig
from repro.federation import mesh_roles


# Subtraction pipeline (DESIGN.md §6): the federated child providers are the
# generic ``histogram.as_round_child_fn`` adaptation of the providers below —
# the left-mask/parent-halve staging runs INSIDE the shard_map body, before
# the party collective, so the all_gather (and the quantized payload, and the
# meter record) all carry the half-frontier width.  Every party derives the
# right siblings locally after the merge (``tree.build_round`` calls
# ``histogram.derive_sibling`` on the gathered result — in SPMD terms, the
# active party's subtraction, replicated).  ``build_round`` derives the
# adaptation from the inner backend's ``round_histogram_fn`` automatically;
# no dedicated federated child provider is needed.


# ---------------------------------------------------------------------------
# Round-native collectives (DESIGN.md §9): the tree axis is explicit, so the
# per-level party exchange is ONE collective carrying the whole round's
# (T, active, d_party, B, 3) payload instead of a vmap-batched per-tree one.
# ---------------------------------------------------------------------------
def plain_gather(x, party_axis: str, axis: int):
    """The default (synchronous) level exchange: one tiled all_gather."""
    return jax.lax.all_gather(x, party_axis, axis=axis, tiled=True)


def federated_round_histogram_fn(
    party_axis: str = mesh_roles.PARTY_AXIS,
    data_axes: tuple = (),
    base_fn: Callable = hist_mod.compute_round_histogram,
    meter=None,
    gather: Callable = plain_gather,
):
    """Round histogram provider running *inside* shard_map.

    Computes the local-shard round histogram (one segment pass over all T
    trees; shared-root caching rides the ``root_delta_rows`` keyword and
    stays a local compute transformation — the collective payload is
    unchanged), psums over sample shards, then all-gathers the feature axis
    over parties: ONE collective per level for the whole round.

    ``meter`` records the actual payload each party ships — the full local
    float32 (T, nodes, d_party, B, 3) histogram (per-tree bytes × T; the
    probes trace at T = 1, and the run ledger scales by the schedule).

    ``gather`` is the exchange seam (DESIGN.md §10): ``plain_gather`` for
    the synchronous single all_gather, or ``async_exchange
    .double_buffered_gather`` to split the payload into two buffers whose
    transfers overlap.  Either way the meter records the payload ONCE —
    the split is a scheduling detail, not a protocol message.
    """

    def fn(binned_shard, g, h, weight, assign, num_nodes, num_bins,
           root_delta_rows=0, level=0):
        local = base_fn(binned_shard, g, h, weight, assign, num_nodes,
                        num_bins, root_delta_rows=root_delta_rows,
                        level=level)
        with jax.named_scope("fedgbf.exchange"):
            for ax in data_axes:
                local = jax.lax.psum(local, ax)
            if meter is not None:
                meter.record("histograms", local)
            return gather(local, party_axis, 2)

    return fn


def local_round_histogram_fn(
    party_axis: str = mesh_roles.PARTY_AXIS,
    data_axes: tuple = (),
    base_fn: Callable = hist_mod.compute_round_histogram,
):
    """Like ``federated_round_histogram_fn`` but WITHOUT the party
    all-gather — the argmax aggregation keeps histograms party-local."""

    def fn(binned_shard, g, h, weight, assign, num_nodes, num_bins,
           root_delta_rows=0, level=0):
        local = base_fn(binned_shard, g, h, weight, assign, num_nodes,
                        num_bins, root_delta_rows=root_delta_rows,
                        level=level)
        with jax.named_scope("fedgbf.exchange"):
            for ax in data_axes:
                local = jax.lax.psum(local, ax)
        return local

    return fn


def local_round_leaf_fn(data_axes: tuple = ()):
    """Round leaf-statistics provider ((T, n) → (T, leaves, 3)): a local
    pass on the active party (Alg. 2 step 14), psum'd over sample shards.
    Also serves the round engine's compaction liveness counts — weights and
    routing are party-replicated, so no party collective is needed."""

    def fn(g, h, weight, assign, num_leaves):
        local = hist_mod.round_leaf_stats(g, h, weight, assign, num_leaves)
        with jax.named_scope("fedgbf.exchange"):
            for ax in data_axes:
                local = jax.lax.psum(local, ax)
        return local

    return fn


def centralized_round_choose_fn(
    cfg: TreeConfig, party_axis: str = mesh_roles.PARTY_AXIS, meter=None
):
    """Round split chooser for the ``histogram`` mode: the gathered global
    (T, nodes, d, B, 3) histogram is evaluated identically on every party.
    The per-tree feature masks arrive as the (T, d_party) local slice and
    are gathered to match.  ``meter`` records each party's mask payload
    (1 B per local feature per tree)."""

    def fn(hist_global, feature_mask_local):
        if meter is not None:
            meter.record("feature_mask", feature_mask_local)
        with jax.named_scope("fedgbf.exchange"):
            fmask = jax.lax.all_gather(
                feature_mask_local, party_axis, axis=1, tiled=True
            )
        return split_mod.choose_splits_round(hist_global, fmask, cfg)

    return fn


def pack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """Pack a (..., n) 0/1 array into (..., ceil(n/8)) uint8 bitmaps
    (little-endian within each byte).  The id_partition wire format:
    per-level go-right decisions are 1 bit/row, so the routing broadcast
    ships ``ceil(n/8)`` bytes instead of ``4·n`` (int32) — a 32× cut."""
    n = x.shape[-1]
    n_bytes = -(-n // 8)
    pad = [(0, 0)] * (x.ndim - 1) + [(0, n_bytes * 8 - n)]
    bits = jnp.pad(x.astype(jnp.uint8), pad)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(
        bits.reshape(x.shape[:-1] + (n_bytes, 8)) * weights,
        axis=-1, dtype=jnp.uint8,
    )


def unpack_bits(packed: jnp.ndarray, n: int) -> jnp.ndarray:
    """Inverse of ``pack_bits``: (..., ceil(n/8)) uint8 → (..., n) int32."""
    bits = (packed[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & jnp.uint8(1)
    return bits.reshape(packed.shape[:-1] + (-1,))[..., :n].astype(jnp.int32)


def federated_round_route_fn(party_axis: str = mesh_roles.PARTY_AXIS,
                             meter=None):
    """Round ownership-masked routing: the whole round's (T, n) partition
    bitmaps travel in ONE psum per level (Alg. 2 step 3 / SecureBoost
    step 4, batched over the tree axis).

    Wire format: the go-right decisions are BIT-PACKED before the psum —
    each row's splitting feature is owned by exactly one party, so across
    parties every bit position has at most one non-zero contributor and the
    uint8 byte-sum is carry-free (identical to the bitwise OR).  The psum
    operand (and the metered payload) is the ``(T, ceil(n/8))`` bitmap the
    protocol inventory prices (one n-bit bitmap per level), 32× smaller
    than the unpacked int32 vector.
    """

    def fn(binned_shard, assign, decision):
        n, d_party = binned_shard.shape
        p = jax.lax.axis_index(party_axis)
        f_global = jnp.take_along_axis(decision.feature, assign, axis=1)
        thr = jnp.take_along_axis(decision.threshold, assign, axis=1)
        f_local = f_global - p * d_party
        owned = (f_local >= 0) & (f_local < d_party)
        fv = binned_shard[
            jnp.arange(n)[None, :], jnp.clip(f_local, 0, d_party - 1)
        ]  # (T, n)
        go_right_local = jnp.where(
            owned & (f_global >= 0), (fv > thr).astype(jnp.int32), 0
        )
        packed_local = pack_bits(go_right_local)  # (T, ceil(n/8)) uint8
        if meter is not None:
            meter.record("id_partition", packed_local)
        with jax.named_scope("fedgbf.exchange"):
            packed = jax.lax.psum(packed_local, party_axis)  # carry-free == OR
        return assign * 2 + unpack_bits(packed, n)

    return fn
