"""Message compression for the VFL transport (DESIGN.md §5).

The paper's pitch is cutting SecureBoost's "high interactive communication
costs"; this module supplies the two standard levers SecureBoost+ applies to
the dominant protocol message (the per-level histogram exchange) plus the
measurement plumbing that makes every saving verifiable:

* **Quantized histogram exchange** (``TransportSpec(kind="quantized")``):
  each party quantizes its local (g, h) histogram channels to int8/int16
  with one float32 scale per (node, feature, channel) and ships the integer
  payload + scales instead of full-precision float32 triples.  Rounding is
  stochastic (unbiased) by default.  The count channel is *not* shipped —
  split search (``core.split.split_gains``) reads only the g/h channels, and
  leaf statistics are computed locally by the active party (Alg. 2 step 14)
  — so the dequantized global histogram carries a zero count channel.
  Bytes per (node, feature): ``B·2·bits/8 + 2·4`` vs ``B·3·4`` raw — 5.3×
  smaller for int8 at B = 32.

* **Top-k candidate pruning** (``TransportSpec(kind="topk")``): the argmax
  aggregation generalized — each party ships its k best (gain, feature,
  threshold) tuples per node instead of exactly one.  k = 1 *is* the argmax
  mode; any k ≥ 1 stays lossless for split selection (every party's own best
  is in its top-k, and the party-major merge order reproduces the
  centralized first-occurrence tie-break), so the knob buys headroom for
  gain-perturbing transports (quantized gains, DP noise) at k·12 bytes per
  node per party — still ~d·B/k smaller than the histogram exchange.

* **MessageMeter / probe_tree_cost**: every party-axis collective in
  ``federation/aggregator.py`` (and this module) reports the *actual* payload
  it ships — size × itemsize of the traced operand — into an optional meter.
  ``probe_tree_cost`` abstractly evaluates a backend's real forest program
  (``jax.eval_shape``, no FLOPs) with a fresh meter and returns measured
  bytes per tree, which ``federation.protocol`` reconciles against the
  predicted wire model (``ProtocolLedger``).  Measuring the traced program
  rather than re-deriving formulas is the point: any drift between the
  implementation and the cost model shows up as a reconciliation mismatch.

GOSS sample subsampling — the third SecureBoost+ lever — is a sampling-mask
policy, not a transport, and lives in ``core/forest.py``
(``goss_masks_from_keys``) gated by ``FedGBFConfig.sampling``.

Sibling subtraction (``TreeConfig.hist_subtraction``, DESIGN.md §6) is a
*pipeline* lever orthogonal to all of the above: levels >= 1 exchange only
the left-child histograms (``histogram.as_child_fn`` adapts every provider
here and in aggregator.py, so quantized payloads halve too) and the ledger's
wire model halves the per-level node count to match — the reconciliation
contract stays exact either way.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import histogram as hist_mod
from repro.core import split as split_mod
from repro.core.types import TreeConfig
from repro.federation import mesh_roles

#: histogram stat channels that traverse the wire under quantization for a
#: SCALAR (K = 1) objective — split search needs only (sum_g, sum_h); the
#: count channel stays local.  K-channel objectives ship 2K wire channels
#: (the providers slice ``[..., :-1]``: everything but the trailing count).
GH_STATS = 2


@dataclasses.dataclass(frozen=True)
class TransportSpec:
    """Wire format of the per-level VFL exchange (hashable, jit-static).

    ``kind``:
      ``"raw"``        full-precision float32 payloads (the PR-1 behavior);
      ``"quantized"``  int``bits`` histogram payload + per-(node, feature,
                       channel) float32 scales (histogram aggregation only);
      ``"topk"``       ``k`` best (gain, feature, threshold) tuples per node
                       per party (argmax aggregation only).
    """

    kind: str = "raw"
    bits: int = 8          # quantized: integer payload width (8 | 16)
    k: int = 4             # topk: candidates per node per party
    stochastic: bool = True  # quantized: stochastic (unbiased) rounding
    seed: int = 0          # quantized: rounding-noise key root

    def __post_init__(self):
        if self.kind not in ("raw", "quantized", "topk"):
            raise ValueError(f"unknown transport kind {self.kind!r}")
        if self.kind == "quantized" and self.bits not in (8, 16):
            raise ValueError(f"quantized transport needs bits in (8, 16), got {self.bits}")
        if self.kind == "topk" and self.k < 1:
            raise ValueError(f"topk transport needs k >= 1, got {self.k}")

    @property
    def tag(self) -> str:
        """Short name used in backend impl strings ("q8", "q16", "topk")."""
        if self.kind == "quantized":
            return f"q{self.bits}"
        if self.kind == "topk":
            return "topk"
        return "raw"


RAW = TransportSpec()
Q8 = TransportSpec(kind="quantized", bits=8)
Q16 = TransportSpec(kind="quantized", bits=16)
TOPK = TransportSpec(kind="topk", k=4)


def reconciled_ledger(
    mesh,
    tree: TreeConfig,
    cfg,
    aggregation: str = "histogram",
    transport: Optional[TransportSpec] = None,
    n_samples: int = 1024,
    num_features: Optional[int] = None,
    shard_samples: bool = False,
    async_exchange: bool = False,
    n_channels: int = 1,
    chaos=None,
):
    """One-call measured-vs-predicted accounting for a training run.

    Probes the backend's actual per-tree payloads (``probe_tree_cost``),
    builds the matching ``ProtocolSpec`` (wire predictions need the even
    party shard dims and, under row sharding, the data-shard count — the
    per-shard id_partition bitmaps round up independently), and returns a
    ``protocol.ProtocolLedger`` with the measured side recorded — ready for
    ``reconcile()`` / ``breakdown()``.  The shared entry point of every
    driver (launcher, example, comm_bench), so the reconciliation contract
    lives in one place.  Pass the *backend's own* transport
    (``descriptor.transport_spec``) — never reconstruct it from the tag,
    which cannot carry non-default parameters.
    """
    from repro.federation import protocol  # local: protocol is core-only

    num_parties = mesh.shape[mesh_roles.PARTY_AXIS]
    d = num_features if num_features is not None else num_parties * 2
    per_tree, grad = probe_tree_cost(
        mesh, tree, aggregation=aggregation, transport=transport,
        n_samples=n_samples, num_features=d, shard_samples=shard_samples,
        async_exchange=async_exchange, n_channels=n_channels, chaos=chaos,
    )
    data_shards = 1
    if shard_samples:
        for ax in mesh_roles.data_axes(mesh):
            data_shards *= mesh.shape[ax]
    spec = protocol.ProtocolSpec(
        n_samples=n_samples, party_dims=(d // num_parties,) * num_parties,
        num_bins=tree.num_bins, max_depth=tree.max_depth,
        aggregation=aggregation, hist_subtraction=tree.hist_subtraction,
        max_active_nodes=tree.max_active_nodes, data_shards=data_shards,
        n_channels=n_channels,
    )
    ledger = protocol.ProtocolLedger(spec=spec, cfg=cfg, transport=transport,
                                     chaos=chaos)
    ledger.record_run(per_tree, grad)
    return ledger


# ---------------------------------------------------------------------------
# Quantization codec
# ---------------------------------------------------------------------------
def quantize_stats(
    x: jnp.ndarray, bits: int, key: jax.Array, stochastic: bool = True
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize histogram stats to int``bits`` along the bin axis.

    Args:
      x: (..., B, C) float32 — per-bin stats (the bin axis is second-last).
      bits: 8 or 16.
      key: PRNG key for the stochastic-rounding noise.
      stochastic: unbiased stochastic rounding (floor(x/s + u)); nearest
        rounding otherwise.

    Returns:
      (q, scale): q (..., B, C) int8/int16; scale (..., C) float32 with
      ``x ≈ q * scale[..., None, :]``.  All-zero (node, feature, channel)
      slices get scale 1 so dequantization is exact there.
    """
    qmax = float(2 ** (bits - 1) - 1)
    absmax = jnp.max(jnp.abs(x), axis=-2, keepdims=True)          # (..., 1, C)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    y = x / scale
    if stochastic:
        y = jnp.floor(y + jax.random.uniform(key, x.shape))
    else:
        y = jnp.round(y)
    dtype = jnp.int8 if bits == 8 else jnp.int16
    q = jnp.clip(y, -qmax, qmax).astype(dtype)
    return q, scale[..., 0, :].astype(jnp.float32)


def dequantize_stats(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """Inverse of ``quantize_stats``: (..., B, C) int × (..., C) → float32."""
    return q.astype(jnp.float32) * scale[..., None, :]


# ---------------------------------------------------------------------------
# Measured-bytes plumbing
# ---------------------------------------------------------------------------
class MessageMeter:
    """Trace-time recorder of actual collective payload sizes.

    Collective wrappers call ``record(phase, operand)`` on the operand they
    are about to ship; the size is read off the (possibly abstract) array —
    ``size × dtype.itemsize`` — so metering works under ``jax.eval_shape``
    with zero run-time cost.  Entries accumulate once per *trace*, not per
    execution, so a meter is a probing device: attach a fresh meter to a
    fresh backend and trace exactly one program (``probe_tree_cost``), then
    scale by the schedule (``protocol.measured_run_cost``).  Backends built
    without a meter skip recording entirely.
    """

    def __init__(self) -> None:
        self.entries: list = []

    def record(self, phase: str, array) -> None:
        self.entries.append(
            {"phase": phase, "nbytes": int(array.size) * array.dtype.itemsize}
        )

    def phase_totals(self) -> dict:
        out: dict = {}
        for e in self.entries:
            out[e["phase"]] = out.get(e["phase"], 0) + e["nbytes"]
        return out

    def phase_counts(self) -> dict:
        """Number of recorded collectives per phase in the traced program —
        the round engine's 'one collective per level, not T' contract is
        checked against these counts (benchmarks/ci_guard.py)."""
        out: dict = {}
        for e in self.entries:
            out[e["phase"]] = out.get(e["phase"], 0) + 1
        return out

    def reset(self) -> None:
        self.entries = []


def probe_tree_cost(
    mesh,
    tree: TreeConfig,
    aggregation: str = "histogram",
    transport: Optional[TransportSpec] = None,
    n_samples: int = 1024,
    num_features: Optional[int] = None,
    shard_samples: bool = False,
    async_exchange: bool = False,
    n_channels: int = 1,
    chaos=None,
) -> tuple[dict, int]:
    """Measure one tree's actual per-phase wire bytes by abstract evaluation.

    Builds the requested VFL backend with a fresh ``MessageMeter`` and
    ``jax.eval_shape``s its real forest program on a single-tree mask, so
    every collective's traced operand reports the bytes it would ship — no
    device computation happens.

    Returns:
      (per_tree, grad_per_round): ``per_tree`` maps phase → bytes for ONE
      tree as recorded in the SPMD program (per *sending party* for the
      party-exchange phases — see ``protocol.PER_PASSIVE_PHASES`` for the
      scaling semantics); ``grad_per_round`` is the (g, h) broadcast payload
      per passive party per round.
    """
    from repro.federation import vfl  # local import: vfl imports compress

    num_parties = mesh.shape[mesh_roles.PARTY_AXIS]
    d = num_features if num_features is not None else num_parties * 2
    if d % num_parties:
        raise ValueError(f"num_features={d} must divide over {num_parties} parties")
    meter = MessageMeter()
    backend = vfl.make_vfl_backend(
        mesh, tree, aggregation=aggregation, transport=transport,
        shard_samples=shard_samples, meter=meter,
        async_exchange=async_exchange, chaos=chaos,
    )
    sds = jax.ShapeDtypeStruct
    # K-channel objectives (DESIGN.md §11) carry (n, K) derivatives; K = 1
    # keeps the historical (n,) vectors so the traced program is unchanged.
    gh_shape = (n_samples,) if n_channels == 1 else (n_samples, n_channels)
    with jax.set_mesh(mesh):
        jax.eval_shape(
            backend.forest_builder,
            sds((n_samples, d), jnp.int32),
            sds(gh_shape, jnp.float32),
            sds(gh_shape, jnp.float32),
            sds((1, n_samples), jnp.float32),
            sds((1, d), bool),
        )
    totals = meter.phase_totals()
    if shard_samples and "id_partition" in totals:
        # The routing psum operand is the only data-sharded payload; the
        # SPMD trace records one shard's packed (ceil(n/shards/8),) bitmap
        # slice, but the protocol message covers all n samples (each shard
        # ships its bitmap), so the full wire payload is the per-shard
        # record times the shard count — matching the wire model's
        # per-shard ceil arithmetic (protocol.wire_party_tree_cost).
        shards = 1
        for ax in mesh_roles.data_axes(mesh):
            shards *= mesh.shape[ax]
        totals["id_partition"] *= shards
    grad = totals.pop("grad_broadcast", 0)
    return totals, grad


def probe_round_collectives(
    mesh,
    tree: TreeConfig,
    n_trees: int,
    aggregation: str = "histogram",
    transport: Optional[TransportSpec] = None,
    n_samples: int = 1024,
    num_features: Optional[int] = None,
    async_exchange: bool = False,
) -> dict:
    """Trace a T-tree ROUND program and report per-phase collective counts
    and bytes — the round engine's structural contract (DESIGN.md §9): the
    per-level exchange is ONE collective carrying the whole round's
    ``(T, active, d_party, B, ...)`` payload, so the histogram-phase record
    count equals the number of histogram levels regardless of T (2 per
    level under quantization: int payload + scales).  The async backends
    (DESIGN.md §10) must preserve these counts: double-buffering splits the
    transfer, not the logical message, and the meter records the payload
    before the split.

    Returns {"counts": phase → records/trace, "totals": phase → bytes}.
    """
    from repro.federation import vfl  # local import: vfl imports compress

    num_parties = mesh.shape[mesh_roles.PARTY_AXIS]
    d = num_features if num_features is not None else num_parties * 2
    meter = MessageMeter()
    backend = vfl.make_vfl_backend(
        mesh, tree, aggregation=aggregation, transport=transport, meter=meter,
        async_exchange=async_exchange,
    )
    sds = jax.ShapeDtypeStruct
    with jax.set_mesh(mesh):
        jax.eval_shape(
            backend.forest_builder,
            sds((n_samples, d), jnp.int32),
            sds((n_samples,), jnp.float32),
            sds((n_samples,), jnp.float32),
            sds((n_trees, n_samples), jnp.float32),
            sds((n_trees, d), bool),
        )
    return {"counts": meter.phase_counts(), "totals": meter.phase_totals()}


# ---------------------------------------------------------------------------
# Compressed collective providers (shard_map inner fns)
# ---------------------------------------------------------------------------
def quantized_round_histogram_fn(
    party_axis: str = mesh_roles.PARTY_AXIS,
    data_axes: tuple = (),
    transport: TransportSpec = Q8,
    meter: Optional[MessageMeter] = None,
    base_fn: Callable = hist_mod.compute_round_histogram,
    gather: Optional[Callable] = None,
):
    """Round-native quantized histogram provider (DESIGN.md §9): one party
    ``all_gather`` per level carries the whole round's int payload
    ``(T, nodes, d_party, B, 2)`` + scales ``(T, nodes, d_party, 2)`` —
    one ``quantize_stats`` scale per (tree, node, feature, channel).  The
    count channel never traverses the wire (split search does not read it;
    leaf stats are a separate, local pass), so the returned global
    histogram has count ≡ 0.  The stochastic-rounding key derives from
    ``fold_in(seed, num_nodes) ⊕ party`` — deliberately not threaded from
    the training rng so the provider keeps the plain histogram-fn
    signature (unbiased per element; inputs change every round).
    Shared-root caching (``root_delta_rows``) is a local transformation
    applied *before* quantization, so the wire payload is unchanged.

    ``gather`` is the exchange seam (DESIGN.md §10): the int payload rides
    the pluggable gather (double-buffered under the async backends); the
    tiny per-(node, feature, channel) scale vector always ships in one
    plain all_gather — splitting it would buy nothing."""
    if transport.kind != "quantized":
        raise ValueError(f"need a quantized TransportSpec, got {transport!r}")
    from repro.federation import aggregator  # local: aggregator is sibling

    if gather is None:
        gather = aggregator.plain_gather

    def fn(binned_shard, g, h, weight, assign, num_nodes, num_bins,
           root_delta_rows=0, level=0):
        local = base_fn(binned_shard, g, h, weight, assign, num_nodes,
                        num_bins, root_delta_rows=root_delta_rows,
                        level=level)
        with jax.named_scope("fedgbf.exchange"):
            for ax in data_axes:
                local = jax.lax.psum(local, ax)
        # everything but the trailing count channel traverses the wire:
        # (T, nodes, d_party, B, 2K) — GH_STATS (= 2) at K = 1.
        payload = local[..., :-1]
        # fold the LEVEL (not just the width) into the key: subtraction and
        # compaction make several levels share a num_nodes, and equal-shape
        # payloads would otherwise draw bit-identical rounding noise.
        key = jax.random.fold_in(jax.random.PRNGKey(transport.seed), level)
        key = jax.random.fold_in(key, num_nodes)
        key = jax.random.fold_in(key, jax.lax.axis_index(party_axis))
        q, scale = quantize_stats(payload, transport.bits, key,
                                  transport.stochastic)
        if meter is not None:
            meter.record("histograms", q)
            meter.record("histograms", scale)
        with jax.named_scope("fedgbf.exchange"):
            q_g = gather(q, party_axis, 2)
            s_g = jax.lax.all_gather(scale, party_axis, axis=2, tiled=True)
        deq = dequantize_stats(q_g, s_g)  # (T, nodes, d, B, 2)
        count = jnp.zeros(deq.shape[:-1] + (1,), deq.dtype)
        return jnp.concatenate([deq, count], axis=-1)

    return fn


def topk_round_choose_fn(
    cfg: TreeConfig,
    k: int,
    party_axis: str = mesh_roles.PARTY_AXIS,
    meter: Optional[MessageMeter] = None,
    gather: Optional[Callable] = None,
):
    """Round-native top-k chooser: the per-tree candidate exchange batched
    over the explicit tree axis (one vmapped gather program — a single
    collective per level in the lowered program).  The lossless party-major
    tie-break contract is untouched: it delegates to ``topk_choose_fn``
    per tree."""
    per_tree = topk_choose_fn(cfg, k, party_axis, meter, gather=gather)
    return lambda hist, fmask: jax.vmap(per_tree)(hist, fmask)


def topk_choose_fn(
    cfg: TreeConfig,
    k: int,
    party_axis: str = mesh_roles.PARTY_AXIS,
    meter: Optional[MessageMeter] = None,
    gather: Optional[Callable] = None,
):
    """Split chooser exchanging each party's k best candidates per node.

    The argmax aggregation's candidate exchange, generalized (the raw
    argmax mode IS k = 1): each party evaluates its local gains, ``top_k``s them, and only the (gain,
    feature, threshold) tuples are gathered.  The merge flattens the
    gathered candidates *party-major* with each party's list in descending
    gain / ascending-flat-index order (``lax.top_k`` breaks ties toward the
    lower index), so ``argmax``'s first-occurrence rule reproduces the
    centralized tie-break exactly — the mode is lossless for any k ≥ 1.

    ``gather`` is the *stacking* exchange seam (``gather(x, party_axis)``
    -> leading party axis): the default is a direct ``all_gather``; the
    chaos transport (DESIGN.md §13) substitutes its fault-injecting,
    checksum-verified wrapper here.
    """
    if gather is None:
        gather = lambda x, pa: jax.lax.all_gather(x, pa)

    def fn(hist_local, feature_mask_local):
        num_nodes, d_party, num_bins, _ = hist_local.shape
        p = jax.lax.axis_index(party_axis)
        gains = split_mod.split_gains(hist_local, cfg)  # (nodes, d_party, B)
        gains = jnp.where(
            feature_mask_local[None, :, None], gains, split_mod.NEG_INF
        )
        flat = gains.reshape(num_nodes, d_party * num_bins)
        k_eff = min(k, d_party * num_bins)
        top_gain, top_idx = jax.lax.top_k(flat, k_eff)  # (nodes, k_eff)
        feat = (top_idx // num_bins).astype(jnp.int32) + p * d_party
        thr = (top_idx % num_bins).astype(jnp.int32)
        if meter is not None:
            for arr in (top_gain, feat, thr):
                meter.record("split_candidates", arr)
        with jax.named_scope("fedgbf.exchange"):
            gains_all = gather(top_gain, party_axis)  # (P, nodes, k)
            feats_all = gather(feat, party_axis)
            thrs_all = gather(thr, party_axis)
        num_parties = gains_all.shape[0]
        merge = lambda a: jnp.moveaxis(a, 1, 0).reshape(
            num_nodes, num_parties * k_eff
        )
        g2, f2, t2 = merge(gains_all), merge(feats_all), merge(thrs_all)
        best = jnp.argmax(g2, axis=1)
        take = lambda a: jnp.take_along_axis(a, best[:, None], axis=1)[:, 0]
        best_gain = take(g2)
        has_split = best_gain > 0.0
        return split_mod.SplitDecision(
            feature=jnp.where(has_split, take(f2), -1),
            threshold=jnp.where(has_split, take(t2), num_bins),
            gain=best_gain,
        )

    return fn
