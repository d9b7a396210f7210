"""The vertically-federated forest builder: Alg. 1/2 under shard_map.

The entire per-round forest construction runs as one SPMD program in which
the party axis of the mesh *is* the party decomposition of the VFL protocol:
every mesh shard holds one party's feature columns, executes the per-party
steps of Alg. 2 locally, and the protocol's messages become jax.lax
collectives (see aggregator.py for the exact correspondence).

Losslessness: both aggregation modes produce trees identical to the
centralized builder (tests/test_federation.py asserts this bit-for-bit),
which is the SecureBoost property the paper's §4.2.1 relies on to evaluate
federated models locally.
"""

from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import forest as forest_mod
from repro.core.backend import BackendDescriptor, TreeBackend, register_backend
from repro.core.types import TreeConfig
from repro.federation import aggregator, compress, mesh_roles
from repro.federation import async_exchange as async_mod
from repro.federation import chaos as chaos_mod


def make_vfl_backend(
    mesh: Mesh,
    tree: TreeConfig,
    aggregation: str = "histogram",
    party_axis: str = mesh_roles.PARTY_AXIS,
    shard_samples: bool = False,
    transport=None,
    meter=None,
    async_exchange: bool = False,
    chaos=None,
) -> TreeBackend:
    """Construct the vertically-federated TreeBackend (DESIGN.md §1).

    The per-party providers (federated histogram / choose / route / leaf
    collectives from aggregator.py) form an *inner* backend that runs inside
    the shard_map body; the returned backend's ``forest_builder`` wraps the
    whole per-round forest construction in that one SPMD program, so the
    boosting loop threads a single object either way.

    Args:
      mesh: mesh containing ``party_axis`` (and optionally data axes).
      tree: static tree config baked into the shard_map program.
      aggregation: "histogram" (paper-faithful full-histogram exchange) or
        "argmax" (beyond-paper candidate-only exchange; see aggregator.py).
      shard_samples: also shard the sample axis over the data axes (the
        multi-worker extension; histograms/leaf stats psum over those axes).
      transport: ``compress.TransportSpec`` selecting the wire format of the
        per-level exchange (DESIGN.md §5): None/"raw" = full-precision
        float32; "quantized" (histogram mode) = int8/int16 payloads +
        per-(node, feature, channel) scales; "topk" (argmax mode) = k
        candidates per node per party.
      meter: ``compress.MessageMeter`` — when given, every party-axis
        collective records its actual payload size at trace time (use via
        ``compress.probe_tree_cost``; see MessageMeter for semantics).
      async_exchange: double-buffer the per-level histogram exchange
        (DESIGN.md §10): the payload ships as two overlapping transfers
        instead of one barrier all_gather.  Bit-identical results, one
        logical metered message per level either way.  Histogram
        aggregation only — the argmax/top-k candidate exchange already
        ships small independent gathers.
      chaos: ``chaos.ChaosSpec`` — wrap the level exchange (whatever base
        gather the flags above select) in the fault-injecting, checksum-
        verified chaos transport (DESIGN.md §13).  The recovered result is
        bit-identical to the wrapped transport even under injected faults;
        the meter gains a ``"retries"`` phase for the integrity channel +
        retransmissions.
    """
    cfg = tree
    num_parties = mesh.shape[party_axis]
    data_axes = mesh_roles.data_axes(mesh) if shard_samples else ()
    if transport is None:
        transport = compress.RAW
    if async_exchange and aggregation != "histogram":
        raise ValueError(
            "async_exchange applies to the histogram aggregation only "
            "(the argmax candidate exchange is already multi-buffered)"
        )

    # Chaos transport (DESIGN.md §13): ONE stateful wrapper per backend,
    # composed over whatever base gather the other flags select.  The
    # forest builders reset its trace-time slot counter at every entry so
    # each traced program enumerates fault slots 0..L-1 deterministically.
    chaos_gather = None
    if chaos is not None:
        base_gather = (partial(async_mod.double_buffered_gather,
                               split_axis=-2)
                       if async_exchange else aggregator.plain_gather)
        chaos_gather = chaos_mod.ChaoticGather(
            chaos, base_gather, num_parties, meter=meter
        )

    # Round-native providers (DESIGN.md §9): the tree axis is explicit, so
    # each level's party exchange is ONE collective carrying the whole
    # round's (T, active, d_party, B, ...) payload.
    if aggregation == "histogram":
        if chaos_gather is not None:
            # same provider lattice, with the chaos gather at the seam
            if transport.kind == "quantized":
                histogram_fn = compress.quantized_round_histogram_fn(
                    party_axis, data_axes, transport, meter=meter,
                    gather=chaos_gather,
                )
            elif transport.kind == "raw":
                histogram_fn = aggregator.federated_round_histogram_fn(
                    party_axis, data_axes, meter=meter, gather=chaos_gather
                )
            else:
                raise ValueError(
                    f"transport {transport.kind!r} does not apply to the "
                    "histogram aggregation (use 'raw' or 'quantized')"
                )
        elif async_exchange:
            histogram_fn = async_mod.async_round_histogram_fn(
                party_axis, data_axes, transport, meter=meter
            )
        elif transport.kind == "quantized":
            histogram_fn = compress.quantized_round_histogram_fn(
                party_axis, data_axes, transport, meter=meter
            )
        elif transport.kind == "raw":
            histogram_fn = aggregator.federated_round_histogram_fn(
                party_axis, data_axes, meter=meter
            )
        else:
            raise ValueError(
                f"transport {transport.kind!r} does not apply to the "
                "histogram aggregation (use 'raw' or 'quantized')"
            )
        choose_fn = aggregator.centralized_round_choose_fn(
            cfg, party_axis, meter=meter
        )
    elif aggregation == "argmax":
        histogram_fn = aggregator.local_round_histogram_fn(party_axis, data_axes)
        if transport.kind == "topk":
            choose_fn = compress.topk_round_choose_fn(
                cfg, transport.k, party_axis, meter=meter,
                gather=chaos_gather,
            )
        elif transport.kind == "raw":
            choose_fn = compress.topk_round_choose_fn(
                cfg, 1, party_axis, meter=meter, gather=chaos_gather
            )
        else:
            raise ValueError(
                f"transport {transport.kind!r} does not apply to the "
                "argmax aggregation (use 'raw' or 'topk')"
            )
    else:
        raise ValueError(f"unknown aggregation {aggregation!r}")
    route_fn = aggregator.federated_round_route_fn(party_axis, meter=meter)
    leaf_fn = aggregator.local_round_leaf_fn(data_axes=data_axes)
    # Subtraction pipeline (DESIGN.md §6): no dedicated provider needed —
    # ``build_round`` derives ``as_round_child_fn(histogram_fn)`` from the
    # transport above, so the left-mask/halve staging runs inside the
    # shard_map body and the party all_gather (raw or quantized, metered
    # either way) ships the half-frontier payload; every party derives the
    # right siblings locally after the merge.

    impl = f"vfl-{aggregation}"
    if async_exchange:
        impl += "-async"
    if transport.kind != "raw":
        impl += f"-{transport.tag}"
    if shard_samples:
        impl += "-sharded"
    if chaos is not None:
        impl += "-chaos"
    descriptor = BackendDescriptor(
        impl=impl,
        num_parties=num_parties,
        party_axis=party_axis,
        data_axes=data_axes,
        shard_samples=shard_samples,
        transport=transport.tag,
        transport_spec=None if transport.kind == "raw" else transport,
        async_exchange=async_exchange,
        chaos=chaos,
    )
    inner = TreeBackend(
        descriptor=descriptor,
        round_histogram_fn=histogram_fn,
        round_choose_fn=choose_fn,
        round_route_fn=route_fn,
        round_leaf_fn=leaf_fn,
    )

    sample_spec = P(data_axes) if data_axes else P()
    in_specs = (
        P(sample_spec[0] if data_axes else None, party_axis),  # binned (n, d)
        sample_spec,                                           # g (n,)
        sample_spec,                                           # h (n,)
        P(None, sample_spec[0] if data_axes else None),        # smask (T, n)
        P(None, party_axis),                                   # fmask (T, d)
    )

    # The shard_map bodies close over the static shared-root buffer width
    # (``root_delta_rows``, DESIGN.md §9) — a local compute transformation
    # inside each party's histogram program, so the collective payloads are
    # unchanged.  One wrapped program per distinct width, cached.
    @lru_cache(maxsize=None)
    def _sharded(rdr: int):
        def _forest_body(binned_shard, g, h, smask, fmask_shard):
            return forest_mod.build_forest.__wrapped__(  # un-jitted inner
                binned_shard, g, h, smask, fmask_shard, cfg, backend=inner,
                root_delta_rows=rdr,
            )

        return shard_map(
            _forest_body,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), sample_spec),  # (trees replicated, train_pred)
            check_vma=False,
        )

    # Per-tree variant: predictions keep the tree axis (T, n) — replicated on
    # the party axis (each party computes the full routing via the psum'd
    # bitmaps), sharded like the samples on the data axes.
    @lru_cache(maxsize=None)
    def _sharded_per_tree(rdr: int):
        def _forest_body_per_tree(binned_shard, g, h, smask, fmask_shard):
            return forest_mod._forest_per_tree(  # un-jitted per-tree inner
                binned_shard, g, h, smask, fmask_shard, cfg, backend=inner,
                root_delta_rows=rdr,
            )

        return shard_map(
            _forest_body_per_tree,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=(P(), P(None, sample_spec[0] if data_axes else None)),
            check_vma=False,
        )

    @partial(jax.jit, static_argnames=("rdr",))
    def _run(binned, g, h, sample_mask, feature_mask, rdr=0):
        return _sharded(rdr)(binned, g, h, sample_mask, feature_mask)

    @partial(jax.jit, static_argnames=("rdr",))
    def _run_per_tree(binned, g, h, sample_mask, feature_mask, rdr=0):
        return _sharded_per_tree(rdr)(binned, g, h, sample_mask, feature_mask)

    # Row padding for uneven shards (DESIGN.md §8): shard_map needs n
    # divisible by the data-axis extent, but callers hand arbitrary n.  The
    # pad happens HERE — inside the backend, *after* the boosting engine
    # drew its exact-count subsampling masks over the real n rows — so the
    # sampling semantics are untouched: padded rows enter with sample-mask
    # weight 0 (histograms, leaf stats, liveness counts and shared-root
    # deltas all weight by the mask, so they are inert) and the returned
    # predictions slice back to the caller's n.
    shard_count = 1
    for _ax in data_axes:
        shard_count *= mesh.shape[_ax]

    def _pad_rows(binned, g, h, sample_mask):
        n = binned.shape[0]
        n_pad = -(-n // shard_count) * shard_count
        if n_pad == n:
            return binned, g, h, sample_mask, n
        pad = n_pad - n
        # g/h are (n,) for scalar objectives, (n, K) for K-channel ones —
        # either way only the sample axis pads.
        row_pad = lambda v: jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
        return (
            jnp.pad(binned, ((0, pad), (0, 0))),
            row_pad(g),
            row_pad(h),
            jnp.pad(sample_mask, ((0, 0), (0, pad))),
            n,
        )

    def _check(binned, _cfg):
        """The tree config is baked into the shard_map program, so a
        caller-passed cfg must match ``tree`` (a silent mismatch would build
        trees at one depth and traverse at another)."""
        if _cfg is not None and _cfg != cfg:
            raise ValueError(
                f"backend {descriptor.impl!r} was built with {cfg}, but the "
                f"caller passed {_cfg}; construct the backend with the same "
                "TreeConfig as FedGBFConfig.tree"
            )
        d = binned.shape[1]
        if d % num_parties != 0:
            raise ValueError(
                f"d={d} must shard evenly over {num_parties} parties; "
                "pad columns with data.tabular.pad_features"
            )

    def forest_builder(binned, g, h, sample_mask, feature_mask, _cfg=None,
                       root_delta_rows=0):
        _check(binned, _cfg)
        if chaos_gather is not None:
            chaos_gather.begin_trace()
        if meter is not None:
            # The per-round (g, h) broadcast active -> each passive party.
            # Not a collective here (the derivatives enter replicated), so
            # it is metered at the program boundary from the actual arrays
            # — the REAL n rows, before any shard padding.
            meter.record("grad_broadcast", g)
            meter.record("grad_broadcast", h)
        binned, g, h, sample_mask, n = _pad_rows(
            binned, g, h, sample_mask.astype(jnp.float32)
        )
        trees, pred = _run(binned, g, h, sample_mask, feature_mask,
                           rdr=root_delta_rows)
        return trees, pred[:n]

    def forest_builder_per_tree(binned, g, h, sample_mask, feature_mask,
                                _cfg=None, root_delta_rows=0):
        _check(binned, _cfg)
        if chaos_gather is not None:
            chaos_gather.begin_trace()
        if meter is not None:
            meter.record("grad_broadcast", g)
            meter.record("grad_broadcast", h)
        binned, g, h, sample_mask, n = _pad_rows(
            binned, g, h, sample_mask.astype(jnp.float32)
        )
        trees, per_tree = _run_per_tree(
            binned, g, h, sample_mask, feature_mask, rdr=root_delta_rows
        )
        return trees, per_tree[:, :n]

    # The per-node collectives live only on the INNER backend consumed inside
    # the shard_map body; exposing them here would invite generic callers
    # (forest.build_forest(backend=...), backend.build_tree) to run them
    # outside shard_map, where the axis names are unbound.  The public
    # surface of a VFL backend is build_forest -> forest_builder (and the
    # per-tree variant the scanned training engine consumes).
    return TreeBackend(
        descriptor=descriptor,
        forest_builder=forest_builder,
        forest_builder_per_tree=forest_builder_per_tree,
    )


def make_federated_forest_fn(
    mesh: Mesh,
    cfg: TreeConfig,
    aggregation: str = "histogram",
    party_axis: str = mesh_roles.PARTY_AXIS,
    shard_samples: bool = False,
):
    """DEPRECATED shim: returns ``make_vfl_backend(...).build_forest`` with
    the legacy hook kwargs (histogram_fn= etc.) absorbed for drop-in use.

    Prefer passing the backend object itself to ``boosting.train_fedgbf``.
    """
    backend = make_vfl_backend(
        mesh, cfg, aggregation=aggregation, party_axis=party_axis,
        shard_samples=shard_samples,
    )

    def forest_fn(binned, g, h, sample_mask, feature_mask, _cfg=None, **_ignored):
        return backend.build_forest(binned, g, h, sample_mask, feature_mask, _cfg)

    return forest_fn


# Registry entries: vfl backends bind a mesh + tree config at construction,
# e.g. ``get_backend("vfl-argmax", mesh=mesh, tree=TreeConfig(...))``.
# Compressed-transport variants (DESIGN.md §5) are distinct registry names,
# not kwargs, so scaling work stays registry factories per DESIGN.md §1.
def _vfl_factory(aggregation: str, shard_samples: bool, transport=None,
                 async_exchange: bool = False, chaos_enabled: bool = False):
    def factory(mesh=None, tree=None, **kw):
        if mesh is None or tree is None:
            raise ValueError(
                "vfl backends need mesh= and tree= (a TreeConfig), e.g. "
                "get_backend('vfl-histogram', mesh=mesh, tree=TreeConfig())"
            )
        explicit = kw.pop("transport", None)
        if (transport is not None and explicit is not None
                and explicit != transport):
            # The registry name encodes the transport (DESIGN.md §1/§5); a
            # conflicting explicit spec would silently ship a different wire
            # format than the name promises.
            raise ValueError(
                f"backend name encodes transport {transport.tag!r} but "
                f"transport= {explicit!r} was passed; drop the kwarg or use "
                "the matching registry name"
            )
        chaos = kw.pop("chaos", None)
        if chaos_enabled:
            # "-chaos" names default to the zero-fault spec: the wrapper
            # (checksum channel + selection fold) is live, faults are not.
            chaos = chaos if chaos is not None else chaos_mod.ChaosSpec()
        elif chaos is not None:
            raise ValueError(
                "chaos= was passed to a non-chaos backend name; use the "
                "matching '-chaos' registry name (DESIGN.md §13)"
            )
        return make_vfl_backend(
            mesh, tree, aggregation=aggregation, shard_samples=shard_samples,
            transport=transport if transport is not None else explicit,
            async_exchange=async_exchange, chaos=chaos, **kw
        )

    return factory


# The async double-buffered exchange (DESIGN.md §10) is a histogram-mode
# lever, so only the histogram family grows "-async" names.  Every name in
# the lattice also grows a "-chaos" twin (DESIGN.md §13): the fault-
# injecting transport composes over any of them.
_TRANSPORTS = {
    "histogram": (("", None), ("-q8", compress.Q8), ("-q16", compress.Q16)),
    "argmax": (("", None), ("-topk", compress.TOPK)),
}
for _agg, _variants in _TRANSPORTS.items():
    for _suffix, _transport in _variants:
        _asyncs = (False, True) if _agg == "histogram" else (False,)
        for _async in _asyncs:
            _name = f"vfl-{_agg}" + ("-async" if _async else "") + _suffix
            for _shard, _sname in ((False, _name), (True, _name + "-sharded")):
                register_backend(
                    _sname,
                    _vfl_factory(_agg, shard_samples=_shard,
                                 transport=_transport, async_exchange=_async),
                )
                register_backend(
                    _sname + "-chaos",
                    _vfl_factory(_agg, shard_samples=_shard,
                                 transport=_transport, async_exchange=_async,
                                 chaos_enabled=True),
                )


def party_shardings(mesh: Mesh, party_axis: str = mesh_roles.PARTY_AXIS):
    """NamedShardings for placing the global arrays party-wise up front so the
    shard_map incurs no re-layout: binned (n, d) sharded on columns."""
    return {
        "binned": NamedSharding(mesh, P(None, party_axis)),
        "vector": NamedSharding(mesh, P()),
    }
