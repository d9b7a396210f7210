"""TreeBackend: the execution seam of the tree library (DESIGN.md §1).

Historically the histogram/split/route/leaf providers were four loose
callables threaded ad-hoc through ``boosting -> forest -> tree``, and the
federated path bypassed them with a fifth (``forest_fn``).  A ``TreeBackend``
bundles all of them plus an execution descriptor (impl name, party/mesh
configuration) into one hashable object that is threaded as a single jit
static argument.  Named backends come from a registry:

  ``"local"``         centralized execution, segment-sum histograms;
  ``"local-pallas"``  centralized execution, Pallas TPU histogram kernel;
  ``"vfl-histogram"`` shard_map VFL, paper-faithful full-histogram exchange;
  ``"vfl-argmax"``    shard_map VFL, candidate-only exchange (beyond-paper);
  ``"vfl-histogram-q8"`` / ``"-q16"``  histogram exchange quantized to
                      int8/int16 + per-(node, feature, channel) scales
                      (lossy; federation/compress.py, DESIGN.md §5);
  ``"vfl-argmax-topk"`` each party ships its k best candidates per node
                      (lossless for any k >= 1);
  ``"vfl-histogram-async[-q8|-q16]"`` the histogram exchange double-
                      buffered: the per-level collective ships as two
                      overlapping transfers (DESIGN.md §10), bit-identical
                      results, one logical message either way;
  ``"vfl-*-sharded"`` the above with samples additionally sharded over the
                      data axes (rows split ``(n/data_shards, ...)`` per
                      host; histograms/leaf stats psum over the data axes,
                      uneven row counts pad with weight-0 rows inside the
                      backend — the multi-host extension, DESIGN.md §8).

The ``vfl-*`` factories need a device mesh and a ``TreeConfig``
(``get_backend(name, mesh=..., tree=...)``); they are registered lazily by
``federation/vfl.py`` on first request so ``core`` never imports
``federation``.  Later scaling work (async rounds, multi-host execution,
histogram caching) plugs in here by registering new factories.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

_REGISTRY: dict = {}


@dataclasses.dataclass(frozen=True)
class BackendDescriptor:
    """Execution metadata of a TreeBackend (all fields hashable/static).

    ``impl`` is the registry name; ``histogram_impl`` names the histogram
    provider family (``"segment"`` | ``"onehot"`` | ``"pallas"``); the party/
    data fields describe the SPMD decomposition for federated backends and
    stay at their defaults for centralized ones.  ``transport`` names the
    wire format of the per-level party exchange (``"raw"`` | ``"q8"`` |
    ``"q16"`` | ``"topk"``; federation/compress.py) and ``transport_spec``
    carries the full (frozen, hashable) ``compress.TransportSpec`` for
    non-raw formats — the tag alone cannot represent non-default parameters
    (a custom top-k k or quantization seed), and byte accounting must never
    guess them.
    """

    impl: str
    histogram_impl: str = "segment"
    num_parties: int = 1
    party_axis: Optional[str] = None
    data_axes: tuple = ()
    shard_samples: bool = False
    transport: str = "raw"
    transport_spec: Optional[object] = None  # compress.TransportSpec (non-raw)
    # Double-buffered level exchange (DESIGN.md §10): the per-level party
    # all_gather ships as two overlapping transfers instead of one barrier
    # collective.  Payloads and results are bit-identical; only the
    # schedule changes.
    async_exchange: bool = False
    # Chaos transport (DESIGN.md §13): the frozen ``chaos.ChaosSpec`` when
    # the level exchange runs under the fault-injecting wrapper, else None.
    # Carried here for the same reason as ``transport_spec``: byte
    # accounting must replay the exact fault schedule, never guess it.
    chaos: Optional[object] = None

    @property
    def is_federated(self) -> bool:
        return self.party_axis is not None


@dataclasses.dataclass(frozen=True)
class TreeBackend:
    """Bundled execution providers for tree/forest construction.

    The execution unit is the *round* (DESIGN.md §9): ``core.tree.build_round``
    drives round-native providers whose operands carry an explicit leading
    ``(T, ...)`` tree axis.  Per-tree providers remain the compatibility
    seam — when only they are set, ``build_round`` lifts them over the tree
    axis with ``jax.vmap``; a backend overrides the ``round_*`` twin to fuse
    the tree axis into its program (the segment-sum fold, the Pallas
    tree-grid kernel, ONE party collective per level).

    Provider semantics (all optional — None selects the centralized default):

      histogram_fn  signature of ``core.histogram.compute_histogram``;
      round_histogram_fn  round-native twin (``compute_round_histogram``
                    contract): (T, n) weight/assign -> (T, nodes, d, B, 3);
                    must accept the keywords ``level`` (the static tree
                    level — stateful transports key per-level state off it)
                    and, when the backend is used with shared-root caching
                    (§9), ``root_delta_rows``;
      child_histogram_fn / round_child_histogram_fn  child-only histogram
                    providers of the subtraction pipeline (DESIGN.md §6):
                    same signatures, but ``assign`` is the current level's
                    assignment and the frontier argument is the PARENT
                    count — return left-child histograms at half width.
                    None derives them generically via
                    ``histogram.as_child_fn``/``as_round_child_fn``;
                    backends override only to fuse the left-mask/parent-id
                    staging (the Pallas child kernels).  Consulted only when
                    ``TreeConfig.hist_subtraction`` is set;
      choose_fn     (hist, feature_mask) -> SplitDecision;
      round_choose_fn  ((T, nodes, d, B, 3), (T, d)) -> (T, nodes) decision;
      route_fn      (binned, assign, decision) -> new assign;
      round_route_fn  batched twin over (T, n) assignments;
      leaf_fn       signature of ``core.histogram.leaf_stats``
                    ((g, h, weight, assign, num_leaves) -> (num_leaves, 3)),
                    used for the leaf-statistics pass;
      round_leaf_fn  round twin ((T, n) -> (T, num_leaves, 3)); also serves
                    the compaction liveness counts (psum'd when sharded);
      forest_builder  full override of ``core.forest.build_forest`` — the
                    federated path uses this to wrap the whole per-round
                    forest construction in one shard_map program with the
                    other providers baked in.
      forest_builder_per_tree  full override of
                    ``core.forest.build_forest_per_tree`` (same wrapping, but
                    returning per-tree predictions) — consumed by the scanned
                    training engine, which owns the bagging combine.

    Frozen (hashable) so the whole object rides through ``jax.jit`` as one
    static argument; reuse a backend instance across rounds/calls to reuse
    the jit cache.
    """

    descriptor: BackendDescriptor
    histogram_fn: Optional[Callable] = None
    child_histogram_fn: Optional[Callable] = None
    choose_fn: Optional[Callable] = None
    route_fn: Optional[Callable] = None
    leaf_fn: Optional[Callable] = None
    round_histogram_fn: Optional[Callable] = None
    round_child_histogram_fn: Optional[Callable] = None
    round_choose_fn: Optional[Callable] = None
    round_route_fn: Optional[Callable] = None
    round_leaf_fn: Optional[Callable] = None
    forest_builder: Optional[Callable] = None
    forest_builder_per_tree: Optional[Callable] = None

    @property
    def name(self) -> str:
        return self.descriptor.impl

    def build_forest(self, binned, g, h, sample_mask, feature_mask, cfg=None,
                     root_delta_rows=0):
        """Build one forest layer (drop-in for ``core.forest.build_forest``).

        ``cfg`` may be omitted for backends whose ``forest_builder`` bakes
        the tree config into a pre-built program (the shard_map VFL path).
        ``root_delta_rows`` is the static shared-root delta-buffer width
        (``core.tree.build_round``; 0 = direct level-0 pass).
        """
        if self.forest_builder is not None:
            return self.forest_builder(
                binned, g, h, sample_mask, feature_mask, cfg,
                root_delta_rows=root_delta_rows,
            )
        if cfg is None:
            raise ValueError(f"backend {self.name!r} needs an explicit TreeConfig")
        from repro.core import forest as forest_mod  # local to avoid cycle

        return forest_mod.build_forest(
            binned, g, h, sample_mask, feature_mask, cfg, backend=self,
            root_delta_rows=root_delta_rows,
        )

    def build_forest_per_tree(self, binned, g, h, sample_mask, feature_mask,
                              cfg=None, root_delta_rows=0):
        """Build one forest layer, returning (trees, per_tree_pred (T, n)).

        The scanned training engine's entry point (DESIGN.md §4): the caller
        owns the bagging combine so it can mask out inactive tree slots.
        """
        if self.forest_builder_per_tree is not None:
            return self.forest_builder_per_tree(
                binned, g, h, sample_mask, feature_mask, cfg,
                root_delta_rows=root_delta_rows,
            )
        if self.forest_builder is not None:
            raise ValueError(
                f"backend {self.name!r} overrides forest_builder but provides "
                "no forest_builder_per_tree; the scanned engine needs the "
                "per-tree variant (see federation/vfl.py for the template)"
            )
        if cfg is None:
            raise ValueError(f"backend {self.name!r} needs an explicit TreeConfig")
        from repro.core import forest as forest_mod  # local to avoid cycle

        return forest_mod.build_forest_per_tree(
            binned, g, h, sample_mask, feature_mask, cfg, backend=self,
            root_delta_rows=root_delta_rows,
        )

    def build_tree(self, binned, g, h, sample_mask, feature_mask, cfg):
        """Build one tree (drop-in for ``core.tree.build_tree``)."""
        from repro.core import tree as tree_mod  # local to avoid cycle

        return tree_mod.build_tree(
            binned, g, h, sample_mask, feature_mask, cfg, backend=self
        )


def register_backend(name: str, factory: Callable[..., TreeBackend]) -> None:
    """Register a named backend factory: ``factory(**kwargs) -> TreeBackend``."""
    _REGISTRY[name] = factory


def available_backends() -> tuple:
    """Registered backend names (triggers the lazy vfl registration)."""
    _ensure_vfl_registered()
    return tuple(sorted(_REGISTRY))


def get_backend(name: str, **kwargs) -> TreeBackend:
    """Construct a named backend. ``vfl-*`` names need ``mesh=``/``tree=``."""
    if name not in _REGISTRY and name.startswith("vfl"):
        _ensure_vfl_registered()
    if name not in _REGISTRY:
        raise ValueError(
            f"unknown backend {name!r}; available: {available_backends()}"
        )
    return _REGISTRY[name](**kwargs)


def resolve_backend(backend, **kwargs) -> TreeBackend:
    """Accept None | name | TreeBackend and return a TreeBackend."""
    if backend is None:
        return get_backend("local")
    if isinstance(backend, str):
        return get_backend(backend, **kwargs)
    if isinstance(backend, TreeBackend):
        return backend
    raise TypeError(f"backend must be None, str, or TreeBackend; got {backend!r}")


def _ensure_vfl_registered() -> None:
    try:
        import repro.federation.vfl  # noqa: F401  (registers vfl-* factories)
    except ImportError as e:
        # Only a genuinely absent federation package degrades to local-only;
        # any other ImportError (e.g. a broken transitive dep) must surface
        # rather than masquerade as "unknown backend".
        if e.name and e.name.startswith("repro.federation"):
            return
        raise


def _local_factory(**_kw) -> TreeBackend:
    return TreeBackend(BackendDescriptor(impl="local"))


def _local_pallas_factory(**_kw) -> TreeBackend:
    # The Pallas histogram kernel: id/stats staging happens inside the
    # kernel (kernels/histogram/train_histogram.py), not in XLA.  The child
    # variant additionally forms the subtraction pipeline's left-mask and
    # parent ids in-kernel, so the half-width pass stays staging-free too.
    # The round variants add the tree-grid axis (DESIGN.md §9): one kernel
    # launch accumulates the whole round's (T, nodes, d, B, 3) histogram.
    from repro.core.histogram import histogram_dispatch

    return TreeBackend(
        BackendDescriptor(impl="local-pallas", histogram_impl="pallas"),
        histogram_fn=histogram_dispatch("pallas"),
        child_histogram_fn=histogram_dispatch("pallas-child"),
        round_histogram_fn=histogram_dispatch("pallas-round"),
        round_child_histogram_fn=histogram_dispatch("pallas-round-child"),
    )


register_backend("local", _local_factory)
register_backend("local-pallas", _local_pallas_factory)
