"""Activation-sharding constraints, mesh-aware and no-op off-mesh.

GSPMD propagates weight shardings through the forward, but without anchors on
activations it can choose replication — the calibration experiment in
EXPERIMENTS.md §Perf showed ~14x redundant per-device FLOPs on smollm before
these constraints existed. Every helper:

  * reads the ambient abstract mesh (jax.set_mesh / jit context),
  * silently no-ops when there is no mesh (CPU smoke tests) or when the dim
    is not divisible by the target axis size (MQA kv=1, batch=1, H=9, ...).

Axis conventions match DESIGN.md §8: batch -> ("pod","data"), feature/head/
expert fan-out -> "model".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P, get_abstract_mesh


def _mesh_axes() -> dict:
    mesh = get_abstract_mesh()
    if mesh.empty:
        return {}
    return dict(mesh.shape)


def _batch_axes(axes: dict) -> tuple:
    return tuple(a for a in ("pod", "data") if a in axes)


def _fits(dim: int, names, axes: dict) -> bool:
    if isinstance(names, str):
        names = (names,)
    total = 1
    for n in names:
        if n not in axes:
            return False
        total *= axes[n]
    return dim % total == 0


def constrain(x: jnp.ndarray, spec_builder) -> jnp.ndarray:
    """Apply with_sharding_constraint(spec_builder(axes)) if a mesh is set."""
    axes = _mesh_axes()
    if not axes:
        return x
    spec = spec_builder(axes)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def shard_tokens(x: jnp.ndarray) -> jnp.ndarray:
    """(B, S, ...) activations between blocks: batch over (pod, data)."""

    def build(axes):
        ba = _batch_axes(axes)
        if not ba or not _fits(x.shape[0], ba, axes):
            return None
        return P(ba, *([None] * (x.ndim - 1)))

    return constrain(x, build)


def shard_fused_heads(x: jnp.ndarray, n_heads: int | None = None,
                      seq_ok: bool = True) -> jnp.ndarray:
    """(B, S, H*hd) fused-head activations (attention output before w_o).

    When heads divide the model axis, shard the fused dim (w_o's contraction
    reduces locally, reduce-scatter friendly). When they DON'T (gemma2 H=8),
    keep the SEQUENCE sharding the scores carried — constraining the fused
    dim here made XLA reshard by all-gathering the (S, S) f32 probs in the
    backward (EXPERIMENTS.md §Perf, gemma2 iteration 2).
    """

    def build(axes):
        ba = _batch_axes(axes)
        b = ba if (ba and _fits(x.shape[0], ba, axes)) else None
        heads_fit = n_heads is None or _fits(n_heads, "model", axes)
        if not heads_fit and seq_ok and x.shape[1] > 1 and                 _fits(x.shape[1], "model", axes):
            return P(b, "model", None)
        m = "model" if _fits(x.shape[-1], "model", axes) else None
        if b is None and m is None:
            return None
        return P(b, None, m)

    return constrain(x, build)


def shard_heads(x: jnp.ndarray, role: str = "q", seq_ok: bool = True) -> jnp.ndarray:
    """(B, S, H, hd) split heads.

    Preference order (EXPERIMENTS.md §Perf, gemma2 hillclimb):
      1. heads over "model" when H divides — zero-redundancy head parallelism;
      2. for QUERIES: the query-sequence dim over "model" — keeps the (S, S)
         score/prob tensors sharded through fwd AND bwd (the hd fallback made
         XLA all-gather 4 full S^2 f32 tensors per layer in the backward);
      3. head_dim over "model" (legacy fallback, kept for decode's S == 1);
      4. batch only.
    K/V never seq-shard (they are contracted over the full key sequence).
    """

    def build(axes):
        ba = _batch_axes(axes)
        b = ba if (ba and _fits(x.shape[0], ba, axes)) else None
        if _fits(x.shape[2], "model", axes):
            return P(b, None, "model", None)
        if role == "q" and seq_ok and x.shape[1] > 1 and _fits(x.shape[1], "model", axes):
            return P(b, "model", None, None)
        if role != "kv" and _fits(x.shape[3], "model", axes):
            return P(b, None, None, "model")
        return P(b, None, None, None) if b else None

    return constrain(x, build)


def shard_ff(x: jnp.ndarray) -> jnp.ndarray:
    """(B, S, F) FFN hidden (or (T, F) for MoE): last dim over model."""

    def build(axes):
        ba = _batch_axes(axes)
        b = ba if (x.ndim >= 3 and ba and _fits(x.shape[0], ba, axes)) else None
        m = "model" if _fits(x.shape[-1], "model", axes) else None
        if b is None and m is None:
            return None
        return P(*([b] + [None] * (x.ndim - 2) + [m]))

    return constrain(x, build)
