"""Compositional roofline costing for scanned (rolled) programs.

XLA's cost_analysis counts a while-loop body ONCE, so the production program
(layers under lax.scan) underreports FLOPs/bytes/collectives by ~num_units.
Fully unrolling fixes the numbers but costs minutes of compile per program —
infeasible for the 10 x 4 x 2 matrix on one CPU core.

Instead we cost compositionally:

    total = program_rolled + (num_units - 1) * unit_body
            [+ (enc_layers - 1) * enc_body]           (whisper)
            [+ (num_shared_apps - 1) * shared_block]  (zamba2)

where each term is a separate small jit program compiled with the SAME mesh
and shardings. The rolled program still proves the full pipeline lowers and
provides memory_analysis (it IS the deployable artifact); the body programs
provide exact per-layer costs. Validation against a full unroll (smollm
train_4k: composite within a few percent) lives in EXPERIMENTS.md §Dry-run.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import shapes as shapes_mod
from repro.launch.shardings import batch_spec, cache_spec, param_spec
from repro.models import blocks, model as model_mod
from repro.tools import roofline as roofline_mod


def _per_device_cost(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    stats = roofline_mod.parse_collectives(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": float(stats.total_bytes),
        "collectives": stats,
    }


def _unit_param_shapes(cfg, pos_strip=True):
    """Shapes of ONE unit's params (leading stack axis stripped)."""
    shapes = jax.eval_shape(
        lambda k: model_mod.init_params(k, cfg), jax.random.PRNGKey(0)
    )
    units = shapes["units"]
    strip = lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
    return jax.tree.map(strip, units), shapes


def _shard_tree(tree, mesh):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_spec(path, leaf, mesh)),
        tree,
    )


def _x_spec(cfg, batch, seq):
    return jax.ShapeDtypeStruct((batch, seq, cfg.d_model),
                                jnp.dtype(cfg.compute_dtype))


def unit_body_cost(cfg, mesh, batch: int, seq: int, kind: str,
                   enc_out_spec=None) -> dict:
    """Per-device cost of one scan unit (fwd for prefill/decode kind='fwd',
    fwd+bwd with remat for kind='train')."""
    unit_shapes, _ = _unit_param_shapes(cfg)
    unit_sh = _shard_tree(unit_shapes, mesh)
    x_spec = _x_spec(cfg, batch, seq)
    x_sh = batch_spec(mesh, 3, batch)

    def fwd(unit_params, x, enc_out=None):
        for pos, bt in enumerate(cfg.pattern):
            x, _ = blocks.block_forward(unit_params[pos], x, bt, cfg, enc_out)
        return x

    if kind == "train":
        body = jax.checkpoint(fwd) if cfg.remat else fwd
        if enc_out_spec is not None:
            fn = jax.grad(
                lambda up, x, eo: jnp.sum(body(up, x, eo).astype(jnp.float32)),
                argnums=(0, 1),
            )
            args = (unit_shapes, x_spec, enc_out_spec)
            shardings = (unit_sh, x_sh, batch_spec(mesh, 3, batch))
        else:
            fn = jax.grad(
                lambda up, x: jnp.sum(body(up, x).astype(jnp.float32)),
                argnums=(0, 1),
            )
            args, shardings = (unit_shapes, x_spec), (unit_sh, x_sh)
    else:
        if enc_out_spec is not None:
            fn = lambda up, x, eo: fwd(up, x, eo)
            args = (unit_shapes, x_spec, enc_out_spec)
            shardings = (unit_sh, x_sh, batch_spec(mesh, 3, batch))
        else:
            fn = fwd
            args, shardings = (unit_shapes, x_spec), (unit_sh, x_sh)

    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
    return _per_device_cost(compiled)


def decode_body_cost(cfg, mesh, batch: int, seq_len: int) -> dict:
    """Per-device cost of one decode-scan unit (1 token vs its cache slice)."""
    unit_shapes, _ = _unit_param_shapes(cfg)
    unit_sh = _shard_tree(unit_shapes, mesh)
    cache_shapes = jax.eval_shape(
        lambda: model_mod.init_cache(cfg, batch, seq_len)
    )
    strip = lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
    unit_cache = [jax.tree.map(strip, c) for c in cache_shapes["blocks"]]
    # cache_spec on stripped leaves: batch moves to dim 0
    cache_sh = jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_spec(path, leaf, mesh, batch_dim=0), unit_cache
    )
    cross = cache_shapes.get("cross")
    cross_spec = None
    cross_sh = None
    if cross is not None:
        cross_spec = jax.tree.map(strip, cross)
        cross_sh = jax.tree_util.tree_map_with_path(
            lambda path, leaf: cache_spec(path, leaf, mesh, batch_dim=0),
            cross_spec,
        )

    x_spec = _x_spec(cfg, batch, 1)
    x_sh = batch_spec(mesh, 3, batch)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(unit_params, caches, x, pos, cross_cache=None):
        new = []
        for p_idx, bt in enumerate(cfg.pattern):
            cc = cross_cache if bt == "dec_attn" else None
            x, nc = blocks.block_decode(
                unit_params[p_idx], x, caches[p_idx], pos, bt, cfg,
                cross_cache=cc,
            )
            new.append(nc)
        return x, new

    args = [unit_shapes, unit_cache, x_spec, pos_spec]
    shardings = [unit_sh, cache_sh, x_sh, NamedSharding(mesh, P())]
    if cross_spec is not None:
        args.append(cross_spec)
        shardings.append(cross_sh)
    with jax.set_mesh(mesh):
        compiled = (
            jax.jit(fn, in_shardings=tuple(shardings))
            .lower(*args)
            .compile()
        )
    return _per_device_cost(compiled)


def shared_block_cost(cfg, mesh, batch: int, seq: int, kind: str) -> dict:
    """Per-device cost of zamba2's weight-shared attention block."""
    shapes = jax.eval_shape(
        lambda k: blocks.init_shared_attn(k, cfg), jax.random.PRNGKey(0)
    )
    sh = _shard_tree(shapes, mesh)
    x_spec = _x_spec(cfg, batch, seq)
    x_sh = batch_spec(mesh, 3, batch)

    if kind == "train":
        body = jax.checkpoint(
            lambda p, x: blocks.shared_attn_forward(p, x, cfg)
        )
        fn = jax.grad(
            lambda p, x: jnp.sum(body(p, x).astype(jnp.float32)),
            argnums=(0, 1),
        )
    else:
        fn = lambda p, x: blocks.shared_attn_forward(p, x, cfg)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=(sh, x_sh)).lower(
            shapes, x_spec
        ).compile()
    return _per_device_cost(compiled)


def shared_decode_cost(cfg, mesh, batch: int, seq_len: int) -> dict:
    shapes = jax.eval_shape(
        lambda k: blocks.init_shared_attn(k, cfg), jax.random.PRNGKey(0)
    )
    sh = _shard_tree(shapes, mesh)
    cache = jax.eval_shape(
        lambda: blocks.init_block_cache("attn", cfg, batch, seq_len)
    )
    cache_sh = jax.tree_util.tree_map_with_path(
        lambda path, leaf: cache_spec(path, leaf, mesh, batch_dim=0), cache
    )
    x_spec = _x_spec(cfg, batch, 1)
    fn = lambda p, c, x, pos: blocks.shared_attn_decode(p, x, c, pos, cfg)
    with jax.set_mesh(mesh):
        compiled = jax.jit(
            fn,
            in_shardings=(sh, cache_sh, batch_spec(mesh, 3, batch),
                          NamedSharding(mesh, P())),
        ).lower(shapes, cache, x_spec,
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
    return _per_device_cost(compiled)


def composite_cost(cfg, mesh, shape_name: str, program_cost: dict) -> dict:
    """total = rolled program + (U-1) * unit body [+ encoder, shared terms]."""
    spec = shapes_mod.SHAPES[shape_name]
    U = cfg.num_units
    total = dict(program_cost)

    def add(term: dict, times: float):
        for k in ("flops", "bytes", "collective_bytes"):
            total[k] = total[k] + times * term[k]

    if spec.kind in ("train", "prefill"):
        kind = "train" if spec.kind == "train" else "fwd"
        if cfg.encoder is not None:
            enc_spec = _x_spec(cfg, spec.global_batch, cfg.encoder.num_frames)
            enc_body = unit_body_cost(
                dataclasses.replace(cfg, pattern=("enc_attn",), encoder=None),
                mesh, spec.global_batch, cfg.encoder.num_frames, kind,
            )
            add(enc_body, cfg.encoder.num_layers - 1)
            body = unit_body_cost(
                cfg, mesh, spec.global_batch, spec.seq_len, kind,
                enc_out_spec=enc_spec,
            )
        else:
            body = unit_body_cost(cfg, mesh, spec.global_batch, spec.seq_len, kind)
        add(body, U - 1)
        if cfg.shared_attn_every > 0:
            apps = model_mod._num_shared_apps(cfg)
            sb = shared_block_cost(cfg, mesh, spec.global_batch, spec.seq_len, kind)
            add(sb, max(apps - 1, 0))
    else:  # decode
        body = decode_body_cost(cfg, mesh, spec.global_batch, spec.seq_len)
        add(body, U - 1)
        if cfg.shared_attn_every > 0:
            apps = model_mod._num_shared_apps(cfg)
            sb = shared_decode_cost(cfg, mesh, spec.global_batch, spec.seq_len)
            add(sb, max(apps - 1, 0))
    return total
