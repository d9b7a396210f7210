import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run driver (deliverable e).

Lowers + compiles every (architecture x input shape) combination against the
production mesh — (16, 16) single pod and (2, 16, 16) multi-pod — and records
memory_analysis / cost_analysis / collective bytes for the roofline.

The XLA_FLAGS line above MUST precede every other import (jax locks the
device count on first init); do not move it. Do NOT import this module from
tests or benches — they must see the real single device. Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
    PYTHONPATH=src python -m repro.launch.dryrun --all --both-meshes
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch import costmodel
from repro.launch import shapes as shapes_mod
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.launch.shardings import (
    batch_spec,
    cache_shardings,
    param_shardings,
    replicated,
    train_state_shardings,
)
from repro.models import model as model_mod
from repro.models import train as train_mod
from repro.tools import roofline as roofline_mod

REPORT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "reports", "dryrun")
#: The chip the production mesh is made of (a key of ``roofline.PEAKS``).
TARGET_DEVICE_KIND = "TPU v5 lite"


def build_step(cfg, spec, mesh):
    """Returns (fn, example_args (ShapeDtypeStructs), in_shardings).

    ``spec`` is a shapes.ShapeSpec — one of shapes.SHAPES for the assigned
    matrix, or any custom spec (the in-pytest smoke uses a tiny one)."""
    specs = shapes_mod.input_specs_for(cfg, spec)

    if spec.kind == "train":
        state_shapes = jax.eval_shape(
            lambda k: train_mod.init_train_state(k, cfg), jax.random.PRNGKey(0)
        )
        step = train_mod.make_train_step(cfg)
        state_sh = train_state_shardings(cfg, mesh)
        batch_sh = {
            k: batch_spec(mesh, len(v.shape), v.shape[0])
            for k, v in specs.items()
        }
        return step, (state_shapes, specs), (state_sh, batch_sh)

    params_shapes = jax.eval_shape(
        lambda k: model_mod.init_params(k, cfg), jax.random.PRNGKey(0)
    )
    params_sh = param_shardings(cfg, mesh)

    if spec.kind == "prefill":
        def prefill_fn(params, batch):
            return model_mod.prefill(
                params, batch["tokens"], cfg,
                patch_embeds=batch.get("patch_embeds"),
                frames=batch.get("frames"),
            )

        batch_sh = {k: batch_spec(mesh, len(v.shape), v.shape[0])
                    for k, v in specs.items()}
        return prefill_fn, (params_shapes, specs), (params_sh, batch_sh)

    # decode
    def serve_step(params, cache, token, pos):
        return model_mod.decode_step(params, cache, token, pos, cfg)

    cache_sh = cache_shardings(cfg, mesh, spec.global_batch, spec.seq_len)
    tok_sh = batch_spec(mesh, 2, spec.global_batch)
    return (
        serve_step,
        (params_shapes, specs["cache"], specs["token"], specs["pos"]),
        (params_sh, cache_sh, tok_sh, replicated(mesh)),
    )


def run_one(arch: str, shape_name: str, multi_pod: bool,
            save: bool = True, unroll: bool = False,
            variant: str = "") -> dict:
    ok, reason = shapes_mod.applicable(arch, shape_name)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}__{shape_name}__{mesh_name}" + (f"__{variant}" if variant else "")
    if not ok:
        report = {"tag": tag, "status": "skipped", "reason": reason}
        _save(report, tag, save)
        print(f"[SKIP] {tag}: {reason}")
        return report

    # Default: ROLLED production program (the deployable artifact) + the
    # compositional cost model (costmodel.py). --unroll switches to a fully
    # unrolled program whose cost_analysis is directly exact (validation).
    cfg = dataclasses.replace(get_config(arch), scan_unroll=unroll)
    moe_impl = os.environ.get("REPRO_MOE_IMPL")
    if moe_impl and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, impl=moe_impl)
        )
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    spec = shapes_mod.SHAPES[shape_name]

    t0 = time.time()
    try:
        fn, args, in_sh = build_step(cfg, spec, mesh)
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        coll = roofline_mod.parse_collectives(compiled.as_text())
        program_cost = costmodel._per_device_cost(compiled)
        if unroll:
            total = program_cost
        else:
            t1 = time.time()
            total = costmodel.composite_cost(cfg, mesh, shape_name, program_cost)
            t_bodies = time.time() - t1
        roof = roofline_mod.roofline_from_costs(total, cfg, spec, chips,
                                                TARGET_DEVICE_KIND)
        report = {
            "tag": tag,
            "status": "ok",
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_name,
            "chips": chips,
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
                "temp_bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
                "peak_bytes_per_device": getattr(
                    mem, "peak_memory_in_bytes",
                    getattr(mem, "temp_size_in_bytes", None),
                ),
            },
            "roofline": roof.as_dict(),
            "costing": "unrolled-exact" if unroll else "composite",
            "collectives_program": {
                "bytes_by_kind": coll.bytes_by_kind,
                "count_by_kind": coll.count_by_kind,
            },
        }
        print(
            f"[OK]  {tag}: compile {t_compile:.0f}s "
            f"flops={roof.flops:.3e} hbm={roof.hbm_bytes:.3e} "
            f"coll={roof.collective_bytes:.3e} dominant={roof.dominant} "
            f"useful={roof.useful_ratio:.2f}"
        )
    except Exception as e:  # noqa: BLE001 — failures ARE the test output
        report = {
            "tag": tag,
            "status": "error",
            "error": f"{type(e).__name__}: {e}",
            "traceback": traceback.format_exc()[-4000:],
        }
        print(f"[FAIL] {tag}: {type(e).__name__}: {str(e)[:300]}")
    _save(report, tag, save)
    return report


def _save(report: dict, tag: str, save: bool) -> None:
    if not save:
        return
    os.makedirs(REPORT_DIR, exist_ok=True)
    with open(os.path.join(REPORT_DIR, tag + ".json"), "w") as f:
        json.dump(report, f, indent=1)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(shapes_mod.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="full 10x4 matrix")
    ap.add_argument("--unroll", action="store_true",
                    help="fully unroll scans (exact but slow; validation)")
    ap.add_argument("--variant", default="", help="report filename suffix")
    args = ap.parse_args()

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shape_names = (
        list(shapes_mod.SHAPES) if (args.all or not args.shape) else [args.shape]
    )
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for multi_pod in meshes:
        for arch in archs:
            for shape_name in shape_names:
                report = run_one(arch, shape_name, multi_pod,
                                 unroll=args.unroll, variant=args.variant)
                if report["status"] == "error":
                    failures += 1
    print(f"\ndone; failures={failures}")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
