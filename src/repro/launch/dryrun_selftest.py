"""In-pytest dry-run smoke: lowers train/prefill/decode for smoke configs on
a small forced-device mesh (run in a subprocess, like federation.selftest):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.dryrun_selftest
"""

from __future__ import annotations

import sys

import jax

from repro.configs import ARCH_IDS, get_smoke_config
from repro.launch import shapes as shapes_mod
from repro.launch.dryrun import build_step
from repro.launch.mesh import make_test_mesh

SMOKE_SPECS = [
    shapes_mod.ShapeSpec("smoke_train", "train", 64, 8),
    shapes_mod.ShapeSpec("smoke_prefill", "prefill", 64, 8),
    shapes_mod.ShapeSpec("smoke_decode", "decode", 64, 8),
]

# smoke subset spanning all families
ARCHS = ["smollm-135m", "gemma2-2b", "zamba2-7b", "rwkv6-7b",
         "granite-moe-3b-a800m", "whisper-large-v3"]


def main() -> int:
    mesh = make_test_mesh()
    failures = 0
    for arch in ARCHS:
        cfg = get_smoke_config(arch)
        for spec in SMOKE_SPECS:
            try:
                fn, args, in_sh = build_step(cfg, spec, mesh)
                with jax.set_mesh(mesh):
                    compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
                cost = compiled.cost_analysis()
                if isinstance(cost, (list, tuple)):
                    cost = cost[0]
                print(f"OK {arch} {spec.name} flops/dev={cost.get('flops', 0):.3e}")
            except Exception as e:  # noqa: BLE001
                failures += 1
                print(f"FAIL {arch} {spec.name}: {type(e).__name__}: "
                      f"{str(e)[:200]}")
    print("DRYRUN SELFTEST " + ("FAILED" if failures else "PASSED"))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
