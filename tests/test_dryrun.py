"""Dry-run smoke (deliverable e, reduced): lowers + compiles train/prefill/
decode for six smoke archs on an 8-device forced mesh in a subprocess."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_selftest_subprocess():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun_selftest"],
        env=env, capture_output=True, text=True, timeout=1200,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-2000:]}"
    assert "DRYRUN SELFTEST PASSED" in out.stdout


def test_shape_applicability_table():
    from repro.configs import ARCH_IDS
    from repro.launch import shapes

    runs = {a for a in ARCH_IDS if shapes.applicable(a, "long_500k")[0]}
    assert runs == {"zamba2-7b", "rwkv6-7b", "gemma2-2b", "mixtral-8x22b"}
    for a in ARCH_IDS:
        for s in ("train_4k", "prefill_32k", "decode_32k"):
            assert shapes.applicable(a, s)[0]


def test_roofline_collective_parser():
    from repro.tools.roofline import parse_collectives

    hlo = """
  %ag = bf16[16,1024,128]{2,1,0} all-gather(%x), replica_groups={}
  %ar = f32[256]{0} all-reduce(%y), to_apply=%sum
  %a2a = f32[8,32]{1,0} all-to-all(%z), dimensions={0}
  %cp = u32[4]{0} collective-permute(%w), source_target_pairs={{0,1}}
  %rs = bf16[512]{0} reduce-scatter(%v), dimensions={0}, to_apply=%sum
"""
    stats = parse_collectives(hlo)
    assert stats.count_by_kind == {
        "all-gather": 1, "all-reduce": 1, "all-to-all": 1,
        "collective-permute": 1, "reduce-scatter": 1,
    }
    assert stats.bytes_by_kind["all-gather"] == 16 * 1024 * 128 * 2
    assert stats.bytes_by_kind["all-reduce"] == 256 * 4
    assert stats.bytes_by_kind["reduce-scatter"] == 512 * 2


def test_roofline_peaks_keyed_by_device_kind():
    """Peaks come from the published-table entry for the device kind; an
    unknown kind is an error, never a default."""
    import pytest

    from repro.tools.roofline import Roofline, peaks

    assert peaks("TPU v5 lite").bf16_flops == 197e12
    assert peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    roof = Roofline(flops=197e12, hbm_bytes=0.0, collective_bytes=0.0,
                    chips=1, model_flops=0.0, device_kind="TPU v5 lite")
    assert roof.compute_s == 1.0
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("cpu")


def test_compile_cache_dir(monkeypatch):
    """The cache follows JAX_COMPILATION_CACHE_DIR when it is set (and sets
    nothing), else the fixed in-checkout directory."""
    import jax

    from repro.launch import compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.enable()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
