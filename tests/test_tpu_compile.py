"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes the Mosaic compiler refuses, unsupported ops, VMEM overruns.
Nothing runs, so they say nothing about results or speed.

Shapes are the paper's Give-Me-Some-Credit cell: 105k training rows x 10
features, B = 32 bins, depth 3 (frontier 1/2/4 nodes), a 5-tree round,
and the 81-tree ensemble the 20-round dynamic 5->2 schedule packs.

The topology is described inside a module fixture (never at import), so
every xdist worker collects the same tests and only the one that runs
them loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, D, B, TREES = 105_000, 10, 32, 5
ENSEMBLE_TREES, DEPTH, SERVE_ROWS = 81, 3, 32_768


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A described device cannot read the persistent cache back; keep it off.
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture
def compiled_for_tpu(monkeypatch):
    """Compile ``fn`` at the given shapes for the chip (kernels forced out of
    interpret mode) and return the optimized HLO text."""
    from repro.kernels.ensemble_predict import ops as predict_ops
    from repro.kernels.histogram import ops as hist_ops

    monkeypatch.setattr(hist_ops, "interpret_mode", lambda: False)
    monkeypatch.setattr(predict_ops, "interpret_mode", lambda: False)

    def compile_(fn, *avals):
        text = jax.jit(fn).lower(*avals).compile().as_text()
        assert "tpu_custom_call" in text, "kernel was not lowered to Mosaic"
        return text

    return compile_


def _aval(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# Depth 3: direct passes at frontier 1/2/4 nodes; the subtraction
# pipeline's child passes at levels 1/2 (1/2 parents).
@pytest.mark.parametrize(
    "nodes,child", [(1, False), (2, False), (4, False), (1, True), (2, True)]
)
def test_round_histogram_kernel_compiles(one_chip, compiled_for_tpu, nodes,
                                         child):
    from repro.kernels.histogram.ops import compute_round_histogram_pallas

    fn = lambda binned, g, h, w, a: compute_round_histogram_pallas(
        binned, g, h, w, a, nodes, B, child=child
    )
    compiled_for_tpu(
        fn,
        _aval((N, D), jnp.int32, one_chip),
        _aval((N,), jnp.float32, one_chip),
        _aval((N,), jnp.float32, one_chip),
        _aval((TREES, N), jnp.float32, one_chip),
        _aval((TREES, N), jnp.int32, one_chip),
    )


@pytest.mark.parametrize("raw", [True, False])
def test_serving_kernel_compiles(one_chip, compiled_for_tpu, raw):
    from repro.kernels.ensemble_predict.ops import _ensemble_pallas

    internal, leaves = 2**DEPTH - 1, 2**DEPTH
    fn = lambda f, t, lw, s, x: _ensemble_pallas(
        f, t, lw, s, -2.6, x, DEPTH, 256, raw
    )
    x_dtype = jnp.float32 if raw else jnp.int32
    compiled_for_tpu(
        fn,
        _aval((ENSEMBLE_TREES, internal), jnp.int32, one_chip),
        _aval((ENSEMBLE_TREES, internal), x_dtype, one_chip),
        _aval((ENSEMBLE_TREES, leaves), jnp.float32, one_chip),
        _aval((ENSEMBLE_TREES,), jnp.float32, one_chip),
        _aval((SERVE_ROWS, D), x_dtype, one_chip),
    )
