"""Compile-only checks of the Pallas kernels for a described TPU v5e.

The TPU compiler ships with jaxlib and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot:
block shapes the Mosaic compiler refuses, unsupported ops, VMEM overruns.
Nothing runs, so they say nothing about results or speed.

Shapes are the paper's Give-Me-Some-Credit cell: 105k training rows x 10
features, B = 32 bins, depth 3 (frontier 1/2/4 nodes), a 5-tree round,
and the 81-tree ensemble the 20-round dynamic 5->2 schedule packs; and the
HIGGS cell's widest histogram levels (700k x 28, B 256, 64 and 128 nodes).

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


# The HIGGS cell: 700k training rows x 28 features, B 256, depth 8.  The
# flat kernel's one-hot at 64 parents (16,384 lanes) overruns VMEM; the
# wide-frontier form holds one node block whatever the width.
@pytest.mark.parametrize("nodes,child", [(64, True), (128, False)])
def test_wide_frontier_histogram_compiles(one_chip, compiled_for_tpu, nodes,
                                          child):
    from repro.kernels.histogram.ops import (compute_round_histogram_pallas,
                                             histogram_form)

    n, d, b = 700_000, 28, 256
    assert histogram_form(nodes, b) == "nodes"
    fn = lambda binned, g, h, w, a: compute_round_histogram_pallas(
        binned, g, h, w, a, nodes, b, child=child
    )
    text = compiled_for_tpu(
        fn,
        _aval((n, d), jnp.int32, one_chip),
        _aval((n,), jnp.float32, one_chip),
        _aval((n,), jnp.float32, one_chip),
        _aval((TREES, n), jnp.float32, one_chip),
        _aval((TREES, n), jnp.int32, one_chip),
    )
    assert "fedgbf_histogram_nodes" in text


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


@pytest.mark.parametrize("width", [1, 2, 4])
def test_round_route_has_no_per_row_gather(one_chip, width):
    """Routing a depth-3 level compiles to whole-row reads: no gather of
    single elements (``slice_sizes={1,1}``) is left, and the level counts
    as routed by rows."""
    import re

    from repro.core import split, tree
    from repro.obs import compiles

    def route(binned, assign, feature, threshold):
        return tree.route_local_round(binned, assign, split.SplitDecision(
            feature=feature, threshold=threshold, gain=None))

    rows = compiles.install().route_levels
    before = rows.value
    text = jax.jit(route).lower(
        _aval((N, D), jnp.int32, one_chip),
        _aval((TREES, N), jnp.int32, one_chip),
        _aval((TREES, width), jnp.int32, one_chip),
        _aval((TREES, width), jnp.int32, one_chip),
    ).compile().as_text()
    assert rows.value - before == 1
    gathers = re.findall(r"= \S+ gather\(.*", text)
    assert not [g for g in gathers if "slice_sizes={1,1}" in g], gathers


def test_train_auc_compiles_to_one_sort_and_no_loop(one_chip):
    """The train AUC at the GMSC cell's rows compiles for the chip to one
    two-operand sort and prefix scans: no gather, scatter or while loop."""
    import re

    from repro.core import metrics

    text = jax.jit(metrics.auc).lower(
        _aval((N,), jnp.float32, one_chip), _aval((N,), jnp.float32, one_chip)
    ).compile().as_text()
    for op in ("gather", "scatter", "while"):
        assert not re.search(rf"= \S+ {op}\(", text), op
    sorts = re.findall(r"= \((.*?)\) sort\(", text)
    assert len(sorts) == 1 and sorts[0].count("[") == 2, sorts
