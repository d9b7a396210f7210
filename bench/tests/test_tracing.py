"""The reduction from a profiler trace to device intervals and metrics."""

import os

import pytest

from bench import tracing

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "serve_v5e.xplane.pb")


def test_union_of_intervals():
    assert tracing.union_ns([]) == 0
    assert tracing.union_ns([(0, 10), (5, 15), (20, 25)]) == 20
    assert tracing.union_ns([(20, 25), (0, 10), (10, 12)]) == 17


def _trace(ops0, ops1=None, window=(0, 100), host=()):
    devices = {"/device:TPU:0": ops0}
    if ops1 is not None:
        devices["/device:TPU:1"] = ops1
    return tracing.Trace(window, devices, list(host))


def test_instruction_name_of_an_hlo_event():
    assert tracing.instruction("%fusion.12 = f32[8]{0} fusion(%a), kind=kLoop"
                               ) == "fusion.12"
    assert tracing.CONTAINER.match("while.188")
    assert not tracing.CONTAINER.match("while_fusion.3")


def test_busy_kernel_and_exposed_collective_time():
    tr = _trace([("fusion.1", 0, 10), ("all-reduce.3", 5, 30),
                 ("fedgbf_histogram.2", 40, 50)],
                [("all-gather-start", 0, 20), ("fusion.7", 10, 20)])
    # device 0 busy 0-30 and 40-50, device 1 busy 0-20: mean 30 ns
    assert tracing.busy_s(tr) == pytest.approx(30e-9)
    assert tracing.window_s(tr) == pytest.approx(100e-9)
    assert tracing.op_seconds(tr, lambda n: "fedgbf_histogram" in n) == pytest.approx(5e-9)
    assert tracing.op_seconds(tr, tracing.is_collective) == pytest.approx(22.5e-9)
    # device 0: all-reduce 5-30 overlaps compute 5-10 -> 20 exposed;
    # device 1: all-gather 0-20 overlaps compute 10-20 -> 10 exposed
    assert tracing.exposed_collective_s(tr) == pytest.approx(15e-9)


def test_breakdown_names_gaps_by_the_host_span_inside_them():
    tr = _trace([("fusion.1", 0, 10), ("fusion.2", 60, 70)],
                host=[("bench.wait", 12, 58), ("bench.serve_call", 58, 75)])
    b = tracing.breakdown(tr)
    assert b["device_ops"] == [["fusion.1", pytest.approx(10e-9)],
                               ["fusion.2", pytest.approx(10e-9)]]
    assert b["idle_gaps"][0] == ["bench.wait", pytest.approx(50e-9)]
    assert b["idle_gaps"][1] == ["host.other", pytest.approx(30e-9)]


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace():
    """A short stretch of the serving cell recorded on a TPU v5e: one device
    plane, the traversal kernel by name, busy under the window."""
    tr = tracing.load(RECORDED)
    assert list(tr.devices) == ["/device:TPU:0"]
    kernel = tracing.op_seconds(tr, lambda n: "fedgbf_ensemble_predict" in n)
    assert 0 < kernel <= tracing.busy_s(tr) < tracing.window_s(tr)
    assert not tracing.op_seconds(tr, tracing.is_collective)
    names = {h[0] for h in tr.host}
    assert {"bench.window", "bench.serve_call"} <= names
    b = tracing.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
