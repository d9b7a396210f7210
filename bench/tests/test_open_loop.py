"""The open-loop generator and loop, against a fake server on the host."""

import time
import types

import numpy as np

from bench.drivers import open_loop

TRAFFIC = {"base_seed": 0, "single_row_share": 0.8, "multi_rows_min": 2,
           "multi_rows_max": 256}


class FakeServer:
    """Scores a row as its first feature; ``stall_at`` sleeps once when the
    row carrying that value is served."""

    def __init__(self, stall_at=None, stall_s=0.0, service_s=0.0):
        self.metrics = types.SimpleNamespace(
            batches=types.SimpleNamespace(value=0))
        self.stall_at, self.stall_s, self.service_s = stall_at, stall_s, service_s

    def serve(self, rows):
        self.metrics.batches.value += 1
        if self.stall_at is not None and (rows[:, 0] == self.stall_at).any():
            time.sleep(self.stall_s)
        if self.service_s:
            time.sleep(self.service_s)
        return rows[:, 0].astype(np.float32)


def test_same_seed_same_schedule_and_every_seed_the_same_work():
    a = open_loop.schedule(TRAFFIC, 1000.0, 2.0, 5)
    b = open_loop.schedule(TRAFFIC, 1000.0, 2.0, 5)
    c = open_loop.schedule(TRAFFIC, 1000.0, 2.0, 6)
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    np.testing.assert_array_equal(a[2].integers(0, 99, 8), b[2].integers(0, 99, 8))
    sizes = lambda s: np.sort(np.diff(s[1]))
    np.testing.assert_array_equal(sizes(a), sizes(c))      # same sizes
    assert not np.array_equal(np.diff(a[1]), np.diff(c[1]))  # other order
    assert a[0].size == 2000 and a[0][0] == 0 and a[0][-1] < 2.0
    assert np.all(np.diff(a[0]) >= 0)
    mean = np.diff(a[1]).mean()
    assert 8 < mean < 15


def _run(server, n=40, gap=0.005):
    due = np.arange(n) * gap
    offsets = np.arange(n + 1)
    rows = np.arange(n, dtype=np.float32)[:, None] * np.ones((1, 3), np.float32)
    return due, open_loop.open_loop(server, rows, due, offsets)


def test_latency_counts_from_the_due_time_so_a_stall_delays_later_requests():
    due, res = _run(FakeServer(stall_at=10.0, stall_s=0.05))
    lat = res["done"] - due
    # the requests due during the stall wait for it: their latency counts
    # from when they were due, not from when the loop got to them
    assert lat[10] >= 0.05
    assert lat[11] >= 0.04 and lat[15] >= 0.02
    assert np.all(res["dispatched"][11:16] >= due[10] + 0.05)
    assert np.median(lat[30:]) < 0.005
    np.testing.assert_array_equal(res["scores"], np.arange(40, dtype=np.float32))


def test_every_due_request_is_counted_even_when_drained_after_the_window():
    # each call takes 20 ms while requests come every 5 ms: the queue grows
    # and the last requests finish well after the last one was due
    due, res = _run(FakeServer(service_s=0.02))
    assert np.all(np.isfinite(res["done"]))
    assert res["done"][-1] > due[-1] + 0.015
    assert sum(c[2] for c in res["calls"]) == len(res["calls"])
    np.testing.assert_array_equal(res["scores"], np.arange(40, dtype=np.float32))
    assert res["late"].size <= len(res["calls"])
