"""Open-loop latency is taken from the scheduled send time: a stall in the
server delays the queries sent behind it, and that shows in the tail even
though each of them is served quickly once it is sent."""

from __future__ import annotations

import sys
from pathlib import Path

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import drive


class FakeServer:
    """Answers each query after ``service_s``; a query of kind ``stall``
    holds the sending thread for ``stall_s`` first, as a flush run on the
    submitting thread does."""

    def __init__(self, service_s=0.002, stall_s=0.0):
        self.service_s, self.stall_s = service_s, stall_s

    def submit(self, corpus, kind, params, deadline):
        if kind == "stall":
            time.sleep(self.stall_s)
        fut = Future()
        threading.Timer(self.service_s, fut.set_result,
                        args=(np.arange(3, dtype=np.float32),)).start()
        return fut


def _schedule(n=60, gap=0.01, stall_at=None):
    return [(i * gap, ("c0", "stall" if i == stall_at else "q", {}), None)
            for i in range(n)]


def test_percentiles_are_samples():
    v = np.array([5.0, 1.0, 3.0, 2.0, 4.0])
    assert drive.percentile(v, 50) == 3.0
    assert drive.percentile(v, 95) == 5.0
    assert drive.percentile(np.array([1.0, np.inf]), 95) == np.inf


def test_stall_moves_p95():
    base = drive.run_open(FakeServer().submit, _schedule(), 0.6)
    stalled = drive.run_open(FakeServer(stall_s=0.25).submit,
                             _schedule(stall_at=10), 0.6)
    p95 = [drive.percentile(drive.latencies_ms(w), 95)
           for w in (base, stalled)]
    assert p95[0] < 100
    # about 25 queries were due during the 250 ms stall: timed from their
    # send they would look fast, timed from their due time they are late
    assert p95[1] > 100
    assert drive.sender_lag_ms(stalled).max() > 150
    assert all(o.digest is not None for o in stalled.outcomes)


def test_unanswered_is_infinitely_late():
    w = drive.Window([drive.Outcome("c", "k", {}, 0.0, done=0.1),
                      drive.Outcome("c", "k", {}, 0.0, done=None),
                      drive.Outcome("c", "k", {}, 0.0, done=0.05,
                                    error="DeadlineExceeded")], 0, 1, 1)
    lat = drive.latencies_ms(w)
    assert lat[0] == pytest.approx(100.0)
    assert np.isinf(lat[1:]).all()


def test_closed_loop_rate_counts_the_window():
    srv = FakeServer(service_s=0.01)
    streams = [iter([[("c0", "q", {})]] * 10_000) for _ in range(4)]
    w = drive.run_closed(srv.submit, streams, 0.5)
    n = drive.answered_by(w, w.stop)
    # four clients, about 10 ms a query, for half a second
    assert 80 < n <= 4 * 50
    assert all(o.done is not None for o in w.outcomes)
