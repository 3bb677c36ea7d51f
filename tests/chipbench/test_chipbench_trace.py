"""The reduction from a profiler trace to busy time, idle share, module
time and labelled idle gaps, on a small trace recorded on a TPU v5e (a few
served queries of every kind, under a host span ``probe.window``)."""

from __future__ import annotations

import sys

import gzip
from pathlib import Path

import pytest

# the benchmark is a package at the root of the checkout
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench import trace

DATA = Path(__file__).resolve().parent / "data" / "probe.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    pd = ProfileData.from_serialized_xspace(gzip.decompress(DATA.read_bytes()))
    window = [(e.start_ns, e.start_ns + e.duration_ns)
              for p in pd.planes if p.name.startswith("/host:")
              for line in p.lines for e in line.events
              if e.name == "probe.window"]
    return trace.events(pd), window


def test_recorded_trace_reduces(recorded):
    (devices, spans), window = recorded
    assert list(devices) == ["/device:TPU:0"]
    d = devices["/device:TPU:0"]
    assert len(d.ops) == 1403 and len(d.modules) == 115
    s = trace.summarize(devices, spans, chips=1, window=window[0])
    assert s.window_s == pytest.approx(0.084051416)
    # the union of operation intervals: nested ops are not counted twice
    assert 0 < s.busy_s < s.window_s
    assert s.busy_s <= sum(m for m in s.module_s.values()) + 1e-9
    assert s.idle_share == pytest.approx(1 - s.busy_s / s.window_s)
    assert s.busy_s == pytest.approx(0.010902331)
    # modules grouped by name without the fingerprint
    top = max(s.module_s, key=s.module_s.get)
    assert top == "jit__frontier_fused_impl"
    assert s.module_s[top] == pytest.approx(0.005480183)
    # the idle time is all the window's time that no op covers
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s)
    b = trace.breakdown(s)
    assert b["device_ops"][0][0] == top and len(b["device_ops"]) <= 10


def test_union_and_gap_labels():
    ms = 1e6
    devices = {"/device:TPU:0": trace.DeviceEvents(
        ops=[(1 * ms, 3 * ms), (2 * ms, 4 * ms), (6 * ms, 7 * ms),
             (6.5 * ms, 6.8 * ms)],
        modules=[("jit_a(1)", 1 * ms, 4 * ms), ("jit_b(2)", 6 * ms, 7 * ms)])}
    spans = [(trace.WINDOW_SPAN, 0, 10 * ms),
             ("chipbench.flush", 3.5 * ms, 9 * ms),
             ("chipbench.pack_build", 4 * ms, 6 * ms)]
    s = trace.summarize({k: trace.DeviceEvents(v.ops, [
        (trace.module_name(n), a, b) for n, a, b in v.modules])
        for k, v in devices.items()}, spans, chips=1)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.004)          # [1,4] and [6,7]
    assert s.idle_share == pytest.approx(0.6)
    assert s.module_s == pytest.approx({"jit_a": 0.003, "jit_b": 0.001})
    gaps = dict(s.idle_gaps)
    assert gaps["chipbench.pack_build"] == pytest.approx(0.002)   # 4-6
    assert gaps["chipbench.flush"] == pytest.approx(0.003)        # 7-10
    assert gaps[trace.NO_SPAN] == pytest.approx(0.001)            # 0-1


def test_window_span_required():
    with pytest.raises(ValueError):
        trace.summarize({}, [], chips=1)
