"""The trace reduction, on a trimmed chip trace.

``data/trace_fit.json.gz`` holds the events of a traced warm
``KMedoids(k=10, solver="banditpam_pp")`` fit of ``mnist_like(10000)`` on
a TPU v5 lite, as
``trace.load_events`` read them, trimmed to the 50 ms after the first
device operation: every event that overlaps those 50 ms is kept, and a
span event :data:`trace.SPAN` covering them is added (the trace was
taken before the harness wrote its own span).  Each number of
``trace.reduce`` is checked here against a plain recount.
"""

import gzip
import json
import os

import numpy as np
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_fit.json.gz")


@pytest.fixture(scope="module")
def events():
    with gzip.open(DATA, "rt") as f:
        return [trace.Event(*e) for e in json.load(f)]


def _span(events):
    (span,) = [e for e in events if e.name == trace.SPAN]
    return span.start_ns, span.start_ns + span.dur_ns


def _device_ops(events, lo, hi):
    return [e for e in events if e.plane.startswith(trace.DEVICE_PREFIX)
            and e.line == trace.OPS_LINE
            and e.start_ns < hi and e.start_ns + e.dur_ns > lo]


def test_layout_is_as_documented(events):
    planes = {e.plane for e in events}
    assert "/device:TPU:0" in planes and "/host:CPU" in planes
    lines = {e.line for e in events if e.plane == "/device:TPU:0"}
    assert {"XLA Ops", "XLA Modules"} <= lines
    assert any(trace.PALLAS_MARK in e.name for e in events)


def test_busy_and_idle_match_a_recount(events):
    lo, hi = _span(events)
    got = trace.reduce(events)
    us = np.zeros(-(-(hi - lo) // 1000), bool)       # 1 us raster
    for e in _device_ops(events, lo, hi):
        a = max(e.start_ns, lo) - lo
        b = min(e.start_ns + e.dur_ns, hi) - lo
        us[a // 1000:-(-b // 1000)] = True
    assert got["window_s"] == pytest.approx((hi - lo) / 1e9)
    assert got["busy_s"] == pytest.approx(us.sum() * 1e-6, rel=0.02)
    assert got["idle_share"] == pytest.approx(
        1 - got["busy_s"] / got["window_s"])
    assert 0 < got["busy_s"] <= got["window_s"]


def test_pallas_time_counts_innermost_kernels(events):
    lo, hi = _span(events)
    ops = _device_ops(events, lo, hi)
    want = 0
    for e in ops:
        end = e.start_ns + e.dur_ns
        inner = any(o is not e and e.start_ns <= o.start_ns
                    and o.start_ns + o.dur_ns <= end
                    and o.dur_ns < e.dur_ns for o in ops)
        if trace.PALLAS_MARK in e.name and not inner:
            want += min(end, hi) - max(e.start_ns, lo)
    got = trace.reduce(events)
    assert want > 0
    assert got["pallas_s"] == pytest.approx(want / 1e9)
    assert got["pallas_s"] <= got["busy_s"]


def test_breakdowns(events):
    got = trace.reduce(events)
    assert 0 < len(got["device_ops"]) <= 10
    assert 0 < len(got["idle_gaps"]) <= 10
    names = [n for n, _ in got["device_ops"]]
    assert all("=" not in n and not n.startswith("%") for n in names)
    lo, hi = _span(events)
    idle = sum(s for _, s in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9


def test_op_name():
    assert trace.op_name("%fusion.12 = f32[8] fusion(x)") == "fusion"
    assert trace.op_name(
        "%stream_top2_kernel.1 = (f32[1,256]) custom-call(a)") == \
        "stream_top2_kernel"
    assert trace.op_name("%copy = f32[2] copy(x)") == "copy"


def test_no_span_or_no_device_reads_nothing(events):
    assert trace.reduce([e for e in events if e.name != trace.SPAN]) is None
    host = [e for e in events if not e.plane.startswith(trace.DEVICE_PREFIX)]
    assert trace.reduce(host) is None
