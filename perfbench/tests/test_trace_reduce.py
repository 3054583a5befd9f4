"""Tests of the trace reduction: the busy/idle union, per-kernel sums and
named idle gaps on small traces kept beside this file, and the reader on
a trace recorded here."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"
HOST = ("/host:CPU", "python3")


def ev(plane, line, name, t0_ms, dur_ms):
    return (plane, line, name, t0_ms * 1e6, dur_ms * 1e6)


def small_trace():
    """A 100 ms window: the host ticks from 10 to 60 ms and waits from 60
    to 100; the device runs two overlapping ``list_intersect`` launches
    and one other op, and one op that began before the window."""
    return [
        ev(*HOST, tr.WINDOW_SPAN, 0, 100),
        ev(*HOST, "bench.tick", 10, 50),
        ev(*HOST, "bench.submit", 2, 3),
        ev(*HOST, "bench.wait", 60, 40),
        ev(DEV, tr.OPS_LINE, "%_paged_call.1 = s32[1,128] custom-call(s32[1])",
           20, 10),
        ev(DEV, tr.OPS_LINE, "%_paged_call = s32[1,256] custom-call(s32[2])",
           25, 10),
        ev(DEV, tr.OPS_LINE, "%fusion.3 = s32[8] fusion(s32[8] %a)", 50, 5),
        ev(DEV, tr.OPS_LINE, "%copy-start.2 = (s32[16]) copy-start()",
           -10, 15),
        ev(DEV, "XLA Modules", "jit__paged_call", 20, 15),
    ]


def test_union_clipping_kernels_and_gaps():
    red = tr.reduce(small_trace())
    assert red["window_s"] == pytest.approx(0.1)
    # busy: [0, 5] clipped + [20, 35] union + [50, 55] = 25 ms
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["kernels"] == {"list_intersect": pytest.approx(0.020)}
    gaps = red["idle_gaps"]
    assert [round(s, 6) for _, s in gaps] == [0.045, 0.015, 0.015]
    # the longest gap (55-100 ms) is named by the host's wait; the
    # 5-20 ms and 35-50 ms gaps fall inside the tick
    assert gaps[0][0] == "bench.wait"
    assert {g[0] for g in gaps[1:]} == {"bench.tick"}
    ops = dict(red["device_ops"])
    assert ops == {"_paged_call": pytest.approx(0.020),
                   "fusion": pytest.approx(0.005),
                   "copy-start": pytest.approx(0.005)}


def test_no_window_or_no_device_op_reads_nothing():
    events = small_trace()
    assert tr.reduce(events[1:]) is None
    assert tr.reduce([e for e in events if e[0] != DEV]) is None


def test_kernel_table_maps_names():
    assert tr.kernel_of("%_call.1 = s32[128,1,2048] custom-call(s32[128] "
                        "%copy-done)") == "page_score"
    assert tr.kernel_of("%_paged_call = s32[1,128] custom-call()") == \
        "list_intersect"
    assert tr.kernel_of("%fusion.12 = s32[8] fusion()") is None
    assert tr.kernel_of("%_call_other.1 = s32[8] fusion()") is None
    assert tr.short_name("jit__paged_call(2121308863241874769)") == \
        "jit__paged_call(2121308863241874769)"


FIXTURES = sorted(HERE.glob("trace_*.json"))


@pytest.mark.parametrize("path", FIXTURES, ids=[p.stem for p in FIXTURES])
def test_recorded_chip_trace(path):
    """A slice of a trace recorded on the chip, with the totals its
    reduction gave when it was recorded."""
    fx = json.loads(path.read_text())
    red = tr.reduce([tuple(e) for e in fx["events"]])
    want = fx["expected"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    for k, v in want["kernels"].items():
        assert red["kernels"][k] == pytest.approx(v, rel=1e-9)
    assert 0 < red["busy_s"] <= red["window_s"]


def test_reads_a_trace_recorded_here(tmp_path):
    """The loader on a real ``.xplane.pb``: the window span is found; a
    CPU run has no device ops, so the reduction reads nothing."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = tr.load_events(tr.find_xplane(str(tmp_path)))
    assert any(e[2] == tr.WINDOW_SPAN for e in events)
    assert tr.reduce(events) is None
