"""CPU tests of the readers of the program's spans and counters
(``metrics/host_ms.py``, ``metrics/rounds_per_query.py``) on synthetic
``stats()`` before and after a window."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

HOST_MS = harness.load_module("metrics", "host_ms")
ROUNDS = harness.load_module("metrics", "rounds_per_query")


def span(self_s: float, n: int = 1) -> dict:
    return {"n": n, "total_s": self_s, "self_s": self_s}


BEFORE = {"dispatches": 3, "spans": {
    "sched.submit": span(0.5), "sched.tick": span(1.0),
    "engine.lanes": span(0.25), "kernel.route": span(0.125),
    "device.wait": span(2.0), "sched.rounds": 10}}
AFTER = {"dispatches": 9, "spans": {
    "sched.submit": span(0.7), "sched.tick": span(1.5),
    "sched.advance": span(0.4), "engine.lanes": span(0.45),
    "kernel.route": span(0.225), "kernel.launch": span(0.05),
    "device.wait": span(3.0), "sched.rounds": 50}}


def ctx(completed: int, before=BEFORE, after=AFTER) -> dict:
    return {"completed": completed, "stats_before": before,
            "stats_after": after}


@pytest.mark.parametrize("layer, want_ms", [
    ("plan", 200.0 / 20), ("sched", 500.0 / 20),
    ("advance", 400.0 / 20), ("lanes", 200.0 / 20),
    ("route", (100.0 + 50.0) / 20), ("wait", 1000.0 / 20), ("gc", 0.0)])
@pytest.mark.parametrize("cell", ["and", "top10"])
def test_host_ms_reads_the_layers_self_time_per_query(layer, want_ms, cell):
    got = HOST_MS.read(ctx(20), f"{layer}.{cell}")
    assert got == pytest.approx(want_ms, rel=1e-12)


def test_rounds_per_query_is_the_counter_delta_per_query():
    assert ROUNDS.read(ctx(20), "and") == pytest.approx(2.0)
    assert ROUNDS.read(ctx(8), "top10") == pytest.approx(5.0)


@pytest.mark.parametrize("reader", [HOST_MS, ROUNDS],
                         ids=["host_ms", "rounds_per_query"])
def test_nothing_completed_reads_nothing(reader):
    suffix = "wait.and" if reader is HOST_MS else "and"
    assert reader.read(ctx(0), suffix) is None


@pytest.mark.parametrize("reader", [HOST_MS, ROUNDS],
                         ids=["host_ms", "rounds_per_query"])
def test_a_program_without_the_recorder_reads_nothing(reader):
    """The parent of the recorder has no ``spans`` in its stats: the
    readers give nothing and raise nothing."""
    plain = {"dispatches": 3}
    suffix = "sched.top10" if reader is HOST_MS else "top10"
    assert reader.read(ctx(20, plain, dict(plain)), suffix) is None


def test_every_span_metric_names_a_layer_the_reader_knows():
    spec = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    names = [m["name"] for m in spec["per_layer"]]
    host = [n for n in names if n.startswith("host_ms.")]
    assert len(host) == 14
    for n in host:
        layer, cell = n.split(".")[1:]
        assert layer in HOST_MS.SPANS and cell in ("and", "top10")
    assert {"rounds_per_query.and", "rounds_per_query.top10"} <= set(names)
