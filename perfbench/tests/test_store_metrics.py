"""CPU tests of the readers of the residency tier's spans and counters
(``metrics/fault_ms.py``, ``upload_ms.py``, ``store_hit_rate.py``,
``fault_pages_per_query.py``) on synthetic ``stats()`` before and after a
window."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness  # noqa: E402

FAULT_MS = harness.load_module("metrics", "fault_ms")
UPLOAD_MS = harness.load_module("metrics", "upload_ms")
HIT_RATE = harness.load_module("metrics", "store_hit_rate")
FAULT_PAGES = harness.load_module("metrics", "fault_pages_per_query")
READERS = [FAULT_MS, UPLOAD_MS, HIT_RATE, FAULT_PAGES]
IDS = ["fault_ms", "upload_ms", "store_hit_rate", "fault_pages_per_query"]


def span(self_s: float, n: int = 1) -> dict:
    return {"n": n, "total_s": 2 * self_s, "self_s": self_s}


BEFORE = {"spans": {
    "sched.tick": span(1.0), "store.fault": span(0.25),
    "store.upload": span(0.5), "store.lookups": 100, "store.hits": 40,
    "store.faults": 60, "store.fault_bytes": 60 * 16384,
    "store.pool_grows": 0}}
AFTER = {"spans": {
    "sched.tick": span(2.0), "store.fault": span(0.45),
    "store.upload": span(0.55), "store.lookups": 300, "store.hits": 190,
    "store.faults": 110, "store.fault_bytes": 110 * 16384,
    "store.pool_grows": 0}}


def ctx(completed: int, before=BEFORE, after=AFTER) -> dict:
    return {"completed": completed, "stats_before": before,
            "stats_after": after}


def test_fault_and_upload_ms_read_the_spans_self_time_per_query():
    assert FAULT_MS.read(ctx(20), "ooc") == pytest.approx(200.0 / 20)
    assert UPLOAD_MS.read(ctx(20), "ooc") == pytest.approx(50.0 / 20)


def test_hit_rate_is_the_share_of_the_windows_lookups_that_hit():
    assert HIT_RATE.read(ctx(20), "ooc") == pytest.approx(75.0)


def test_fault_pages_per_query_is_the_counter_delta_per_query():
    assert FAULT_PAGES.read(ctx(20), "ooc") == pytest.approx(2.5)


def test_a_span_first_met_in_the_window_counts_from_zero():
    before = {"spans": {"sched.tick": span(1.0)}}
    assert FAULT_MS.read(ctx(10, before), "ooc") == pytest.approx(45.0)
    assert FAULT_PAGES.read(ctx(10, before), "ooc") == pytest.approx(11.0)
    assert HIT_RATE.read(ctx(10, before), "ooc") == pytest.approx(
        100.0 * 190 / 300)


@pytest.mark.parametrize("reader", READERS, ids=IDS)
def test_a_program_without_the_store_entries_reads_nothing(reader):
    """The parent of these entries has the recorder but no ``store.*``
    span or counter, and the one before it no recorder at all: the
    readers give nothing and raise nothing."""
    plain = {"spans": {"sched.tick": span(1.0), "sched.rounds": 5}}
    assert reader.read(ctx(20, plain, dict(plain)), "ooc") is None
    bare = {"dispatches": 3}
    assert reader.read(ctx(20, bare, dict(bare)), "ooc") is None


@pytest.mark.parametrize("reader", [FAULT_MS, UPLOAD_MS, FAULT_PAGES],
                         ids=["fault_ms", "upload_ms",
                              "fault_pages_per_query"])
def test_nothing_completed_reads_nothing(reader):
    assert reader.read(ctx(0), "ooc") is None


def test_a_window_without_lookups_has_no_hit_rate():
    assert HIT_RATE.read(ctx(20, AFTER, AFTER), "ooc") is None


def test_the_residency_metrics_are_read_in_the_out_of_core_cell_only():
    spec = json.loads((HERE.parent / "BENCHMARK.json").read_text())
    layer = [m for m in spec["per_layer"] if m["layer"] == "residency tier"]
    assert sorted(m["name"] for m in layer) == [
        "fault_ms.ooc", "fault_pages_per_query.ooc", "store_hit_rate.ooc",
        "upload_ms.ooc"]
    cells = {w["name"]: w for w in spec["workloads"]}
    for m in layer:
        assert m["moves"] == "qps" and m["workloads"] == ["gov2-web-ooc.and"]
        _, cfg, _ = harness.cell_spec(spec, m["workloads"][0])
        assert cfg["server"]["store"] == "memory"
    assert cells["gov2-web-ooc.and"]["chips"] == 1
