"""The span-and-counter recorder (``repro.obs``): nesting and self time on
a fake clock, the per-thread stack, counters and snapshot deltas, the
``REPRO_SPANS=0`` no-op, Python's collector as ``py.gc``, and one
scheduler workload whose spans account for its ticks and appear as host
events in a ``jax.profiler`` trace."""

from __future__ import annotations

import gc
import glob
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from strategies import adversarial_lists, random_ast

from repro import obs
from repro.core.repair import repair_compress
from repro.engine import PallasEngine
from repro.kernels import LAUNCHES
from repro.query import naive_eval
from repro.serve.scheduler import QueryScheduler


class FakeClock:
    """Seconds that move only when the test says so."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def delta(after: dict, before: dict, name: str, key: str = "self_s"):
    a, b = after.get(name), before.get(name)
    if isinstance(a, dict):
        return a[key] - (b[key] if b else 0)
    return (a or 0) - (b or 0)


def test_nesting_and_self_time_on_a_fake_clock():
    clock = FakeClock()
    rec = obs.Recorder(clock=clock)
    with rec.span("outer", tick=1) as outer:
        clock.advance(1.0)
        with rec.span("inner") as inner:
            clock.advance(2.0)
            with rec.span("leaf"):
                clock.advance(0.5)
        with rec.span("inner"):
            clock.advance(3.0)
        clock.advance(0.25)
    snap = rec.snapshot()
    assert outer.seconds == 6.75 and inner.seconds == 2.5
    assert snap["outer"] == {"n": 1, "total_s": 6.75, "self_s": 1.25}
    assert snap["inner"] == {"n": 2, "total_s": 5.5, "self_s": 5.0}
    assert snap["leaf"] == {"n": 1, "total_s": 0.5, "self_s": 0.5}


def test_span_records_when_its_block_raises():
    clock = FakeClock()
    rec = obs.Recorder(clock=clock)
    with pytest.raises(ValueError):
        with rec.span("outer"):
            with rec.span("inner"):
                clock.advance(1.0)
                raise ValueError("poisoned")
    with rec.span("after"):
        clock.advance(2.0)
    snap = rec.snapshot()
    assert snap["outer"]["self_s"] == 0.0 and snap["inner"]["n"] == 1
    assert snap["after"] == {"n": 1, "total_s": 2.0, "self_s": 2.0}


def test_each_thread_keeps_its_own_stack():
    """A span on a second thread, opened while the main thread is inside
    a span, is nobody's child and takes no time from the main span."""
    clock = FakeClock()
    rec = obs.Recorder(clock=clock)
    opened, release = threading.Event(), threading.Event()

    def worker():
        with rec.span("thread.work"):
            opened.set()
            release.wait(10)

    with rec.span("main.work"):
        t = threading.Thread(target=worker)
        t.start()
        assert opened.wait(10)
        clock.advance(4.0)
        release.set()
        t.join(10)
        clock.advance(1.0)
    snap = rec.snapshot()
    assert snap["main.work"] == {"n": 1, "total_s": 5.0, "self_s": 5.0}
    assert snap["thread.work"] == {"n": 1, "total_s": 4.0, "self_s": 4.0}


def test_counters_and_snapshot_deltas():
    clock = FakeClock()
    rec = obs.Recorder(clock=clock)
    rec.count("rounds", 3)
    with rec.span("tick"):
        clock.advance(1.0)
    before = rec.snapshot()
    rec.count("rounds", 4)
    rec.count("launch.k")
    with rec.span("tick"):
        clock.advance(2.0)
    after = rec.snapshot()
    assert before["rounds"] == 3 and after["rounds"] == 7
    assert delta(after, before, "rounds") == 4
    assert delta(after, before, "launch.k") == 1
    assert delta(after, before, "tick") == 2.0
    assert delta(after, before, "tick", "n") == 1
    assert rec.counter("rounds") == 7 and rec.counter("never") == 0
    assert rec.counters("launch.") == {"launch.k": 1}
    # a snapshot is a copy: later spans do not move it
    assert before["tick"]["total_s"] == 1.0


def test_off_recorder_is_a_shared_no_op():
    rec = obs.Recorder(enabled=False)
    a, b = rec.span("x", tick=1), rec.span("y")
    assert a is b
    with a as s:
        pass
    assert s.seconds == 0.0
    rec.watch_gc()
    assert rec._on_gc not in gc.callbacks
    rec.count("c", 2)
    assert rec.snapshot() == {"c": 2}


def test_repro_spans_0_is_read_at_import():
    """With ``REPRO_SPANS=0`` the process's spans record nothing, enter
    no annotation and watch no collection; counters still count."""
    code = (
        "import gc\n"
        "from repro import obs, kernels\n"
        "assert not obs.ENABLED\n"
        "with obs.span('sched.tick', tick=1) as s:\n"
        "    gc.collect()\n"
        "assert s.seconds == 0.0\n"
        "assert obs.RECORDER._on_gc not in gc.callbacks\n"
        "kernels.count_launch('list_intersect', False)\n"
        "assert obs.snapshot() == {'launch.list_intersect': 1}, "
        "obs.snapshot()\n"
        "assert kernels.LAUNCHES['list_intersect'] == 1\n"
        "print('off ok')\n")
    env = dict(os.environ, REPRO_SPANS="0", JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr
    assert "off ok" in p.stdout


def test_gc_pause_is_recorded_as_py_gc():
    """A collection is the span ``py.gc``; inside another span it is that
    span's child.  The clock moves one second per reading."""
    ticks = iter(range(10_000))
    rec = obs.Recorder(clock=lambda: float(next(ticks)))
    was = gc.isenabled()
    gc.disable()            # only the collection below
    rec.watch_gc()
    try:
        with rec.span("sched.tick"):
            a, b = [], []
            a.append(b)
            b.append(a)
            del a, b
            gc.collect()
    finally:
        rec.unwatch_gc()
        if was:
            gc.enable()
    snap = rec.snapshot()
    assert snap["py.gc"]["n"] >= 1
    # one collection: tick opens at 0, py.gc 1..2, tick closes at 3
    if snap["py.gc"]["n"] == 1:
        assert snap["py.gc"]["total_s"] == 1.0
        assert snap["sched.tick"] == {"n": 1, "total_s": 3.0,
                                      "self_s": 2.0}


def test_process_recorder_watches_the_collector():
    before = obs.snapshot()
    gc.collect()
    assert delta(obs.snapshot(), before, "py.gc", "n") >= 1


def test_launches_read_the_launch_counters():
    before = LAUNCHES["pair_count[interpret]"]
    from repro.kernels import count_launch
    count_launch("pair_count", True)
    assert LAUNCHES["pair_count[interpret]"] == before + 1
    assert "pair_count[interpret]" in LAUNCHES
    assert dict(LAUNCHES)["pair_count[interpret]"] == before + 1
    assert LAUNCHES["no_such_kernel"] == 0
    assert "no_such_kernel" not in LAUNCHES


# -- the served path ----------------------------------------------------------

TICK_CHILDREN = ("sched.advance", "sched.dispatch", "engine.lanes",
                 "kernel.route", "kernel.launch", "device.wait")


@pytest.fixture(scope="module")
def served():
    lists = adversarial_lists(np.random.default_rng(707), universe=600,
                              n_random=6, max_len=60)
    rng = np.random.default_rng(708)
    queries = [random_ast(rng, len(lists)) for _ in range(8)]
    return lists, repair_compress(lists), queries


def _run(served, trace_dir=None):
    """The workload through a fresh scheduler; returns the answers, the
    queries' in-flight records and the recorder's snapshots around it."""
    lists, res, queries = served
    # a fresh engine: its probe memo holds no answer yet
    eng = PallasEngine(res, max_short_len=64, interpret=True)
    sch = QueryScheduler(eng, batch_window=4, result_cache_size=0)
    gc.disable()            # no collection inside the spans compared
    before = sch.stats()["spans"]
    # svs: every probe round launches the list_intersect kernel
    qids = [sch.submit(q, force_algo="svs") for q in queries]
    flights = list(sch._queue)
    if trace_dir is not None:
        import jax
        jax.profiler.start_trace(trace_dir)
    try:
        sch.drain()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        gc.enable()
    after = sch.stats()["spans"]
    outs = [sch.take(q) for q in qids]
    for q, got in zip(queries, outs):
        np.testing.assert_array_equal(got, naive_eval(q, lists,
                                                      res.universe))
    return flights, before, after


def test_mxu_lookup_counter_counts_each_launch(served):
    """One interpret-mode launch of each kernel whose table lookups run on
    the MXU raises its ``gather.mxu.<kernel>`` counter by one, beside
    ``launch.<kernel>``; a kernel without table lookups raises none."""
    from repro.core.jax_index import build_flat_index, build_score_index
    from repro.kernels import MXU_PREFIX, count_launch
    from repro.kernels.list_intersect.ops import next_geq

    _, res, _ = served
    eng = PallasEngine(res, max_short_len=64, interpret=True, page_size=128)
    eng.set_score_index(build_score_index(res, page_size=128))
    flat = build_flat_index(res)
    one = np.zeros(1, np.int32)
    launches = {
        "list_intersect": lambda: next_geq(flat, one, one, interpret=True),
        "page_score": lambda: eng.decode_page_batch(one),
        "pair_count": lambda: count_launch("pair_count", True),
    }
    for kernel, launch in launches.items():
        name = kernel + "[interpret]"
        before = LAUNCHES[name], obs.counter(MXU_PREFIX + name)
        launch()
        mxu = kernel != "pair_count"
        assert (LAUNCHES[name], obs.counter(MXU_PREFIX + name)) == (
            before[0] + 1, before[1] + mxu), kernel


def test_scheduler_spans_account_for_each_tick(served):
    flights, before, after = _run(served)
    ticks = delta(after, before, "sched.tick", "n")
    assert ticks >= 2
    for name in TICK_CHILDREN:
        assert delta(after, before, name, "n") > 0, name
    # every span below the tick runs inside it: their total time is
    # the tick's, less the tick's own self time
    inner = sum(delta(after, before, n) for n in TICK_CHILDREN)
    total = delta(after, before, "sched.tick", "total_s")
    own = delta(after, before, "sched.tick")
    assert inner + own == pytest.approx(total, rel=1e-9, abs=1e-9)
    assert delta(after, before, "sched.submit", "n") == len(flights)
    rounds = sum(fl.rounds for fl in flights)
    assert rounds > 0
    assert delta(after, before, "sched.rounds") == rounds


def test_scheduler_spans_are_host_events_in_a_profile(served, tmp_path):
    from jax.profiler import ProfileData

    _run(served, str(tmp_path))
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    names = set()
    ticks = set()
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.add(ev.name)
                if ev.name == "sched.tick":
                    ticks.update(v for k, v in ev.stats if k == "tick")
    assert {"sched.tick", *TICK_CHILDREN} <= names
    assert len(ticks) >= 2


# -- the residency tier -------------------------------------------------------

STORE_COUNTERS = {"store.lookups": "lookups", "store.hits": "hits",
                  "store.faults": "page_faults",
                  "store.fault_bytes": "fault_bytes",
                  "store.pool_grows": "pool_grows"}


@pytest.mark.parametrize("budget", [1, 2])
def test_store_counters_equal_the_pools_own_fields(served, budget):
    """After a window served out of core, the recorder's ``store.*``
    counters have moved by exactly the pool's own fields (a fresh pool
    starts from zero), and the spans ``store.fault`` and ``store.upload``
    are in ``stats()["spans"]``."""
    lists, res, queries = served
    before = obs.snapshot()
    eng = PallasEngine(res, max_short_len=64, interpret=True,
                       page_size=128, store="memory", resident_pages=budget)
    sch = QueryScheduler(eng, batch_window=4, result_cache_size=0)
    for q, got in zip(queries, sch.search_many(queries)):
        np.testing.assert_array_equal(got, naive_eval(q, lists,
                                                      res.universe))
    after = sch.stats()["spans"]
    st = eng.resident.stats()
    for counter, field in STORE_COUNTERS.items():
        assert delta(after, before, counter) == st[field], counter
    assert st["page_faults"] > 0 and st["lookups"] > st["hits"]
    for name in ("store.fault", "store.upload"):
        assert delta(after, before, name, "n") > 0, name
