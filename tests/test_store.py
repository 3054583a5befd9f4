"""Out-of-core tiered storage gate (DESIGN.md §11).

The differential contract: an engine serving from a bounded admission
cache over a page store (resident budget ~10% of the stream's pages) is
BIT-IDENTICAL to the same engine fully resident — on the host tier, the
jnp paged tier, the pallas kernel tier, and the 1-device shard_map path,
across boolean, ranked top-k, and mixed-codec workloads.

Plus the pins that keep the cache honest:

* the LRU budget holds (evictions happen, the pool never exceeds the
  steady-state bound when per-tick working sets fit it);
* ``swap_index`` gives the new engine a FRESH store/pool while in-flight
  queries finish on the version they pinned;
* the poison pin: after attach, the engine's answers cannot come from
  the in-RAM copies — zeroing ``fi.c`` / the paged leaves and corrupting
  ``res.seq`` leaves every boolean answer exact (the mmap store on disk
  is the only surviving source of stream bytes);
* the paper's §1/§6 I/O bound at page granularity (rehomed from the
  retired ``core/diskindex.py``): retrieving list i touches at most
  ``1 + ceil((l~ - 1) / page_size)`` contiguous pages.
"""

import os

import numpy as np
import pytest

from strategies import adversarial_lists, make_lists

from repro.core.repair import repair_compress
from repro.engine import make_engine
from repro.query import QueryExecutor, naive_eval, rank_oracle
from repro.serve.query_serve import QueryServer
from repro.serve.scheduler import QueryScheduler
from repro.store import (MemoryPageStore, MmapPageStore, ResidentSet,
                         StoreResView, build_page_store, pages_in_spans,
                         resolve_store_kind)

SEED = int(os.environ.get("REPRO_BENCH_SEED", "0"))
PAGE = 128

ENGINE_CONFIGS = ("host", "jnp_paged", "pallas", "sharded")


@pytest.fixture(scope="module")
def srng():
    return np.random.default_rng(SEED + 41)


@pytest.fixture(scope="module")
def slists(srng):
    # big enough that the stream cuts into dozens of 128-symbol pages —
    # a ~10% resident budget then leaves real eviction pressure
    return make_lists(np.random.default_rng(SEED + 17), n_lists=30,
                      universe=4000, min_len=5, max_len=600)


@pytest.fixture(scope="module")
def sres(slists):
    return repair_compress(slists)


@pytest.fixture(scope="module")
def adv_lists():
    return adversarial_lists(np.random.default_rng(SEED + 99),
                             universe=700, n_random=8, max_len=70)


@pytest.fixture(scope="module")
def adv_res(adv_lists):
    return repair_compress(adv_lists)


def _mesh():
    import jax
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _make_engine(name, res, *, store=None, resident_pages=None, codec=None):
    kw = dict(store=store, resident_pages=resident_pages, codec=codec)
    if name == "host":
        return make_engine("host", res, method="lookup", **kw)
    if name == "jnp_paged":
        return make_engine("jnp", res, max_short_len=64, paged=True,
                           page_size=PAGE, **kw)
    if name == "pallas":
        return make_engine("pallas", res, max_short_len=64, interpret=True,
                           page_size=PAGE, **kw)
    if name == "sharded":
        return make_engine("jnp", res, max_short_len=64, paged=True,
                           page_size=PAGE, mesh=_mesh(), **kw)
    raise AssertionError(name)


def _budget(res):
    """~10% of the stream's pages, the ISSUE's out-of-core operating
    point (at least 1)."""
    n = int(np.asarray(res.starts)[-1])
    return max(1, (-(-n // PAGE)) // 10)


def _bool_queries(rng, n_lists, n=24):
    qs = []
    for _ in range(n):
        ts = rng.choice(n_lists, size=int(rng.integers(2, 4)),
                        replace=False)
        qs.append(" AND ".join(str(int(t)) for t in ts))
    for _ in range(n // 3):
        a, b, c = (int(x) for x in rng.choice(n_lists, 3, replace=False))
        qs.append(f"({a} AND {b}) OR NOT {c}")
    return qs


# -- the differential gate ------------------------------------------------


@pytest.mark.parametrize("name", ENGINE_CONFIGS)
def test_outofcore_boolean_bit_identical(name, slists, sres, srng):
    """Bounded-cache serving == fully-resident serving == oracle, for a
    coalesced boolean workload on every engine tier."""
    qs = _bool_queries(np.random.default_rng(SEED + 3), len(slists))
    ref = QueryExecutor(_make_engine(name, sres))
    want = [ref.search(q) for q in qs]
    eng = _make_engine(name, sres, store="mmap",
                       resident_pages=_budget(sres))
    sch = QueryScheduler(eng, batch_window=8)
    got = sch.search_many(qs)
    for q, w, g in zip(qs, want, got):
        np.testing.assert_array_equal(w, g)
        np.testing.assert_array_equal(
            g, naive_eval(ref.plan(q).node, slists, sres.universe))
    if name != "sharded":   # shard_map is its own residency tier
        st = eng.resident.stats()
        assert st["page_faults"] > 0
        assert st["hits"] > 0


@pytest.mark.parametrize("name", ("host", "jnp_paged", "pallas"))
def test_outofcore_topk_bit_identical(name, slists, sres):
    """Ranked top-k through the scheduler: block-max page decodes run
    against the resident pool, scores and order stay exact."""
    rng = np.random.default_rng(SEED + 5)
    bags = [[int(x) for x in rng.choice(len(slists), size=3,
                                        replace=False)]
            for _ in range(8)]
    eng = _make_engine(name, sres, store="mmap",
                       resident_pages=_budget(sres))
    if name != "host":
        eng.score_page_size = PAGE
    sch = QueryScheduler(eng, batch_window=8)
    for bag, r in zip(bags, sch.search_topk_many(bags, 5)):
        od, osc = rank_oracle(slists, sres.universe, bag, 5)
        np.testing.assert_array_equal(r.docs, od)
        np.testing.assert_array_equal(r.scores, osc)


@pytest.mark.parametrize("name", ("host", "jnp_paged", "pallas"))
def test_outofcore_mixed_codec(name, adv_lists, adv_res):
    """Adaptive codec tier out of core: EF/bitmap lanes never touch the
    stream pool, repair lanes fault through it — answers stay exact."""
    qs = _bool_queries(np.random.default_rng(SEED + 7), len(adv_lists),
                       n=16)
    ref = QueryExecutor(_make_engine(name, adv_res, codec="adaptive"))
    want = [ref.search(q) for q in qs]
    eng = _make_engine(name, adv_res, store="mmap", resident_pages=1,
                       codec="adaptive")
    got = QueryScheduler(eng, batch_window=8).search_many(qs)
    for w, g in zip(want, got):
        np.testing.assert_array_equal(w, g)


def test_memory_store_matches_mmap(slists, sres):
    """The two store backends are interchangeable bit-for-bit."""
    qs = _bool_queries(np.random.default_rng(SEED + 9), len(slists), n=12)
    outs = []
    for kind in ("memory", "mmap"):
        eng = _make_engine("jnp_paged", sres, store=kind,
                           resident_pages=_budget(sres))
        outs.append(QueryScheduler(eng, batch_window=8).search_many(qs))
    for a, b in zip(*outs):
        np.testing.assert_array_equal(a, b)


# -- cache-discipline pins ------------------------------------------------


def test_lru_discipline_unit(sres):
    """ResidentSet is a true LRU under its budget: requests that fit
    never grow the pool, eviction removes the least-recently-ensured
    page, and the pages of the CURRENT request are pinned (a request is
    never evicted to make room for itself)."""
    store = build_page_store(sres, kind="mmap", page_size=PAGE)
    assert store.num_pages >= 6
    rs = ResidentSet(store, budget=4)
    rs.ensure([0, 1, 2, 3])
    assert rs.resident_pages == 4 and rs.page_faults == 4
    rs.ensure([1])                       # refresh: 0 is now oldest
    rs.ensure([4])
    st = rs.slot_of_page
    assert st[0] == -1 and st[1] >= 0 and st[4] >= 0    # LRU victim was 0
    assert rs.page_evictions == 1 and rs.resident_pages == 4
    rs.ensure([0, 2, 3, 4])              # full-budget request: self-pinned
    assert rs.pool_grows == 0 and rs.resident_pages == 4
    assert all(st[p] >= 0 for p in (0, 2, 3, 4)) and st[1] == -1
    syms, _ = store.gather([2])
    got, _ = rs.read_span(2 * PAGE, 3 * PAGE)
    np.testing.assert_array_equal(got, syms[0])
    assert 0.0 < rs.hit_rate_window() <= 1.0


def test_engine_pool_stays_bounded(slists, sres):
    """Serving a whole workload at a ~10% budget keeps the pool bounded:
    eviction pressure is real, the resident set never exceeds the pool's
    (possibly correctness-grown) budget, and the pool never balloons to
    the fully-resident size — the out-of-core operating point holds."""
    budget = max(2, _budget(sres))
    eng = _make_engine("jnp_paged", sres, store="mmap",
                       resident_pages=budget)
    sch = QueryScheduler(eng, batch_window=1)   # serial: small tick sets
    qs = _bool_queries(np.random.default_rng(SEED + 11), len(slists))
    sch.search_many(qs)
    st = eng.resident.stats()
    assert st["resident_pages"] <= st["budget"]
    # grows only to the pow2 above the largest single request (a
    # correctness floor, not steady-state drift) — far below the stream
    assert st["budget"] < st["num_pages"]
    assert st["page_evictions"] > 0
    assert 0.0 < st["hit_rate_window"] <= 1.0


def test_tick_working_set_larger_than_budget_grows(slists, sres):
    """Correctness floor: a single merged round needing more pages than
    the budget grows the pool instead of thrashing mid-dispatch."""
    eng = _make_engine("jnp_paged", sres, store="mmap", resident_pages=1)
    rng = np.random.default_rng(SEED + 13)
    lids = rng.integers(0, len(slists), 256).astype(np.int32)
    xs = rng.integers(0, sres.universe, 256).astype(np.int32)
    base = _make_engine("jnp_paged", sres)
    np.testing.assert_array_equal(
        np.asarray(base.next_geq_batch(lids, xs)),
        np.asarray(eng.next_geq_batch(lids, xs)))
    assert eng.resident.stats()["pool_grows"] > 0


def test_swap_index_fresh_pool_and_version_pin(slists, sres, srng):
    """swap_index stands up a new engine with a NEW store + pool (the
    structural (index_version, page) flush); a query in flight across the
    swap finishes on the index it was planned against."""
    lists2 = make_lists(np.random.default_rng(SEED + 23), n_lists=30,
                        universe=4000, min_len=5, max_len=600)
    srv = QueryServer(sres, max_short_len=64, engine="jnp", paged=True,
                      page_size=PAGE, store="mmap",
                      resident_pages=_budget(sres))
    q = "0 AND 1 AND 2"
    qid = srv.submit(q)
    srv.scheduler.tick()                 # in flight, pinned to v0
    old_engine, old_store = srv.engine, srv.engine.store
    res2 = repair_compress(lists2)
    srv.swap_index(res2)
    assert srv.engine is not old_engine
    assert srv.engine.store is not old_store
    assert srv.engine.resident is not old_engine.resident
    srv.scheduler.drain()
    np.testing.assert_array_equal(
        srv.scheduler.take(qid),
        naive_eval(srv.plan(q).node, slists, sres.universe))
    np.testing.assert_array_equal(
        srv.search(q), naive_eval(srv.plan(q).node, lists2,
                                  res2.universe))


@pytest.mark.parametrize("name", ("host", "jnp_paged", "pallas"))
def test_poison_pin_serving_reads_only_the_store(name, slists, sres):
    """After attach, zero every in-RAM copy of the stream the engine
    could cheat from — the answers must still be exact, proving the mmap
    store is the only source of stream bytes (the out-of-core claim)."""
    eng = _make_engine(name, sres, store="mmap",
                       resident_pages=_budget(sres))
    seq_backup = sres.seq.copy()
    try:
        sres.seq[:] = -1
        if hasattr(eng, "fi"):
            assert int(np.asarray(eng.fi.c).size) == 1   # already dropped
            assert int(np.asarray(eng.pi.c_syms_pg).shape[0]) == 1
        qs = _bool_queries(np.random.default_rng(SEED + 29), len(slists),
                           n=10)
        ref = QueryExecutor(_make_engine(name, sres.__class__(
            grammar=sres.grammar, seq=seq_backup, starts=sres.starts,
            first_values=sres.first_values, orig_lengths=sres.orig_lengths,
            universe=sres.universe)))
        got = QueryScheduler(eng, batch_window=8).search_many(qs)
        for q, g in zip(qs, got):
            np.testing.assert_array_equal(
                g, naive_eval(ref.plan(q).node, slists, sres.universe))
    finally:
        sres.seq[:] = seq_backup


# -- store unit tests (incl. the rehomed diskindex coverage) --------------


def test_store_res_view_decodes(slists, sres):
    """StoreResView (the host accessors' read view) decodes every list
    bit-identically to the in-RAM RePairResult."""
    store = build_page_store(sres, kind="mmap", page_size=PAGE)
    view = StoreResView(sres, ResidentSet(store, budget=2))
    for i in range(view.num_lists):
        np.testing.assert_array_equal(view.decode_list(i),
                                      sres.decode_list(i))
        np.testing.assert_array_equal(view.list_symbols(i),
                                      sres.list_symbols(i))


def test_io_optimality_bound(sres):
    """Paper §1/§6 at page granularity (rehomed from core/diskindex):
    retrieving list i touches at most 1 + ceil((l~-1)/P) contiguous
    pages, where l~ is the COMPRESSED length."""
    store = build_page_store(sres, kind="mmap", page_size=PAGE)
    assert store.kind == "mmap"
    assert store.disk_bytes > 0
    for i in range(store.meta["starts"].size - 1):
        lo, hi = store.list_span(i)
        ltilde = hi - lo
        bound = 1 + int(np.ceil(max(ltilde - 1, 0) / PAGE))
        assert store.page_accesses(i) <= bound
        # and the pages are contiguous — the paper's I/O pattern
        pages = store.span_pages(lo, hi)
        if pages.size:
            assert pages[-1] - pages[0] + 1 == pages.size


def test_mmap_store_round_trips(sres, tmp_path):
    """Disk persistence: a store written under an explicit directory
    serves the same pages as the in-memory paging, and one batched
    gather reads many pages at once."""
    mem = build_page_store(sres, kind="memory", page_size=PAGE)
    mm = build_page_store(sres, kind="mmap", page_size=PAGE,
                          store_dir=str(tmp_path))
    assert mm.num_pages == mem.num_pages
    pages = np.arange(mm.num_pages)
    for a, b in zip(mm.gather(pages), mem.gather(pages)):
        np.testing.assert_array_equal(a, b)
    assert mm.pages_gathered == mm.num_pages
    mm.close()


def test_pages_in_spans():
    assert pages_in_spans([0], [1], 128).tolist() == [0]
    assert pages_in_spans([0], [0], 128).tolist() == []        # empty span
    assert pages_in_spans([127], [129], 128).tolist() == [0, 1]
    assert pages_in_spans([0, 700], [5, 800], 128).tolist() == [0, 5, 6]
    assert pages_in_spans([256], [256 + 128], 128).tolist() == [2]


def test_resolve_store_kind_env(monkeypatch):
    assert resolve_store_kind("mmap") == "mmap"
    assert resolve_store_kind("mem") == "memory"
    assert resolve_store_kind("none") is None
    assert resolve_store_kind("") is None
    monkeypatch.setenv("REPRO_STORE", "mmap")
    assert resolve_store_kind(None) == "mmap"
    monkeypatch.setenv("REPRO_STORE", "off")
    assert resolve_store_kind(None) is None
    monkeypatch.delenv("REPRO_STORE")
    assert resolve_store_kind(None) is None
    with pytest.raises(ValueError):
        resolve_store_kind("tape")


def test_scheduler_stats_surface_cache_counters(slists, sres):
    eng = _make_engine("jnp_paged", sres, store="mmap",
                       resident_pages=_budget(sres))
    sch = QueryScheduler(eng, batch_window=4)
    sch.search_many(_bool_queries(np.random.default_rng(SEED + 31),
                                  len(slists), n=8))
    st = sch.stats()
    assert st["page_faults"] > 0
    assert st["fault_bytes"] == st["store"]["fault_bytes"] > 0
    assert st["resident_pages"] >= 1
    assert 0.0 <= st["store_hit_rate"] <= 1.0
    # fully-resident engines report zeros, not KeyErrors (store="" opts
    # out explicitly so a REPRO_STORE env cell cannot re-enable it)
    st0 = QueryScheduler(_make_engine("jnp_paged", sres,
                                      store="")).stats()
    assert st0["page_faults"] == 0 and st0["store"] is None


# -- close-while-serving lifecycle (DESIGN.md §11.6) ----------------------


def test_close_while_pinned_defers_teardown(sres):
    """Regression: MmapPageStore.close() used to rmtree immediately even
    while a ResidentSet held open memmaps over the files.  A close with
    readers pinned must DEFER teardown until the last pin is released."""
    store = build_page_store(sres, kind="mmap", page_size=PAGE)
    path = store.path
    rs = ResidentSet(store, budget=2)
    assert store.pins == 1
    store.close()                      # reader still pinned: defer
    assert not store.closed
    assert os.path.isdir(path)         # backing files still alive
    syms, _ = store.gather(np.asarray([0]))
    assert syms.shape == (1, PAGE)     # reads still served
    rs.ensure(np.asarray([0]))         # the pool can still fault
    rs.release()                       # last pin gone: deferred close fires
    assert store.closed
    assert not os.path.isdir(path)
    with pytest.raises(RuntimeError):
        store.gather(np.asarray([0]))
    rs.release()                       # both idempotent
    store.close()


def test_close_unpinned_is_immediate(sres):
    store = build_page_store(sres, kind="mmap", page_size=PAGE)
    path = store.path
    store.close()
    assert store.closed and not os.path.isdir(path)


def test_pool_gc_releases_pin(sres):
    """Dropping the last reference to a ResidentSet releases its pin via
    the GC finalizer (exactly-once with explicit release())."""
    import gc
    store = build_page_store(sres, kind="mmap", page_size=PAGE)
    rs = ResidentSet(store, budget=2)
    store.close()
    assert not store.closed
    del rs
    gc.collect()
    assert store.closed


def test_inflight_query_across_swap_and_close(slists, sres):
    """Regression (the ISSUE's refresh-path bug): swap_index then close()
    on the OLD index's mmap store while an out-of-core query is still in
    flight on it.  The teardown defers — the query keeps reading pages
    through the close-pending store and completes bit-identically; the
    directory disappears only when the old pool is released."""
    lists2 = make_lists(np.random.default_rng(SEED + 31), n_lists=30,
                        universe=4000, min_len=5, max_len=600)
    srv = QueryServer(sres, max_short_len=64, engine="jnp", paged=True,
                      page_size=PAGE, store="mmap",
                      resident_pages=_budget(sres))
    q = "0 AND 1 AND 2"
    qid = srv.submit(q)
    srv.scheduler.tick()                 # in flight, reading store v0
    old_engine, old_store = srv.engine, srv.engine.store
    path = old_store.path
    res2 = repair_compress(lists2)
    srv.swap_index(res2)
    old_store.close()                    # retire the old index's disk store
    assert not old_store.closed          # deferred: in-flight pool pins it
    assert os.path.isdir(path)
    srv.scheduler.drain()                # remaining rounds read the store
    np.testing.assert_array_equal(
        srv.scheduler.take(qid),
        naive_eval(srv.plan(q).node, slists, sres.universe))
    old_engine.resident.release()        # last reader gone
    assert old_store.closed
    assert not os.path.isdir(path)
    # the new index serves on untouched fresh state
    np.testing.assert_array_equal(
        srv.search(q), naive_eval(srv.plan(q).node, lists2, res2.universe))


# -- the served path over a bounded pool, and its device copy -------------


def _num_pages(res) -> int:
    return -(-int(np.asarray(res.starts)[-1]) // PAGE)


@pytest.mark.parametrize("share", ("one", "quarter", "all"))
def test_served_and_queries_at_every_budget(share, slists, sres):
    """``QueryServer`` on the pallas engine over a memory store, through
    the scheduler with eight clients in flight: every AND answer is the
    intersection of the raw lists, at a budget of one page, a quarter of
    the pages and every page."""
    pages = _num_pages(sres)
    budget = {"one": 1, "quarter": -(-pages // 4), "all": pages}[share]
    srv = QueryServer(sres, engine="pallas", interpret=True,
                      max_short_len=600, page_size=PAGE, store="memory",
                      resident_pages=budget, batch_window=8)
    rng = np.random.default_rng(SEED + 61)
    queries = [sorted(rng.choice(len(slists), int(rng.integers(2, 5)),
                                 replace=False).tolist())
               for _ in range(24)]
    sch, todo, flight, got = srv.scheduler, list(queries), {}, {}
    while todo or flight:
        while todo and len(flight) < 8:     # a client sends its next query
            q = todo.pop()
            flight[srv.submit(" AND ".join(map(str, q)))] = q
        sch.tick()
        for qid in [i for i in flight if i in sch._done]:
            got[tuple(flight.pop(qid))] = sch.take(qid)
    for q in queries:
        want = slists[q[0]]
        for t in q[1:]:
            want = np.intersect1d(want, slists[t])
        np.testing.assert_array_equal(got[tuple(q)], want)
    st = srv.engine.resident.stats()
    assert st["page_faults"] > 0
    assert not any(srv.engine.host_routes.values())
    if share == "one":
        assert st["page_evictions"] > 0
    if share == "all":
        assert st["page_evictions"] == 0


class _Compiles:
    """Executables JAX obtains while the block runs (``jax.monitoring``
    backend compile events, as the benchmark's ``CompileMeter`` counts)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


@pytest.mark.parametrize("budget", (1, 5, 8, 13))
def test_pool_updates_compile_log_many_programs_and_donate(budget):
    """Fault batches of every size from 1 to the budget, in a shuffled
    order and twice over, obtain at most ceil(log2 budget) + 1 programs
    for the pool's device updates and as many for the slot table's.
    Each update donates the array it replaces (no batch copies the pool),
    and the device copy equals the host pool and slot table after each."""
    import math

    # shapes of pool and slot table that no other test compiles
    P, num_pages = 256 + 128 * budget, 997 + 4 * budget
    rng = np.random.default_rng(SEED + 71 + budget)
    syms = rng.integers(0, 1 << 20, (num_pages, P)).astype(np.int32)
    sums = rng.integers(0, 1 << 20, (num_pages, P)).astype(np.int32)
    store = MemoryPageStore(syms, sums, num_pages * P,
                            {"starts": np.zeros(2, np.int64)})
    rs = ResidentSet(store, budget=budget)
    sizes = list(range(1, budget + 1)) * 2
    rng.shuffle(sizes)
    pool_progs = slot_progs = 0
    for n in sizes:
        absent = np.flatnonzero(rs.slot_of_page < 0)
        rs.ensure(rng.choice(absent, n, replace=False))
        old = rs._dev
        with _Compiles() as c:
            dev_syms, dev_sums = rs.device_pool()
        pool_progs += c.n
        if old is not None:
            assert old[0].is_deleted() and old[1].is_deleted()
        old_slots = rs._dev_slots
        with _Compiles() as c:
            _, _, dev_slots = rs.device_tables()
        slot_progs += c.n
        if old_slots is not None:
            assert old_slots.is_deleted()
        np.testing.assert_array_equal(np.asarray(dev_syms)[:, 0],
                                      rs.pool_syms)
        np.testing.assert_array_equal(np.asarray(dev_sums)[:, 0],
                                      rs.pool_sums)
        np.testing.assert_array_equal(np.asarray(dev_slots),
                                      rs.slot_of_page)
    bound = math.ceil(math.log2(budget)) + 1
    assert 1 <= pool_progs <= bound and 1 <= slot_progs <= bound
    assert rs.pool_grows == 0 and rs.page_faults == sum(sizes)


def test_pool_update_buckets():
    from repro.store.resident import update_bucket
    assert [update_bucket(n, 38) for n in (1, 2, 3, 5, 17, 32, 33, 38)] \
        == [1, 2, 4, 8, 32, 32, 38, 38]
    assert {update_bucket(n, 38) for n in range(1, 39)} == \
        {1, 2, 4, 8, 16, 32, 38}
    assert update_bucket(1, 1) == 1


@pytest.mark.parametrize("name", ("jnp_paged", "pallas"))
def test_swap_onto_a_fresh_store_compiles_nothing(name, slists, sres):
    """``swap_index`` builds a new store over the same pages.  A paged
    index compares its store by kind and geometry in its pytree aux data,
    so the swapped-in engine serves the same queries with the programs
    already compiled, and gives the same answers."""
    import jax
    engine = dict(jnp_paged=dict(engine="jnp", paged=True),
                  pallas=dict(engine="pallas", interpret=True))[name]
    srv = QueryServer(sres, max_short_len=64, page_size=PAGE,
                      store="memory",
                      resident_pages=-(-_num_pages(sres) // 4), **engine)
    qs = _bool_queries(np.random.default_rng(SEED + 83), len(slists))
    first = srv.search_many(qs)
    old = srv.engine
    srv.swap_index(sres)
    assert srv.engine.store is not old.store
    assert (jax.tree.structure(srv.engine.pi)
            == jax.tree.structure(old.pi))
    with _Compiles() as c:
        again = srv.search_many(qs)
    assert c.n == 0
    for q, a, b in zip(qs, first, again):
        np.testing.assert_array_equal(a, b, err_msg=q)
        np.testing.assert_array_equal(
            b, naive_eval(srv.plan(q).node, slists, sres.universe))
    assert srv.engine.resident.stats()["page_faults"] > 0
