"""Hot-path dedup differential gates (DESIGN.md §13).

Cross-query lane dedup and overlapped page prefetch are both REQUIRED
to be invisible in results: every (dedup, prefetch) on/off combination
must return bit-identical answers to the serial dispatch-every-lane
path, on every engine configuration —
host / jnp flat / jnp paged / pallas(interpret) / 1-device-mesh
shard_map — across boolean, ranked top-k, mixed-codec, out-of-core
(~10% resident budget) and segmented-ingest serving.

Plus the behaviour pins: ``swap_index`` serves the new index's
answers, insert-epoch correctness on the segmented tier, no lane answer
survives a tick (a replayed workload dispatches every unique lane
again), the prefetch thread is joined before its pages are touched (and
never outlives a drained workload), and a crafted duplicate-heavy
workload must show ``dedup_factor > 1`` with a SHRUNK pow2 dispatch
bucket versus the dedup-off path.
"""

import os
import time

import numpy as np
import pytest

from strategies import adversarial_lists, random_ast

from repro.core.cache import LRUCache
from repro.core.repair import repair_compress
from repro.engine import HostEngine, JnpEngine, PallasEngine, make_engine
from repro.query import And, QueryExecutor, Term, naive_eval, rank_oracle
from repro.serve.scheduler import QueryScheduler

SEED = int(os.environ.get("REPRO_BENCH_SEED", "0"))
PAGE = 128
ENGINE_CONFIGS = ("host", "jnp", "jnp_paged", "pallas")


@pytest.fixture(scope="module")
def dlists():
    return adversarial_lists(np.random.default_rng(SEED + 99),
                             universe=700, n_random=8, max_len=70)


@pytest.fixture(scope="module")
def dres(dlists):
    return repair_compress(dlists)


def _make(name, res, **kw):
    if name == "host":
        return HostEngine(res, **kw)
    if name == "jnp":
        return JnpEngine(res, max_short_len=64, **kw)
    if name == "jnp_paged":
        return JnpEngine(res, max_short_len=64, paged=True,
                         page_size=PAGE, **kw)
    if name == "pallas":
        return PallasEngine(res, max_short_len=64, interpret=True, **kw)
    raise AssertionError(name)


def _off(eng):
    """Disable lane dedup on an engine: the serial dispatch-every-lane
    path."""
    eng.dedup = False
    return eng


def _on(eng):
    """Force dedup ON regardless of the env knob — the CI `dedup-off`
    cell runs this whole file, so tests that assert dedup ENGAGES must
    pin their own configuration."""
    eng.dedup = True
    return eng


def _workload(num_lists, n, seed_off=0):
    rng = np.random.default_rng(SEED + 31 + seed_off)
    return [random_ast(rng, num_lists) for _ in range(n)]


# -- the differential gate: every knob combination ---------------------------

@pytest.mark.parametrize("ename", ENGINE_CONFIGS)
def test_dedup_memo_bit_identity(dlists, dres, ename):
    """dedup-on ≡ dedup-off ≡ serial search ≡ oracle, per lane, on every
    backend.  The workload repeats queries so dedup provably engages."""
    n = 8 if ename == "pallas" else 16
    queries = _workload(len(dlists), n) * 2          # repeats across ticks
    base = _off(_make(ename, dres))
    serial = [QueryExecutor(base).search(q) for q in queries]
    for label, setup in (("dedup-on", _on), ("dedup-off", _off)):
        eng = setup(_make(ename, dres))
        sch = QueryScheduler(eng, batch_window=8, result_cache_size=0)
        for q, got, want in zip(queries, sch.search_many(queries), serial):
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"{ename}/{label}")
            np.testing.assert_array_equal(
                got, naive_eval(q, dlists, dres.universe),
                err_msg=f"{ename}/{label}")


def test_sharded_dispatch_bit_identity(dlists, dres):
    """The deduped rounds ride the shard_map dispatch path."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    queries = _workload(len(dlists), 10, seed_off=1) * 2
    eng = JnpEngine(dres, max_short_len=64, mesh=mesh)
    sch = QueryScheduler(eng, batch_window=8, result_cache_size=0)
    for q, got in zip(queries, sch.search_many(queries)):
        np.testing.assert_array_equal(
            got, naive_eval(q, dlists, dres.universe))
    assert sch.stats()["real_lanes"] >= sch.stats()["unique_lanes"]


def test_topk_bit_identity(dlists, dres):
    """Ranked top-k: deduped ScoreRounds and membership probes return
    exactly the dedup-off docs AND scores."""
    rng = np.random.default_rng(SEED + 5)
    bags = [sorted(int(t) for t in rng.choice(8, 3, replace=False))
            for _ in range(10)] * 2
    for ename in ("host", "jnp"):
        eng_on = _make(ename, dres)
        eng_off = _off(_make(ename, dres))
        for eng in (eng_on, eng_off):
            eng.score_page_size = PAGE
        sch_on = QueryScheduler(eng_on, batch_window=8,
                                result_cache_size=0)
        sch_off = QueryScheduler(eng_off, batch_window=8,
                                 result_cache_size=0)
        got = sch_on.search_topk_many(bags, 10)
        want = sch_off.search_topk_many(bags, 10)
        for ts, a, b in zip(bags, got, want):
            np.testing.assert_array_equal(a.docs, b.docs)
            np.testing.assert_array_equal(a.scores, b.scores)
            od, osc = rank_oracle(dlists, dres.universe, ts, 10)
            np.testing.assert_array_equal(a.docs, od)
            np.testing.assert_array_equal(a.scores, osc)


def test_mixed_codec_bit_identity(dlists, dres):
    """The dedup layer sits ABOVE codec routing: adaptive-tier engines
    with dedup on match the oracle exactly (codec is a function of list
    id, so a deduped lane keeps its codec)."""
    queries = _workload(len(dlists), 12, seed_off=7) * 2
    want = [naive_eval(q, dlists, dres.universe) for q in queries]
    for ename in ("host", "jnp"):
        eng = _make(ename, dres, codec="adaptive")
        sch = QueryScheduler(eng, batch_window=8, result_cache_size=0)
        for got, w in zip(sch.search_many(queries, "svs"), want):
            np.testing.assert_array_equal(got, w, err_msg=ename)
        st = sch.stats()
        nonrep = {k: v for k, v in st["codec_dispatches"].items()
                  if k != "repair"}
        assert sum(nonrep.values()) > 0     # the codec router really ran
        assert st["dedup_factor"] >= 1.0


# -- out-of-core: prefetch overlap ------------------------------------------

def _budget(res):
    n = int(np.asarray(res.starts)[-1])
    return max(1, (-(-n // PAGE)) // 10)


def test_out_of_core_prefetch_bit_identity(dlists, dres):
    """~10% resident budget, mmap store: prefetch-on == prefetch-off ==
    fully-resident, and the prefetch thread never outlives a drain."""
    queries = _workload(len(dlists), 16, seed_off=3) * 2
    want = [naive_eval(q, dlists, dres.universe) for q in queries]
    for prefetch in (True, False):
        eng = make_engine("jnp", dres, max_short_len=64, paged=True,
                          page_size=PAGE, store="mmap",
                          resident_pages=_budget(dres))
        sch = QueryScheduler(eng, batch_window=8, result_cache_size=0,
                             prefetch=prefetch)
        for got, w in zip(sch.search_many(queries), want):
            np.testing.assert_array_equal(got, w,
                                          err_msg=f"prefetch={prefetch}")
        assert sch._pf_thread is None        # joined before drain returned
        st = sch.stats()
        if prefetch:
            assert st["prefetch_enabled"]
        else:
            assert st["prefetched_pages"] == 0
            assert st["overlap_ms"] == 0.0


def test_prefetch_join_before_use(dres, dlists):
    """Thread-safety pin: with an artificially SLOW store gather the
    main thread must wait at the join point — prefetched pages enter the
    pool only after the join, on the main thread, and answers stay
    exact even when every prediction is still in flight at tick start."""
    eng = make_engine("jnp", dres, max_short_len=64, paged=True,
                      page_size=PAGE, store="memory",
                      resident_pages=_budget(dres))
    real_gather = eng.store.gather

    def slow_gather(pages):
        time.sleep(0.02)
        return real_gather(pages)

    eng.store.gather = slow_gather
    queries = _workload(len(dlists), 12, seed_off=9)
    sch = QueryScheduler(eng, batch_window=4, result_cache_size=0,
                         prefetch=True)
    for q, got in zip(queries, sch.search_many(queries)):
        np.testing.assert_array_equal(
            got, naive_eval(q, dlists, dres.universe))
    st = sch.stats()
    assert sch._pf_thread is None
    if st["prefetched_pages"]:
        # the slow gather forces real waiting at the join barrier
        assert st["prefetch_join_wait_ms"] > 0.0


def test_prefetch_admission_never_grows_pool(dres):
    """``admit_prefetched`` is best-effort: it never grows the pool and
    skips pages that became resident since the snapshot."""
    from repro.store import ResidentSet, build_page_store
    store = build_page_store(dres, kind="memory", page_size=PAGE)
    rs = ResidentSet(store, budget=4)
    rs.ensure([0, 1])
    want = rs.peek_missing(np.arange(store.num_pages))
    assert 0 not in want and 1 not in want
    # stage a gather for MORE pages than the pool can absorb
    pages = want[:8]
    syms, sums = store.gather(pages)
    admitted = rs.admit_prefetched(pages, syms, sums)
    assert rs.pool_grows == 0
    assert admitted <= 4 and rs.resident_pages <= 4
    # pages already resident are skipped, not double-admitted
    again = rs.admit_prefetched(pages[:admitted],
                                *store.gather(pages[:admitted]))
    assert again <= max(0, 4 - admitted) + 2   # only evictable slack
    # demanding a prefetched page counts it useful exactly once
    before = rs.prefetch_useful
    rs.ensure(pages[:1])
    rs.ensure(pages[:1])
    assert rs.prefetch_useful == before + (1 if admitted else 0)


# -- segmented ingest + swap pins -------------------------------------------

def test_segmented_ingest_bit_identity(dlists, dres):
    """Interleaved insert/search with dedup on matches the
    rebuilt-from-scratch oracle after EVERY insert — the epoch pin: no
    probe answer can leak from before an insert (delta answers are
    host-evaluated; segment engines are immutable)."""
    from repro.serve.query_serve import QueryServer
    vocab = 40
    docs = [np.arange(vocab, dtype=np.int64)] + [
        np.unique(np.random.default_rng(SEED + 60 + i)
                  .integers(0, vocab, size=8))
        for i in range(14)]

    def invert(ds):
        inv = {}
        for d, terms in enumerate(ds):
            for t in terms.tolist():
                inv.setdefault(int(t), []).append(d)
        return [np.asarray(inv[t], np.int64) for t in sorted(inv)]

    srv = QueryServer(repair_compress(invert(docs[:8])), engine="host")
    srv.enable_ingest(delta_budget=2, compact_fanout=2)
    rng = np.random.default_rng(SEED + 2)
    for i, d in enumerate(docs[8:]):
        srv.insert(d)
        cur = invert(docs[:9 + i])
        a, b = (int(t) for t in rng.choice(vocab, 2, replace=False))
        q = And((Term(a), Term(b)))
        # same query twice: the second submit exercises reuse paths
        for got in srv.search_many([q, q]):
            np.testing.assert_array_equal(
                got, naive_eval(q, cur, len(docs[:9 + i])))
    assert srv.serve_stats()["flushes"] >= 2


def test_swap_index_serves_new_answers(dlists, dres):
    """``swap_index`` leaves no old answer reachable: the swap builds a
    FRESH engine, and the same query then answers from the new index."""
    from repro.serve.query_serve import QueryServer
    srv = QueryServer(dres, engine="host")
    _on(srv.engine)
    q = And((Term(0), Term(1)))
    want_old = naive_eval(q, dlists, dres.universe)
    np.testing.assert_array_equal(srv.search(q, force_algo="svs"),
                                  want_old)
    old_engine = srv.engine
    new_lists = [np.unique(l // 2) for l in dlists]
    new_res = repair_compress(new_lists)
    srv.swap_index(new_res)
    assert srv.engine is not old_engine
    want_new = naive_eval(q, new_lists, new_res.universe)
    np.testing.assert_array_equal(srv.search(q, force_algo="svs"),
                                  want_new)
    assert not np.array_equal(want_old, want_new)


# -- dedup telemetry pins ----------------------------------------------------

def test_duplicate_heavy_dedup_factor_and_bucket(dlists, dres):
    """A duplicate-heavy workload (many queries over the same hot terms)
    must show dedup_factor > 1 AND a shrunk pow2 dispatch bucket:
    dispatched + pad lanes strictly below the dedup-off path's."""
    q = And((Term(0), Term(1), Term(2)))
    queries = [q] * 24

    def run(on):
        eng = _make("jnp", dres)
        _on(eng) if on else _off(eng)
        sch = QueryScheduler(eng, batch_window=24, result_cache_size=0)
        outs = sch.search_many(queries, "svs")
        return sch.stats(), outs

    st_on, outs_on = run(True)
    st_off, outs_off = run(False)
    for a, b in zip(outs_on, outs_off):
        np.testing.assert_array_equal(a, b)
    assert st_on["dedup_factor"] > 1.0, st_on
    assert st_on["unique_lanes"] < st_on["real_lanes"]
    assert st_on["real_lanes"] == st_off["real_lanes"]
    # the device saw strictly fewer lanes, padding included
    assert (st_on["dispatched_lanes"] + st_on["pad_lanes"]
            < st_off["dispatched_lanes"] + st_off["pad_lanes"]), \
        (st_on, st_off)


@pytest.mark.parametrize("ename", ("host", "jnp"))
def test_repeat_workload_dispatches_every_unique_lane(dlists, dres, ename):
    """No lane answer survives a tick: replaying a workload on the SAME
    scheduler (result cache off) gives the same answers and sends every
    unique lane to the backend again, exactly as the first pass did."""
    queries = _workload(len(dlists), 10, seed_off=4)
    want = [naive_eval(q, dlists, dres.universe) for q in queries]
    sch = QueryScheduler(_on(_make(ename, dres)), batch_window=8,
                         result_cache_size=0)
    passes = []
    for _ in range(2):
        before = sch.stats()
        outs = sch.search_many(queries, "svs")
        after = sch.stats()
        passes.append({k: after[k] - before[k]
                       for k in ("unique_lanes", "dispatched_lanes")})
        for got, w in zip(outs, want):
            np.testing.assert_array_equal(got, w)
    assert passes[0]["dispatched_lanes"] > 0
    for p in passes:
        assert p["dispatched_lanes"] == p["unique_lanes"], p
    assert passes[0] == passes[1], passes


# -- cache counter satellite -------------------------------------------------

def test_lru_counters():
    c = LRUCache(2)
    assert c.get("a") is None
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1
    c.put("c", 3)                   # evicts b (a was just touched)
    assert c.get("b") is None
    st = c.stats()
    assert st["hits"] == 1 and st["misses"] == 2
    assert st["evictions"] == 1
    assert st["hit_rate"] == pytest.approx(1 / 3)
    c.flush()                       # counters survive a flush
    assert c.stats()["evictions"] == 1 and c.stats()["size"] == 0
