"""Ranked retrieval's differential gate (DESIGN.md §9).

BM25 top-k with block-max page pruning must return EXACTLY the
brute-force oracle's answer — float32-identical scores AND tie-broken
(score desc, doc asc) order — on every engine configuration (host /
jnp flat / jnp paged / pallas interpret / 1-device-mesh shard_map),
pruned and exhaustive, serial and through the coalescing scheduler.

Plus the pins: the 128-symbol block-max directory (partition + upper
bounds + page-straddling lists), pruned-vs-exhaustive page accounting
with actual skips on a crafted corpus, deterministic tie-breaking,
degenerate k / OOV bags, result-cache keying across scoring modes, and
ranked-round coalescing.
"""

import os

import numpy as np
import pytest

from strategies import adversarial_lists

from repro.core.jax_index import build_score_index
from repro.core.repair import repair_compress
from repro.engine import HostEngine, JnpEngine, PallasEngine
from repro.query import QueryExecutor, rank_oracle, search_topk
from repro.serve.scheduler import QueryScheduler

SEED = int(os.environ.get("REPRO_BENCH_SEED", "0"))
ENGINE_CONFIGS = ("host", "jnp", "jnp_paged", "pallas")


@pytest.fixture(scope="module")
def rlists():
    # module-own rng: corpus identical no matter what ran before (the
    # same isolation convention as the scheduler gate)
    return adversarial_lists(np.random.default_rng(SEED + 204),
                             universe=700, n_random=8, max_len=70)


@pytest.fixture(scope="module")
def rres(rlists):
    return repair_compress(rlists)


def _make_engine(name, res):
    if name == "host":
        return HostEngine(res)
    if name == "jnp":
        return JnpEngine(res, max_short_len=64)
    if name == "jnp_paged":
        return JnpEngine(res, max_short_len=64, paged=True, page_size=128)
    if name == "pallas":
        return PallasEngine(res, max_short_len=64, interpret=True,
                            page_size=128)
    raise ValueError(name)


@pytest.fixture(scope="module")
def rengines(rres):
    return {name: _make_engine(name, rres) for name in ENGINE_CONFIGS}


def _bags(num_lists, n, seed_off=0):
    """Seeded term bags: duplicates and out-of-vocabulary ids included —
    the driver must dedupe and drop them."""
    rng = np.random.default_rng(SEED + 31 + seed_off)
    out = []
    for _ in range(n):
        k = int(rng.integers(1, 5))
        bag = [int(t) for t in rng.integers(0, num_lists, size=k)]
        if rng.random() < 0.3:
            bag.append(bag[0])                       # duplicate term
        if rng.random() < 0.3:
            bag.append(int(rng.choice([-1, num_lists + 2])))   # OOV
        out.append(bag)
    return out


# -- the differential gate ---------------------------------------------------

@pytest.mark.parametrize("ename", ENGINE_CONFIGS)
def test_topk_matches_oracle(rlists, rres, rengines, ename):
    """Exact scores and exact order vs the brute-force BM25 oracle,
    pruned AND exhaustive, across k."""
    eng = rengines[ename]
    n = 6 if ename == "pallas" else 12     # interpret mode is slow
    for i, bag in enumerate(_bags(len(rlists), n)):
        k = (1, 3, 10)[i % 3]
        want_d, want_s = rank_oracle(rlists, rres.universe, bag, k)
        for prune in (True, False):
            got = search_topk(eng, bag, k, prune=prune)
            np.testing.assert_array_equal(got.docs, want_d,
                                          err_msg=f"{ename} bag={bag} k={k}")
            np.testing.assert_array_equal(got.scores, want_s)


def test_topk_sharded_dispatch(rlists, rres):
    """The membership probes of the scoring rounds ride the shard_map
    dispatch when the engine carries a mesh (1-device mesh: same math,
    sharded code path)."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    eng = JnpEngine(rres, max_short_len=64, mesh=mesh)
    for bag in _bags(len(rlists), 5, seed_off=1):
        want_d, want_s = rank_oracle(rlists, rres.universe, bag, 5)
        got = search_topk(eng, bag, 5)
        np.testing.assert_array_equal(got.docs, want_d)
        np.testing.assert_array_equal(got.scores, want_s)


def test_topk_through_scheduler(rlists, rres, rengines):
    """Scheduler-coalesced ranked execution == the serial path, and the
    ranked rounds of concurrent queries actually merge."""
    eng = rengines["host"]
    bags = _bags(len(rlists), 10, seed_off=2)
    serial = [search_topk(eng, bag, 10) for bag in bags]
    sch = QueryScheduler(eng, batch_window=8, result_cache_size=0)
    outs = sch.search_topk_many(bags, 10)
    for want, got in zip(serial, outs):
        np.testing.assert_array_equal(got.docs, want.docs)
        np.testing.assert_array_equal(got.scores, want.scores)
        assert got.pages_scored == want.pages_scored
        assert got.pages_skipped == want.pages_skipped
    st = sch.stats()
    assert st["coalescing_factor"] > 1.0, st
    assert st["pages_scored"] == sum(r.pages_scored for r in serial)


def test_topk_mixed_with_boolean_traffic(rlists, rres, rengines):
    """Ranked and boolean queries interleave on one scheduler; both
    stay exact."""
    from repro.query import naive_eval
    eng = rengines["host"]
    sch = QueryScheduler(eng, batch_window=8)
    bag = [0, 2, 5]
    bool_q = "(0 AND 2) OR 5"
    qid_r = sch.submit_topk(bag, 10)
    qid_b = sch.submit(bool_q)
    sch.drain()
    want_d, want_s = rank_oracle(rlists, rres.universe, bag, 10)
    got_r = sch.take(qid_r)
    np.testing.assert_array_equal(got_r.docs, want_d)
    np.testing.assert_array_equal(got_r.scores, want_s)
    node = QueryExecutor(eng).plan(bool_q).node
    np.testing.assert_array_equal(sch.take(qid_b),
                                  naive_eval(node, rlists, rres.universe))


def test_executor_topk_entrypoint(rlists, rres, rengines):
    """QueryExecutor.topk accepts query strings — the term bag is the
    string's terms."""
    qx = QueryExecutor(rengines["host"])
    got = qx.topk("0 AND 3", 7)
    want_d, want_s = rank_oracle(rlists, rres.universe, [0, 3], 7)
    np.testing.assert_array_equal(got.docs, want_d)
    np.testing.assert_array_equal(got.scores, want_s)


# -- behaviour pins ----------------------------------------------------------

def test_topk_edge_cases(rlists, rres, rengines):
    eng = rengines["host"]
    # k = 0 and OOV-only bags: empty result, nothing scored
    for bag, k in (([0, 1], 0), ([-1, len(rlists) + 5], 10)):
        got = search_topk(eng, bag, k)
        assert got.docs.size == 0 and got.scores.size == 0
        assert got.pages_scored == 0 and got.pages_skipped == 0
    # k beyond the matching-doc count returns every matching doc
    bag = [8]                        # the singleton list
    got = search_topk(eng, bag, 50)
    want_d, want_s = rank_oracle(rlists, rres.universe, bag, 50)
    assert got.docs.size == rlists[8].size == 1
    np.testing.assert_array_equal(got.docs, want_d)
    np.testing.assert_array_equal(got.scores, want_s)
    # duplicate terms == the deduped bag
    a = search_topk(eng, [0, 0, 1, 1], 5)
    b = search_topk(eng, [0, 1], 5)
    np.testing.assert_array_equal(a.docs, b.docs)
    np.testing.assert_array_equal(a.scores, b.scores)


def test_topk_tie_break_is_doc_ascending():
    """Docs with bit-identical scores rank by ascending doc id — pinned
    on a corpus where EVERY doc ties (same doc length, same membership)."""
    lists = [np.arange(20, dtype=np.int64), np.arange(20, dtype=np.int64)]
    res = repair_compress(lists)
    eng = HostEngine(res)
    got = search_topk(eng, [0, 1], 8)
    np.testing.assert_array_equal(got.docs, np.arange(8))
    assert np.unique(got.scores).size == 1
    want_d, want_s = rank_oracle(lists, res.universe, [0, 1], 8)
    np.testing.assert_array_equal(got.docs, want_d)
    np.testing.assert_array_equal(got.scores, want_s)


def test_blockmax_directory_page128(rres, rlists):
    """The 128-symbol directory partitions every list exactly: entry
    counts sum to list lengths, per-entry slices tile the decode, the
    block maxima really bound their slices, and at this page size some
    list MUST straddle a page boundary (the stream is contiguous)."""
    si = build_score_index(rres, page_size=128)
    assert si.page_size == 128
    straddlers = 0
    for t, lst in enumerate(rlists):
        lo, hi = int(si.page_off[t]), int(si.page_off[t + 1])
        ents = np.arange(lo, hi)
        assert int(si.pg_count[ents].sum()) == lst.size
        straddlers += ents.size > 1
        pieces = []
        for e in ents:
            elo, cnt = int(si.pg_elem_lo[e]), int(si.pg_count[e])
            sl = lst[elo:elo + cnt]
            pieces.append(sl)
            contrib = si.idf[t] * si.doc_w[sl]
            assert np.float32(contrib.max()) == si.pg_ub[e]
            assert np.float32(si.doc_w[sl].max()) == si.pg_wmax[e]
            assert int(sl[-1]) == int(si.pg_last[e])
        np.testing.assert_array_equal(np.concatenate(pieces), lst)
    assert straddlers > 0, "fixture must exercise page-straddling lists"


def _skip_corpus():
    """A corpus engineered so block-max pruning MUST skip: a long,
    incompressible common list B spanning several 128-symbol pages, and
    a rare list A = B's 40 smallest docs.  Top-k docs match both terms,
    so θ clears the bound of every B page beyond A's doc range (their
    doc-aligned rest is 0)."""
    rng = np.random.default_rng(SEED + 77)
    B = np.unique(rng.choice(4000, size=1400, replace=False))
    A = B[:40]
    fillers = [np.unique(rng.choice(4000, size=60, replace=False))
               for _ in range(6)]
    return [A, B] + fillers


@pytest.mark.parametrize("ename", ENGINE_CONFIGS)
def test_pruning_skips_and_matches_exhaustive(ename):
    """pages(pruned) + pages(skipped) == pages(exhaustive), skips > 0,
    and the pruned answer is still oracle-exact — on every backend, off
    one SHARED directory so the admission decisions are identical."""
    lists = _skip_corpus()
    res = repair_compress(lists)
    si = build_score_index(res, page_size=128)
    eng = _make_engine(ename, res)
    if ename in ("host", "jnp"):
        eng.score_page_size = 128
    eng.set_score_index(si)
    bag = [0, 1]
    want_d, want_s = rank_oracle(lists, res.universe, bag, 10)
    got = search_topk(eng, bag, 10)
    exh = search_topk(eng, bag, 10, prune=False)
    for r in (got, exh):
        np.testing.assert_array_equal(r.docs, want_d)
        np.testing.assert_array_equal(r.scores, want_s)
    assert got.pages_skipped > 0, "crafted corpus must produce skips"
    assert got.pages_scored + got.pages_skipped == exh.pages_scored
    assert exh.pages_skipped == 0


def test_device_page_decode_matches_host():
    """decode_page_batch is bit-identical host vs jnp-windowed vs the
    pallas kernel (tile-guarded rows included) over EVERY directory
    entry at page 128."""
    lists = _skip_corpus()
    res = repair_compress(lists)
    si = build_score_index(res, page_size=128)
    host = _make_engine("host", res)
    host.score_page_size = 128
    host.set_score_index(si)
    engines = [_make_engine("jnp_paged", res), _make_engine("pallas", res)]
    for eng in engines:
        eng.set_score_index(si)
    all_entries = np.arange(si.pg_list.size, dtype=np.int32)
    want = host.decode_page_batch(all_entries)
    for eng in engines:
        got = eng.decode_page_batch(all_entries)
        assert got.shape[0] == want.shape[0]
        w = min(got.shape[1], want.shape[1])
        np.testing.assert_array_equal(got[:, :w], want[:, :w],
                                      err_msg=eng.name)
        # wider padding (if any) is all INT_INF
        assert (got[:, w:] == np.iinfo(np.int32).max).all()


def test_score_batch_matches_oracle(rlists, rres, rengines):
    """engine.score_batch == the oracle's scores for any doc subset,
    including docs matching no term (score 0)."""
    bag = [0, 1, 4]
    want_d, want_s = rank_oracle(rlists, rres.universe, bag,
                                 rres.universe)
    lookup = dict(zip(want_d.tolist(), want_s.tolist()))
    rng = np.random.default_rng(SEED + 5)
    docs = np.unique(rng.integers(0, rres.universe, size=40))
    want = np.asarray([lookup.get(int(d), 0.0) for d in docs], np.float32)
    for ename in ENGINE_CONFIGS:
        got = rengines[ename].score_batch(docs, bag)
        np.testing.assert_array_equal(got, want, err_msg=ename)


def test_result_cache_keying_across_modes(rlists, rres):
    """Boolean and ranked results never collide in the result cache, and
    ranked entries are keyed by (terms, k, prune)."""
    from repro.serve.query_serve import QueryServer
    srv = QueryServer(rres, engine="host")
    bool_out = srv.search("0 AND 1")
    r10 = srv.search_topk("0 AND 1", 10)
    r3 = srv.search_topk("0 AND 1", 3)
    assert isinstance(bool_out, np.ndarray)
    assert r10.docs.size >= r3.docs.size
    np.testing.assert_array_equal(r3.docs, r10.docs[:r3.docs.size])
    h0 = srv.serve_stats()["result_cache"]["hits"]
    again = srv.search_topk("0 AND 1", 10)          # cache hit
    assert srv.serve_stats()["result_cache"]["hits"] == h0 + 1
    np.testing.assert_array_equal(again.docs, r10.docs)
    np.testing.assert_array_equal(again.scores, r10.scores)
    # the cached copy is immutable; the handed-out copy is independent
    again.docs = np.array([])       # mutate the returned object freely
    fresh = srv.search_topk("0 AND 1", 10)
    np.testing.assert_array_equal(fresh.docs, r10.docs)


def test_launcher_topk_runs_page_score(rlists, rres):
    """The launcher's ranked path — a pallas ``QueryServer`` with the fine
    ``SCORE_PAGE`` directory — decodes its pages with the ``page_score``
    kernel, which the launch counter shows, and stays oracle-exact."""
    from repro.kernels import LAUNCHES
    from repro.launch.serve import SCORE_PAGE
    from repro.serve.query_serve import QueryServer
    srv = QueryServer(rres, engine="pallas", interpret=True)
    srv.engine.score_page_size = SCORE_PAGE
    before = LAUNCHES["page_score[interpret]"]
    bags = [[0, 1, 4], [2, 3]]
    for bag, got in zip(bags, srv.search_topk_many(bags, 10)):
        docs, scores = rank_oracle(rlists, rres.universe, bag, 10)
        np.testing.assert_array_equal(got.docs, docs)
        np.testing.assert_array_equal(got.scores, scores)
    assert LAUNCHES["page_score[interpret]"] > before


@pytest.mark.parametrize("stream_page,score_page", [(256, 384), (384, 192)])
def test_pallas_refuses_foreign_score_geometry(rres, stream_page,
                                               score_page):
    """A score directory whose pages do not tile the pallas engine's stream
    pages is refused, not decoded by another path.  The check reads the
    directory as built: a requested 192 is cut at 256, which does not
    divide 384 although 192 does."""
    eng = PallasEngine(rres, max_short_len=64, interpret=True,
                       page_size=stream_page)
    eng.score_page_size = score_page
    with pytest.raises(ValueError, match="does not divide"):
        search_topk(eng, [0, 1], 10)


def test_page_decode_splits_large_rounds(monkeypatch):
    """A ScoreRound larger than ``ENTRIES_PER_LAUNCH`` decodes in several
    ``page_score`` launches, bit-identical to the host decode."""
    from repro.kernels import LAUNCHES
    from repro.kernels.page_score import ops as PS
    lists = _skip_corpus()
    res = repair_compress(lists)
    si = build_score_index(res, page_size=128)
    host = _make_engine("host", res)
    host.score_page_size = 128
    host.set_score_index(si)
    eng = _make_engine("pallas", res)
    eng.set_score_index(si)
    entries = np.tile(np.arange(si.pg_list.size, dtype=np.int32), 3)
    monkeypatch.setattr(PS, "ENTRIES_PER_LAUNCH", 16)
    before = LAUNCHES["page_score[interpret]"]
    got = eng.decode_page_batch(entries)
    assert LAUNCHES["page_score[interpret]"] - before == -(-entries.size // 16)
    want = host.decode_page_batch(entries)
    w = min(got.shape[1], want.shape[1])
    np.testing.assert_array_equal(got[:, :w], want[:, :w])
    assert (got[:, w:] == np.iinfo(np.int32).max).all()
