"""Serving tier: continuous-batching decode engine + index substrate."""

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_arch
from repro.engine import make_engine
from repro.index import build_index, zipf_corpus, pack_documents
from repro.index.corpus import randomize_lists
from repro.query import And, Or, QueryExecutor, Term
from repro.models import transformer as T
from repro.serve import DecodeEngine, ServeConfig


def test_decode_engine_continuous_batching():
    cfg = get_arch("yi-6b").smoke_config
    params = T.init_params(jax.random.key(0), cfg)
    eng = DecodeEngine(params, cfg,
                       ServeConfig(max_batch=2, s_cache=24, max_new_tokens=4))
    for i in range(5):  # more requests than lanes -> queueing
        eng.submit(np.arange(1, 4 + i) % cfg.vocab)
    outs = eng.run_until_drained()
    assert len(outs) == 5
    for o in outs:
        assert 1 <= len(o) <= 4


def test_decode_engine_greedy_matches_forward():
    """Engine's first generated token == argmax of prefill logits."""
    cfg = get_arch("yi-6b").smoke_config
    params = T.init_params(jax.random.key(0), cfg)
    prompt = np.asarray([3, 7, 11], dtype=np.int32)
    logits, _ = T.prefill(params, cfg, jnp.asarray(prompt)[None, :])
    want = int(jnp.argmax(logits[0]))
    eng = DecodeEngine(params, cfg,
                       ServeConfig(max_batch=1, s_cache=16, max_new_tokens=2))
    eng.submit(prompt)
    outs = eng.run_until_drained()
    assert outs[0][0] == want


# -- index substrate ---------------------------------------------------------------

def test_corpus_and_index_end_to_end():
    corpus = zipf_corpus(num_docs=150, vocab_size=400, mean_doc_len=40,
                         seed=3)
    lists = corpus.postings()
    assert all((np.diff(l) > 0).all() for l in lists if len(l) > 1)
    ix = build_index(lists, corpus.num_docs)
    qx = QueryExecutor(make_engine("host", ix.repair))
    rng = np.random.default_rng(0)
    for _ in range(20):
        i, j = rng.choice(len(lists), 2, replace=False)
        oracle = np.intersect1d(lists[i], lists[j])
        np.testing.assert_array_equal(
            qx.search(And((Term(int(i)), Term(int(j))))), oracle)
    # disjunctive + multi-term
    i, j, k = 0, 1, 2
    np.testing.assert_array_equal(
        qx.search(Or((Term(i), Term(j)))),
        np.union1d(lists[i], lists[j]))
    tri = qx.search(And((Term(i), Term(j), Term(k))))
    oracle = np.intersect1d(np.intersect1d(lists[i], lists[j]), lists[k])
    np.testing.assert_array_equal(tri, oracle)


def test_pack_documents_shrinks_doc_count():
    corpus = zipf_corpus(num_docs=100, vocab_size=200, seed=1)
    packed = pack_documents(corpus, 10)
    assert packed.num_docs == 10
    # packed doc 0 contains everything docs 0..9 contained
    want = np.unique(np.concatenate(corpus.doc_terms[:10]))
    np.testing.assert_array_equal(packed.doc_terms[0], want)


def test_randomize_lists_preserves_lengths():
    corpus = zipf_corpus(num_docs=100, vocab_size=200, seed=2)
    lists = corpus.postings()
    rnd = randomize_lists(lists, corpus.num_docs, seed=0)
    assert [len(a) for a in lists] == [len(b) for b in rnd]
    for b in rnd:
        assert (np.diff(b) > 0).all()
        assert b[-1] < corpus.num_docs


def test_query_server_rebuild_hot_swap():
    """Build-then-hot-swap (DESIGN.md §3.4): a QueryServer rebuilt from a
    grown PostingsSource snapshot keeps serving, with answers correct
    against the NEW collection — for both host and device builders."""
    from repro.core.repair import repair_compress
    from repro.data.pipeline import PostingsSource
    from repro.serve.query_serve import QueryServer

    src = PostingsSource(base_docs=120, growth_docs=60, vocab=300, seed=3)
    lists0, _ = src.lists_at(0)
    srv = QueryServer(repair_compress(lists0), engine="jnp")
    rng = np.random.default_rng(0)

    def check(lists):
        pairs = [tuple(map(int, rng.choice(len(lists), 2, replace=False)))
                 for _ in range(6)]
        for (a, b), got in zip(pairs, srv.and_batch(pairs)):
            np.testing.assert_array_equal(
                got, np.intersect1d(lists[a], lists[b]))

    check(lists0)
    old_engine = srv.engine
    lists1, _ = src.lists_at(1)
    res1 = srv.rebuild(lists1, builder="jnp")
    assert srv.engine is not old_engine
    assert srv.res is res1
    assert len(lists1) > len(lists0)
    check(lists1)
    # swap back to the v0 snapshot through swap_index directly
    srv.swap_index(repair_compress(lists0))
    check(lists0)


def test_postings_source_is_pure():
    from repro.data.pipeline import PostingsSource

    src = PostingsSource(base_docs=80, growth_docs=40, vocab=200, seed=5)
    a, ua = src.lists_at(2)
    b, ub = src.lists_at(2)
    assert ua == ub == src.num_docs_at(2)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_compile_cache_dir(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` wins; without it the cache lives at
    the fixed ``.jax_cache`` in the checkout, never a per-run path."""
    from pathlib import Path
    from repro.launch.compile_cache import compile_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache_dir() == "/elsewhere/cache"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    root = Path(__file__).resolve().parents[1]
    assert compile_cache_dir() == str(root / ".jax_cache")
