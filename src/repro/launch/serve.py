"""Serving launcher: both serving tiers behind one CLI.

  PYTHONPATH=src python -m repro.launch.serve --tier queries   # IR engine
  PYTHONPATH=src python -m repro.launch.serve --tier lm --arch yi-6b

* ``queries`` — the paper's tier: build a synthetic collection, compress
  with Re-Pair, serve batched conjunctive queries from the device engine.
* ``lm``      — continuous-batching LM decode on the arch's smoke config.

The production lowering of both tiers is exercised by the dry-run
(repair-ir × serve_* cells; <arch> × decode_* cells).
"""

from __future__ import annotations

import argparse
import time

import numpy as np


#: score-directory page size of the ranked workload: a fine directory
#: whose pages can be pruned (it divides every engine's stream page)
SCORE_PAGE = 128


def _zipf_sampler(lists, rng):
    """Draw k distinct term ids, Zipf(1.1) over lists ranked longest
    first — the query-term skew of the serving workloads."""
    order = sorted(range(len(lists)), key=lambda i: -len(lists[i]))
    p = np.arange(1, len(lists) + 1, dtype=np.float64) ** -1.1
    p /= p.sum()
    return lambda k: [int(order[r]) for r in
                      rng.choice(len(lists), size=k, replace=False, p=p)]


def boolean_workload(lists, n: int, seed: int = 1) -> list[str]:
    """The launcher's boolean mix: 70% conjunctions of 2-3 Zipf terms,
    30% ``(a AND b) OR NOT c``."""
    rng = np.random.default_rng(seed)
    draw = _zipf_sampler(lists, rng)
    qs = []
    for _ in range(n):
        ts = draw(int(rng.integers(2, 4)))
        qs.append(" AND ".join(str(t) for t in ts)
                  if rng.random() < 0.7 else
                  f"({ts[0]} AND {ts[1]}) OR NOT {ts[-1]}")
    return qs


def ranked_workload(lists, n: int, seed: int = 2) -> list[list[int]]:
    """The launcher's ranked mix: bags of 2-4 Zipf terms."""
    rng = np.random.default_rng(seed)
    draw = _zipf_sampler(lists, rng)
    return [draw(int(nk)) for nk in rng.integers(2, 5, size=n)]


def serve_queries(n_queries: int, engine: str = "jnp",
                  data_shards: int = 0, builder: str = "host",
                  refreshes: int = 0, query: str | None = None,
                  concurrency: int = 0, topk: int = 0,
                  batch_window: int | None = None,
                  codec: str | None = None,
                  store: str | None = None,
                  resident_pages: int | None = None,
                  ingest_rate: int = 0, num_docs: int = 2000,
                  vocab: int = 4000, growth_docs: int = 500,
                  seed: int = 0) -> None:
    from ..build import make_builder
    from ..data.pipeline import PostingsSource
    from ..serve.query_serve import QueryServer

    # ONE versioned postings feed for the whole launch: the corpus the
    # server is built from IS the corpus refresh grows — the refresh loop
    # below consumes only each version's delta, against the same
    # (num_docs, growth_docs, vocab, seed) the server was launched with
    src = PostingsSource(base_docs=num_docs, growth_docs=growth_docs,
                         vocab=vocab, seed=seed)
    inv: dict[int, list[int]] = {}
    served_docs = 0

    def extend_corpus(new_docs) -> int:
        nonlocal served_docs
        for terms in new_docs:
            for t in terms.tolist():
                inv.setdefault(int(t), []).append(served_docs)
            served_docs += 1
        return len(new_docs)

    def corpus_lists() -> list[np.ndarray]:
        return [np.asarray(inv[t], np.int64) for t in sorted(inv)]

    extend_corpus(src.deltas_at(0))
    lists = corpus_lists()
    n_sym = sum(len(l) for l in lists)
    print(f"corpus: {served_docs} docs / {len(lists)} lists "
          f"(vocab {vocab}, seed {seed})")
    # the pallas builder counts against a static candidate table, so give
    # it the [CN07] capped-counting config its table can hold exactly
    # (host/jnp accept the same knob; uncapped they count everything)
    bld = make_builder(builder,
                       **({"table_cap": 4096} if builder == "pallas"
                          else {}))
    t0 = time.perf_counter()
    res = bld.build_grammar(lists)
    dt = time.perf_counter() - t0
    print(f"[{builder}] built {res.grammar.num_rules} rules from "
          f"{n_sym} symbols in {dt:.2f}s ({n_sym/dt:.0f} sym/s)")
    mesh = None
    if data_shards:
        import jax
        import numpy as _np
        from jax.sharding import Mesh
        devs = jax.devices()
        if data_shards > len(devs):
            raise SystemExit(f"--data-shards {data_shards} > "
                             f"{len(devs)} available devices")
        mesh = Mesh(_np.array(devs[:data_shards]), ("data",))
        print(f"shard_map dispatch over data axis: {data_shards} device(s)")
    srv = QueryServer(res, max_short_len=256, engine=engine, mesh=mesh,
                      batch_window=batch_window, codec=codec,
                      store=store, resident_pages=resident_pages)
    if srv.engine.tier is not None:
        rep = srv.engine.tier.space_report(res)
        print(f"codec tier [{rep['mode']}]: {rep['counts']} "
              f"({rep['bits_per_posting']:.2f} bits/posting)")
    if srv.engine.resident is not None:
        ss = srv.engine.resident.stats()
        extra = (f", {srv.engine.store.disk_bytes/1e6:.1f} MB on disk"
                 if hasattr(srv.engine.store, "disk_bytes") else "")
        print(f"page store [{ss['kind']}]: {ss['num_pages']} pages x "
              f"{ss['page_size']} syms, resident budget {ss['budget']}"
              f"{extra}")
    rng = np.random.default_rng(0)
    pairs = [tuple(map(int, rng.choice(len(lists), 2, replace=False)))
             for _ in range(n_queries)]
    srv.and_batch(pairs[:2])
    t0 = time.perf_counter()
    outs = srv.and_batch(pairs)
    dt = time.perf_counter() - t0
    print(f"{len(pairs)} conjunctive queries in {dt*1e3:.1f} ms "
          f"({len(pairs)/dt:.0f} q/s), {sum(len(o) for o in outs)} hits")
    for (a, b), got in list(zip(pairs, outs))[::max(len(pairs)//8, 1)]:
        np.testing.assert_array_equal(got, np.intersect1d(lists[a], lists[b]))
    print("spot checks OK")

    # cross-query batching (DESIGN.md §8): a Zipf boolean workload runs
    # through the scheduler with --concurrency queries in flight; probe
    # rounds of concurrent queries merge into shared device dispatches
    if concurrency:
        from ..query import naive_eval
        qs = boolean_workload(lists, max(concurrency * 4, 16))
        import os
        if batch_window is None and "REPRO_BATCH_WINDOW" not in os.environ:
            # window defaults to the offered concurrency; an explicit
            # --batch-window or REPRO_BATCH_WINDOW wins
            srv.scheduler.batch_window = max(1, concurrency)
        outs = srv.search_many(qs)
        for qstr, got in list(zip(qs, outs))[::max(len(qs) // 8, 1)]:
            np.testing.assert_array_equal(
                got, naive_eval(srv.plan(qstr).node, lists, res.universe))
        st = srv.serve_stats()
        print(f"scheduler: {st['completed']} boolean queries, "
              f"{st['qps']:.0f} q/s, p50 {st['p50_ms']:.2f} ms / "
              f"p95 {st['p95_ms']:.2f} ms, coalescing factor "
              f"{st['coalescing_factor']:.2f} over {st['dispatches']} "
              f"merged dispatches (window {st['batch_window']}), "
              f"spot checks OK")
        # hot-path dedup telemetry (DESIGN.md §13): real vs unique vs pad
        # lanes, and the prefetch overlap (zero unless an out-of-core
        # store is attached)
        print(f"hot-path dedup: factor {st['dedup_factor']:.2f} "
              f"({st['real_lanes']} real / {st['unique_lanes']} unique / "
              f"{st['pad_lanes']} pad lanes), prefetch overlap "
              f"{st['overlap_ms']:.1f} ms "
              f"(accuracy {st['prefetch_accuracy']:.3f})")
        if st["store"] is not None:
            print(f"admission cache: {st['page_faults']} faults / "
                  f"{st['page_evictions']} evictions, "
                  f"{st['resident_pages']} pages resident "
                  f"(budget {st['store']['budget']}), "
                  f"{st['fault_bytes']/1e6:.2f} MB faulted, hit rate "
                  f"{st['store_hit_rate']:.3f}")

    # ranked retrieval (DESIGN.md §9): BM25 top-k with block-max page
    # pruning through the same coalescing scheduler; the telemetry window
    # reports how many page decodes the admission bound refused
    if topk:
        from ..query import rank_oracle
        srv.engine.score_page_size = SCORE_PAGE   # prunable pages
        bags = ranked_workload(lists, 16)
        srv.search_topk(bags[0], topk)    # compile + build the score tier
        t0 = time.perf_counter()
        routs = srv.search_topk_many(bags, topk)
        dt = time.perf_counter() - t0
        st = srv.serve_stats()
        print(f"ranked top-{topk}: {len(bags)} queries in {dt*1e3:.1f} ms "
              f"({len(bags)/dt:.0f} q/s), pages scored "
              f"{st['pages_scored']} / skipped {st['pages_skipped']} "
              f"(frac {st['pages_skipped_frac']:.3f}), final threshold "
              f"{st['threshold_final']:.3f}")
        for bag, got in list(zip(bags, routs))[::4]:
            od, osc = rank_oracle(lists, res.universe, bag, topk)
            np.testing.assert_array_equal(got.docs, od)
            np.testing.assert_array_equal(got.scores, osc)
        print("ranked spot checks OK (exact BM25 scores and order)")

    # boolean queries through the cost-based planner (DESIGN.md §7):
    # --query '(12 AND 40) OR NOT 7' — term ids address postings lists
    if query is not None:
        from ..query import naive_eval
        print(f"\nquery: {query}\nplan:\n{srv.explain(query)}")
        t0 = time.perf_counter()
        hits = srv.search(query)
        dt = time.perf_counter() - t0
        np.testing.assert_array_equal(
            hits, naive_eval(srv.plan(query).node, lists, res.universe))
        print(f"{hits.size} hits in {dt*1e3:.1f} ms (oracle-verified); "
              f"first 10: {hits[:10].tolist()}")

    # index refresh without restarting: grow THE SERVED collection by one
    # version's delta (``deltas_at`` — only the new documents, not an
    # O(corpus) recompute), rebuild, hot-swap, keep answering
    # (DESIGN.md §3.4)
    if refreshes:
        for v in range(1, refreshes + 1):
            added = extend_corpus(src.deltas_at(v))
            new_lists = corpus_lists()
            t0 = time.perf_counter()
            srv.rebuild(new_lists, builder=bld)   # same config as v0
            dt = time.perf_counter() - t0
            n_sym = sum(len(l) for l in new_lists)
            q = [tuple(map(int, rng.choice(len(new_lists), 2,
                                           replace=False)))
                 for _ in range(8)]
            for (a, b), got in zip(q, srv.and_batch(q)):
                np.testing.assert_array_equal(
                    got, np.intersect1d(new_lists[a], new_lists[b]))
            print(f"refresh v{v}: +{added} docs -> {len(new_lists)} lists "
                  f"/ {n_sym} symbols rebuilt + swapped in {dt:.2f}s, "
                  f"serving verified")

    # streaming ingestion (DESIGN.md §12): documents insert one at a time
    # through the segmented log-structured index — immediately visible,
    # flushed into immutable Re-Pair segments past the delta budget,
    # background-compacted by the scheduler — while every round's answers
    # are held bit-identical to a rebuild-from-scratch oracle
    if ingest_rate:
        import os
        from ..query import naive_eval, rank_oracle
        from ..query.parser import parse

        cvocab = 96
        isrc = PostingsSource(base_docs=48, growth_docs=16, vocab=cvocab,
                              mean_doc_len=16, seed=seed)
        # coverage head doc (every term) pins global term id == dense
        # list index on both the segmented and the rebuilt side
        docs = [np.arange(cvocab, dtype=np.int64)]
        docs += [isrc.doc_terms(d) for d in range(47 + 6 * ingest_rate)]

        def inv_of(ds):
            iv: dict[int, list[int]] = {}
            for d, terms in enumerate(ds):
                for t in terms.tolist():
                    iv.setdefault(int(t), []).append(d)
            return [np.asarray(iv[t], np.int64) for t in sorted(iv)]

        res2 = bld.build_grammar(inv_of(docs[:48]))
        srv2 = QueryServer(res2, max_short_len=256, engine=engine,
                           mesh=mesh, batch_window=batch_window,
                           codec=codec, store=store,
                           resident_pages=resident_pages)
        budget = int(os.environ.get("REPRO_DELTA_BUDGET", "12"))
        srv2.enable_ingest(delta_budget=budget, compact_fanout=2)
        qgen = np.random.default_rng(seed + 5)
        pos, checked = 48, 0
        t0 = time.perf_counter()
        for _ in range(6):
            for _ in range(ingest_rate):
                srv2.insert(docs[pos])
                pos += 1
            lists2, n2 = inv_of(docs[:pos]), pos
            ts = sorted(qgen.choice(cvocab, 3, replace=False).tolist())
            qs = [f"{ts[0]} AND {ts[1]}",
                  f"({ts[0]} AND {ts[1]}) OR NOT {ts[2]}"]
            for qstr, got in zip(qs, srv2.search_many(qs)):
                np.testing.assert_array_equal(
                    got, naive_eval(parse(qstr, None), lists2, n2))
            rr = srv2.search_topk(ts, 10)
            od, osc = rank_oracle(lists2, n2, ts, 10)
            np.testing.assert_array_equal(rr.docs, od)
            np.testing.assert_array_equal(rr.scores, osc)
            checked += len(qs) + 1
        dt = time.perf_counter() - t0
        st = srv2.serve_stats()
        print(f"ingest: {pos - 48} docs streamed ({ingest_rate}/round, "
              f"delta budget {budget}) interleaved with {checked} "
              f"verified queries in {dt:.2f}s")
        print(f"  segments {st['segments']}, delta_docs {st['delta_docs']}"
              f", flushes {st['flushes']} ({st['flush_ms']:.1f} ms), "
              f"compactions {st['compactions']}")
        print("ingest gate OK: interleaved insert/search == "
              "rebuild-from-scratch (boolean + top-k, exact scores)")


def serve_lm(arch_name: str, n_requests: int) -> None:
    import jax
    from ..configs import get_arch
    from ..models import transformer as T
    from ..serve import DecodeEngine, ServeConfig

    cfg = get_arch(arch_name).smoke_config
    params = T.init_params(jax.random.key(0), cfg)
    eng = DecodeEngine(params, cfg, ServeConfig(max_batch=4, s_cache=64,
                                                max_new_tokens=16))
    rng = np.random.default_rng(0)
    for _ in range(n_requests):
        plen = int(rng.integers(3, 12))
        eng.submit(rng.integers(1, cfg.vocab, plen).astype(np.int32))
    t0 = time.perf_counter()
    outs = eng.run_until_drained()
    dt = time.perf_counter() - t0
    toks = sum(len(o) for o in outs)
    print(f"served {len(outs)} requests / {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.0f} tok/s, continuous batching over 4 lanes)")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=("queries", "lm"), default="queries")
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--engine", choices=("host", "jnp", "pallas"),
                    default="jnp")
    ap.add_argument("--builder", choices=("host", "jnp", "pallas"),
                    default="host",
                    help="construction backend (repro.build)")
    ap.add_argument("--refresh", type=int, default=0,
                    help="after serving, rebuild+hot-swap the index this "
                         "many times from a growing PostingsSource")
    ap.add_argument("--data-shards", type=int, default=0,
                    help="shard the index across N devices on a 'data' "
                         "mesh axis (0 = unsharded)")
    ap.add_argument("--query", default=None,
                    help="boolean query string to plan + execute, e.g. "
                         "'(12 AND 40) OR NOT 7' or '\"3 4 5\"'")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="run a Zipf boolean workload with this many "
                         "queries in flight through the coalescing "
                         "scheduler (0 = skip)")
    ap.add_argument("--topk", type=int, default=0,
                    help="run a ranked BM25 top-K workload with block-max "
                         "page pruning and print the pruning telemetry "
                         "(0 = skip)")
    ap.add_argument("--batch-window", type=int, default=None,
                    help="scheduler in-flight window (default: "
                         "--concurrency, or REPRO_BATCH_WINDOW)")
    ap.add_argument("--codec", default=None,
                    choices=("repair", "ef", "bitmap", "adaptive"),
                    help="per-list codec tier (DESIGN.md §10): force one "
                         "codec or 'adaptive' cost-model selection "
                         "(default: repair, or REPRO_CODEC)")
    ap.add_argument("--store", default=None,
                    choices=("memory", "mmap"),
                    help="out-of-core page store (DESIGN.md §11): serve "
                         "the compressed stream from a page store behind "
                         "the bounded admission cache (default: fully "
                         "resident, or REPRO_STORE)")
    ap.add_argument("--resident-pages", type=int, default=None,
                    help="admission-cache budget in pages (default: all "
                         "pages, or REPRO_RESIDENT_PAGES)")
    ap.add_argument("--ingest-rate", type=int, default=0,
                    help="stream this many inserted docs per round "
                         "through the segmented index (DESIGN.md §12), "
                         "interleaved with oracle-verified boolean + "
                         "top-k queries (0 = skip)")
    ap.add_argument("--num-docs", type=int, default=2000,
                    help="base collection size served at launch")
    ap.add_argument("--vocab", type=int, default=4000,
                    help="corpus vocabulary size")
    ap.add_argument("--growth-docs", type=int, default=500,
                    help="documents each --refresh version adds")
    ap.add_argument("--seed", type=int, default=0,
                    help="corpus seed (the PostingsSource key)")
    args = ap.parse_args()
    from .compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.tier == "queries":
        serve_queries(args.n, args.engine, data_shards=args.data_shards,
                      builder=args.builder, refreshes=args.refresh,
                      query=args.query, concurrency=args.concurrency,
                      topk=args.topk, batch_window=args.batch_window,
                      codec=args.codec, store=args.store,
                      resident_pages=args.resident_pages,
                      ingest_rate=args.ingest_rate,
                      num_docs=args.num_docs, vocab=args.vocab,
                      growth_docs=args.growth_docs, seed=args.seed)
    else:
        serve_lm(args.arch, args.n)


if __name__ == "__main__":
    main()
