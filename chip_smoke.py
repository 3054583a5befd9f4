"""Bring-up smoke run of the query-serving path on a TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded path only

One process builds a ``PostingsSource`` collection (16,000 docs, vocab
4000, seed 0 by default) with the host Re-Pair builder and serves it
through the objects ``python -m repro.launch.serve --tier queries`` uses:
``QueryServer(engine="pallas")`` and its ``QueryScheduler``.  Phases:

1. device check — ``jax.devices()`` must be TPU;
2. build (set-up time);
3. conjunctive pairs through ``and_batch``;
4. the launcher's Zipf boolean workload through ``search_many`` at
   concurrency 64;
5. BM25 top-k through ``search_topk_many`` at k=10 and k=100;
6. the adaptive codec tier (plus a forced Elias-Fano tier when the cost
   model gives Elias-Fano no probe lanes);
7. out of core: the benchmark's ``gov2-web-ooc`` shard
   (``perfbench/configs/gov2-web-ooc.json``: the ``gov2-web`` collection,
   its stream pages in a memory page store, a quarter of them resident)
   served AND queries through the scheduler eight at a time;
8. the pallas builder at 2,000 docs, bit-identical to the host builder.

Every answer of every phase is compared with a plain reference
(``np.intersect1d`` and ``HostEngine`` for pairs, ``naive_eval`` for
boolean queries, ``rank_oracle`` for top-k, the intersection of the raw
lists out of core).  The run fails when JAX finds
no TPU, when any answer differs, when a pallas engine or builder would
interpret its kernels, or when one of ``list_intersect``, ``page_score``,
``ef_next_geq`` and ``pair_count`` did not launch compiled, or when a
launch of ``list_intersect`` or ``page_score`` did not count its table
lookups on the MXU (``gather.mxu.<kernel>``), or when the out-of-core
phase faulted no page or grew its pool (``store.*`` counters).  With
``--chips 4`` the run serves the boolean workload from
``QueryServer(mesh=...)`` over four chips and compares it with the
one-chip engine and the oracle.  Each phase prints its wall time and
compile count; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

KERNELS = ("list_intersect", "page_score", "ef_next_geq", "pair_count")
MXU_KERNELS = ("list_intersect", "page_score")   # table lookups on the MXU
VOCAB = 4000          # PostingsSource vocabulary
PAIRS = 256           # conjunctive pairs in the pairs phase
CONCURRENCY = 64      # scheduler batch window of the boolean phases
BUILD_DOCS = 2000     # the launcher's default corpus, pallas-builder phase
OOC_CONFIG = "gov2-web-ooc"   # the out-of-core phase's benchmark shard
OOC_MIX = "and-web-closed8"   # and its queries
OOC_QUERIES = 400
STORE_COUNTERS = ("store.lookups", "store.hits", "store.faults",
                  "store.fault_bytes", "store.pool_grows")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileMeter:
    """Counts executables JAX obtains (compiled or read back from the
    persistent cache), the seconds spent obtaining them, and cache hits."""

    def __init__(self, jax):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == CACHE_HIT:
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.seconds, self.cache_hits


def require(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def run_phase(meter, name: str, fn):
    """Run one phase; print its wall time and compiles.  Whatever the
    phase raises propagates and fails the run."""
    c0, s0, h0 = meter.snapshot()
    t0 = time.perf_counter()
    detail = fn()
    wall = time.perf_counter() - t0
    c1, s1, h1 = meter.snapshot()
    print(f"phase {name}: {wall:.3f} s wall, {c1 - c0} compiles "
          f"({s1 - s0:.3f} s), {h1 - h0} persistent-cache hits"
          + (f"; {detail}" if detail else ""), flush=True)


def build_collection(num_docs: int, seed: int):
    """The launcher's corpus: ``PostingsSource`` version 0, inverted."""
    import numpy as np
    from repro.data.pipeline import PostingsSource

    src = PostingsSource(base_docs=num_docs, vocab=VOCAB, seed=seed)
    inv: dict[int, list[int]] = {}
    for d, terms in enumerate(src.deltas_at(0)):
        for t in terms.tolist():
            inv.setdefault(int(t), []).append(d)
    return [np.asarray(inv[t], np.int64) for t in sorted(inv)]


def check_boolean(srv, lists, universe, queries):
    """Serve the workload through the scheduler; every answer must equal
    ``naive_eval`` over the raw lists.  Returns the answers."""
    import numpy as np
    from repro.query import naive_eval
    from repro.query.parser import parse

    srv.scheduler.batch_window = CONCURRENCY
    outs = srv.search_many(queries)
    for q, got in zip(queries, outs):
        np.testing.assert_array_equal(
            got, naive_eval(parse(q, None), lists, universe), err_msg=q)
    return outs


def one_chip(args, meter, lists, res) -> None:
    import numpy as np
    from repro.build import make_builder
    from repro.engine import HostEngine
    from repro import obs
    from repro.kernels import LAUNCHES, MXU_PREFIX
    from repro.launch.serve import (SCORE_PAGE, boolean_workload,
                                    ranked_workload)
    from repro.query import rank_oracle
    from repro.serve.query_serve import QueryServer

    srv = QueryServer(res, engine="pallas", max_short_len=256)
    require(srv.engine.interpret is False, "pallas engine would interpret")
    print(f"stream: {srv.engine.pi.num_pages} pages of "
          f"{srv.engine.pi.page_size} symbols", flush=True)
    queries = boolean_workload(lists, 4 * CONCURRENCY)

    def pairs_phase():
        rng = np.random.default_rng(0)
        pairs = [tuple(map(int, rng.choice(len(lists), 2, replace=False)))
                 for _ in range(PAIRS)]
        outs = srv.and_batch(pairs)
        want = HostEngine(res).intersect_pairs(pairs)
        for (a, b), got, ref in zip(pairs, outs, want):
            np.testing.assert_array_equal(got, ref)
            np.testing.assert_array_equal(got,
                                          np.intersect1d(lists[a], lists[b]))
        return (f"{len(pairs)} pairs, {sum(map(len, outs))} hits, "
                f"host routes {srv.engine.host_routes}")

    def boolean_phase():
        outs = check_boolean(srv, lists, res.universe, queries)
        st = srv.serve_stats()
        return (f"{len(queries)} queries, {sum(map(len, outs))} hits, "
                f"{st['dispatches']} merged dispatches, "
                f"host routes {st['host_routes']}")

    def topk_phase():
        srv.engine.score_page_size = SCORE_PAGE
        bags = ranked_workload(lists, 16)
        before = LAUNCHES["page_score"]
        for k in (10, 100):
            for bag, got in zip(bags, srv.search_topk_many(bags, k)):
                docs, scores = rank_oracle(lists, res.universe, bag, k)
                np.testing.assert_array_equal(got.docs, docs)
                np.testing.assert_array_equal(got.scores, scores)
        n = LAUNCHES["page_score"] - before
        require(n > 0, "top-k never launched page_score")
        st = srv.serve_stats()
        return (f"{len(bags)} bags x k=10,100, {n} page_score launches, "
                f"pages scored {st['pages_scored']} / skipped "
                f"{st['pages_skipped']}, host routes {st['host_routes']}")

    def codec_phase():
        notes = []
        for codec in ("adaptive", "ef"):
            before = LAUNCHES["ef_next_geq"]
            csrv = QueryServer(res, engine="pallas", max_short_len=256,
                               codec=codec)
            require(csrv.engine.interpret is False,
                    "pallas engine would interpret")
            check_boolean(csrv, lists, res.universe, queries)
            n = LAUNCHES["ef_next_geq"] - before
            notes.append(f"{codec}: tiers {csrv.engine.tier.counts()}, "
                         f"{n} ef_next_geq launches, host routes "
                         f"{csrv.serve_stats()['host_routes']}")
            if n:
                break
        require(n > 0, "no codec tier launched ef_next_geq")
        return "; ".join(notes)

    def ooc_phase():
        ooc = Path(__file__).resolve().parent / "perfbench"
        sys.path.insert(0, str(ooc))
        import corpus
        import traffic
        cfg = json.loads((ooc / "configs" / f"{OOC_CONFIG}.json").read_text())
        mix = json.loads((ooc / "traffic" / f"{OOC_MIX}.json").read_text())
        olists, _ = corpus.make_collection(cfg, int(cfg["collection_seed"]))
        ores = make_builder("host").build_grammar(olists)
        osrv = QueryServer(ores, engine="pallas", max_short_len=256,
                           batch_window=int(mix["clients"]), **cfg["server"])
        require(osrv.engine.interpret is False,
                "pallas engine would interpret")
        queries = traffic.QueryStream(mix, olists, args.seed, 0).take(
            OOC_QUERIES)
        before = {k: obs.counter(k) for k in STORE_COUNTERS}
        outs = osrv.search_many([" AND ".join(map(str, q))
                                 for q in queries])
        for q, got in zip(queries, outs):
            want = olists[q[0]]
            for t in q[1:]:
                want = np.intersect1d(want, olists[t])
            np.testing.assert_array_equal(got, want, err_msg=str(q))
        moved = {k: obs.counter(k) - before[k] for k in STORE_COUNTERS}
        st = osrv.engine.resident.stats()
        require(moved["store.faults"] > 0, "out of core: no page faulted")
        require(moved["store.pool_grows"] == 0,
                f"out of core: the pool grew to {st['budget']} pages")
        return (f"{len(queries)} queries, {sum(map(len, outs))} hits, "
                f"{st['num_pages']} pages, {st['budget']} resident, "
                f"counters {moved}, evictions {st['page_evictions']}, "
                f"host routes {osrv.serve_stats()['host_routes']}")

    def build_phase():
        small = build_collection(BUILD_DOCS, args.seed)
        host = make_builder("host", table_cap=4096).build_grammar(small)
        bld = make_builder("pallas", table_cap=4096)
        require(bld.interpret is False, "pallas builder would interpret")
        before = LAUNCHES["pair_count"]
        t0 = time.perf_counter()
        dev = bld.build_grammar(small)
        dt = time.perf_counter() - t0
        for field in ("rules", "sums", "lengths", "depths"):
            np.testing.assert_array_equal(getattr(dev.grammar, field),
                                          getattr(host.grammar, field))
        for field in ("seq", "starts", "first_values"):
            np.testing.assert_array_equal(getattr(dev, field),
                                          getattr(host, field))
        return (f"{BUILD_DOCS} docs, {dev.grammar.num_rules} rules "
                f"bit-identical to the host builder, pallas build "
                f"{dt:.3f} s, {LAUNCHES['pair_count'] - before} "
                f"pair_count launches")

    run_phase(meter, "pairs", pairs_phase)
    run_phase(meter, "boolean", boolean_phase)
    run_phase(meter, "topk", topk_phase)
    run_phase(meter, "codec", codec_phase)
    run_phase(meter, "out-of-core", ooc_phase)
    run_phase(meter, "build", build_phase)

    print(f"kernel launches: {dict(LAUNCHES)}", flush=True)
    missing = [k for k in KERNELS if not LAUNCHES[k]]
    interpreted = [k for k in LAUNCHES if k.endswith("[interpret]")]
    require(not missing and not interpreted,
            f"kernels not launched compiled: {missing}; interpreted: "
            f"{interpreted}")
    mxu = {k: obs.counter(MXU_PREFIX + k) for k in MXU_KERNELS}
    print(f"launches with MXU table lookups: {mxu}", flush=True)
    wrong = {k: n for k, n in mxu.items() if n != LAUNCHES[k]}
    require(not wrong, f"launches with MXU lookups differ: {wrong}")


def four_chips(args, meter, lists, res, devices) -> None:
    import numpy as np
    from jax.sharding import Mesh
    from repro.launch.serve import boolean_workload
    from repro.serve.query_serve import QueryServer

    mesh = Mesh(np.array(devices[:4]), ("data",))
    queries = boolean_workload(lists, 4 * CONCURRENCY)

    def sharded_phase():
        sharded = QueryServer(res, engine="pallas", max_short_len=256,
                              mesh=mesh)
        single = QueryServer(res, engine="pallas", max_short_len=256)
        for s in (sharded, single):
            require(s.engine.interpret is False,
                    "pallas engine would interpret")
        require(sharded.engine._sharded_next_geq is not None,
                "the mesh server has no sharded dispatch")
        got = check_boolean(sharded, lists, res.universe, queries)
        ref = check_boolean(single, lists, res.universe, queries)
        for q, a, b in zip(queries, got, ref):
            np.testing.assert_array_equal(a, b, err_msg=q)
        return (f"{len(queries)} queries over a 4-chip data mesh equal "
                f"the one-chip engine and naive_eval, host routes "
                f"{sharded.serve_stats()['host_routes']}")

    run_phase(meter, "sharded-boolean", sharded_phase)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--num-docs", type=int, default=16000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"no TPU: JAX found {len(devices)} {platform} device(s)",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    print(f"device: {kind} x {len(devices)} ({platform})", flush=True)
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    meter = CompileMeter(jax)

    from repro.build import make_builder
    t0 = time.perf_counter()
    lists = build_collection(args.num_docs, args.seed)
    res = make_builder("host").build_grammar(lists)
    setup = time.perf_counter() - t0
    print(f"collection: {args.num_docs} docs, {len(lists)} lists, "
          f"{sum(map(len, lists))} postings, {res.grammar.num_rules} "
          f"rules, {len(res.seq)} stream symbols, set-up {setup:.3f} s",
          flush=True)

    if args.chips == 4:
        four_chips(args, meter, lists, res, devices)
    else:
        one_chip(args, meter, lists, res)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
