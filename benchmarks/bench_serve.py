"""Serving-throughput benchmark: the cross-query batching runtime
(DESIGN.md §8.4) and the hot-path dedup layer on top (DESIGN.md §13).

A Zipf boolean/phrase workload (``common.boolean_workload``) is driven
through the coalescing scheduler at concurrency {1, 8, 64} per engine
backend, each cell twice: with cross-query lane dedup ON (the default
serving configuration) and OFF (the dispatch-every-lane path).
Concurrency 1 is the serial baseline (batch window 1 — one query in
flight, coalescing factor exactly 1); higher windows let the
scheduler merge the pending probe rounds of all in-flight queries into
shared device dispatches.  Reported per cell: qps, p50/p95 latency, the
mean coalescing factor, and the lane ledger — ``real_lanes`` (what the
queries asked for), ``unique_lanes`` (what survived dedup),
``pad_lanes`` (pow2 filler; reported separately so no factor counts
padding as work), plus ``dedup_factor``.

Every result is oracle-checked on a warmup pass before timing, so a qps
number can never come from a wrong answer.  Honest-numbers note (same
as BENCH_build): on a 2-core CPU box the host engine wins on raw qps — the device engines pay interpreter/XLA dispatch costs that
batching amortizes but cannot erase; the coalescing factor and lane
ledger are the hardware-portable signal (on a real accelerator each
merged dispatch is one kernel launch, and every deduped lane is device
work that never happens).

  PYTHONPATH=src python -m benchmarks.run --only serve
  PYTHONPATH=src python -m benchmarks.bench_serve --engine host,jnp
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from repro.core.jax_index import build_flat_index
from repro.core.repair import repair_compress
from repro.engine import make_engine, validate_engines
from repro.query import naive_eval
from repro.serve.scheduler import QueryScheduler

from .common import BENCH_SEED, boolean_workload, corpus_lists, emit

DEFAULT_ENGINES = ("host", "jnp", "pallas")
CONCURRENCY = (1, 8, 64)

CORPUS = dict(num_docs=600, vocab_size=1500, mean_doc_len=60)


def run(engines=DEFAULT_ENGINES, n_queries=64) -> list[dict]:
    lists, num_docs = corpus_lists(**CORPUS)
    res = repair_compress(lists)
    fi = build_flat_index(res)
    queries = boolean_workload(len(lists), [len(l) for l in lists],
                               n_queries=n_queries)
    oracle = [naive_eval(q, lists, res.universe) for q in queries]

    rows = []
    for name in engines:
        kwargs = {"fi": fi} if name in ("jnp", "pallas") else {}
        for dedup_on in (True, False):
            eng = make_engine(name, res, **kwargs)
            eng.dedup = dedup_on
            for conc in CONCURRENCY:
                # warmup pass: jit compilation + the correctness gate
                warm = QueryScheduler(eng, batch_window=conc,
                                      result_cache_size=0)
                for got, want in zip(warm.search_many(queries), oracle):
                    np.testing.assert_array_equal(got, want)
                # timed pass on a fresh scheduler (result cache off: we
                # are timing execution, not memoization of whole results)
                sch = QueryScheduler(eng, batch_window=conc,
                                     result_cache_size=0)
                t0 = time.perf_counter()
                sch.search_many(queries)
                dt = time.perf_counter() - t0
                st = sch.stats()
                rows.append({
                    "engine": name,
                    "concurrency": conc,
                    "dedup": dedup_on,
                    "n_queries": len(queries),
                    "qps": len(queries) / dt,
                    "p50_ms": st["p50_ms"],
                    "p95_ms": st["p95_ms"],
                    "coalescing_factor": st["coalescing_factor"],
                    "dispatches": st["dispatches"],
                    "merged_lanes": st["merged_lanes"],
                    "real_lanes": st["real_lanes"],
                    "unique_lanes": st["unique_lanes"],
                    "pad_lanes": st["pad_lanes"],
                    "dispatched_lanes": st["dispatched_lanes"],
                    "dedup_factor": st["dedup_factor"],
                })
                emit(rows[-1:], f"{name} × concurrency {conc} × "
                                f"dedup={'on' if dedup_on else 'off'}")
    return rows


def main(engines=DEFAULT_ENGINES, n_queries=64) -> dict:
    validate_engines(engines)
    rows = run(engines, n_queries)
    qps = {f"{r['engine']}/c{r['concurrency']}"
           f"/{'on' if r['dedup'] else 'off'}": r["qps"] for r in rows}
    # dedup delta at the widest concurrency: ON qps / OFF qps per engine
    speedup = {}
    for name in engines:
        on = next(r for r in rows if r["engine"] == name
                  and r["concurrency"] == CONCURRENCY[-1] and r["dedup"])
        off = next(r for r in rows if r["engine"] == name
                   and r["concurrency"] == CONCURRENCY[-1]
                   and not r["dedup"])
        speedup[name] = on["qps"] / off["qps"]
        assert on["dedup_factor"] > 1.0, \
            f"{name}: Zipf traffic must dedup ({on['dedup_factor']})"
    assert max(speedup.values()) > 1.0, \
        f"dedup should win somewhere at c{CONCURRENCY[-1]}: {speedup}"
    return {
        "seed": BENCH_SEED,
        "corpus": CORPUS,
        "concurrency": list(CONCURRENCY),
        "rows": rows,
        "qps": qps,
        "coalescing": {f"{r['engine']}/c{r['concurrency']}"
                       f"/{'on' if r['dedup'] else 'off'}":
                       r["coalescing_factor"] for r in rows},
        "dedup_speedup_at_max_conc": speedup,
    }


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", type=str, default=",".join(DEFAULT_ENGINES))
    ap.add_argument("--n", type=int, default=64)
    args = ap.parse_args()
    main(engines=tuple(args.engine.split(",")), n_queries=args.n)
