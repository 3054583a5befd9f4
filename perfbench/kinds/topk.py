"""BM25 top-``k`` queries (the mix's ``k``): the answer's docs and scores
are compared with the float64 reference under the configuration's
``guarantees``."""

from __future__ import annotations

import numpy as np

import reference


def submit(srv, terms, mix: dict) -> int:
    return srv.submit_topk(terms, int(mix["k"]))


def prime(engine, old) -> None:
    """Hand the engine the scoring tier of ``old``, the engine it
    replaced over the same index, and run one score round."""
    if old is not None:
        engine.set_score_index(old.score_index)
        engine.dispatch_score_round(np.zeros(1, np.int32))


def check(cfg: dict, mix: dict, lists, num_docs: int, recs,
          control: bool) -> dict:
    """``score_gap``: the widest gap of the answers, as a share of each
    query's best reference score (``reference.topk_gap``), against the
    configuration's limit.  ``control`` puts the bfloat16 reference in
    the program's place."""
    g = cfg["guarantees"]
    bm = reference.BM25(lists, num_docs, g["bm25_k1"], g["bm25_b"])
    k = int(mix["k"])
    gap = 0.0
    for r in recs:
        if r.answer is None:
            continue
        if control:
            docs, scores = reference.topk_control(bm, r.query, k)
        else:
            docs, scores = r.answer.docs, r.answer.scores
        gap = max(gap, reference.topk_gap(bm, r.query, k, docs, scores))
    return {"score_gap": {"value": gap, "limit": g["score_gap_limit"]}}
