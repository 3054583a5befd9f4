"""Plain references for the answers the served path produces, and the
comparisons that decide a run's ``correct``.

Nothing here imports the program.  A conjunctive query's answer is the
intersection of its raw posting lists.  A ranked query's answer is BM25
top-k under binary term frequencies, as the configuration states it:

    idf(t)   = ln(1 + (N - df(t) + 0.5) / (df(t) + 0.5))
    w(d)     = (k1 + 1) / (1 + k1 * (1 - b + b * dl(d) / avgdl))
    score(d) = w(d) * sum of idf(t) over the query terms whose list holds d

with N the docs that hold a term, dl(d) the lists that hold d, and avgdl
their mean.  The reference scores every doc in float64 and ranks by
(score desc, doc asc).  The controls (``and_control``, ``topk_control``)
are the same references with one guarantee broken: a conjunctive answer
that loses a posting, and BM25 computed in bfloat16, the precision below
the float32 that the configuration states.
"""

from __future__ import annotations

import functools

import numpy as np


def and_reference(lists, terms) -> np.ndarray:
    """Docs that hold every term."""
    return functools.reduce(
        lambda a, b: np.intersect1d(a, b, assume_unique=True),
        (np.asarray(lists[t], np.int64) for t in terms))


def and_control(lists, terms) -> np.ndarray:
    """The reference with the exactness guarantee broken: the last doc of
    the first term's list is left out of the evaluation, as a decoder
    that drops a list's final posting would."""
    first, rest = terms[0], terms[1:]
    cut = np.asarray(lists[first], np.int64)[:-1]
    return functools.reduce(
        lambda a, b: np.intersect1d(a, b, assume_unique=True),
        (np.asarray(lists[t], np.int64) for t in rest), cut)


class BM25:
    """Per-collection BM25 statistics, float64."""

    def __init__(self, lists, num_docs: int, k1: float, b: float):
        self.lists = [np.asarray(l, np.int64) for l in lists]
        dl = np.zeros(max(1, int(num_docs)), np.int64)
        for lst in self.lists:
            dl[lst] += 1
        n = int((dl > 0).sum())
        avgdl = dl.sum() / max(n, 1)
        df = np.asarray([l.size for l in self.lists], np.float64)
        self.idf = np.log1p((n - df + 0.5) / (df + 0.5))
        self.w = np.where(dl > 0, (k1 + 1.0) / (
            1.0 + k1 * (1.0 - b + b * dl / max(avgdl, 1e-12))), 0.0)

    def scores(self, terms, dtype=np.float64) -> tuple[np.ndarray, np.ndarray]:
        """(docs that hold any term, their scores), summing in ascending
        term order in ``dtype``."""
        ts = sorted({int(t) for t in terms})
        docs = np.unique(np.concatenate([self.lists[t] for t in ts]))
        acc = np.zeros(docs.size, dtype)
        for t in ts:
            hit = np.isin(docs, self.lists[t], assume_unique=True)
            acc = (acc + np.where(hit, dtype(self.idf[t]), dtype(0))
                   ).astype(dtype)
        return docs, (self.w[docs].astype(dtype) * acc).astype(dtype)

    def topk(self, terms, k: int, dtype=np.float64
             ) -> tuple[np.ndarray, np.ndarray]:
        docs, s = self.scores(terms, dtype)
        order = np.lexsort((docs, -s.astype(np.float64)))[:k]
        return docs[order], s[order].astype(np.float64)


def bf16(x) -> np.ndarray:
    """Round float64 values to bfloat16 (nearest even), kept in float64."""
    f = np.asarray(x, np.float32)
    u = f.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).astype(np.float64)


def topk_control(bm: BM25, terms, k: int) -> tuple[np.ndarray, np.ndarray]:
    """BM25 top-k computed in bfloat16: each idf, each doc weight, each
    partial sum and each product rounded to bfloat16."""
    ts = sorted({int(t) for t in terms})
    docs = np.unique(np.concatenate([bm.lists[t] for t in ts]))
    acc = np.zeros(docs.size)
    for t in ts:
        hit = np.isin(docs, bm.lists[t], assume_unique=True)
        acc = bf16(acc + np.where(hit, bf16(bm.idf[t]), 0.0))
    s = bf16(bf16(bm.w[docs]) * acc)
    order = np.lexsort((docs, -s))[:k]
    return docs[order], s[order]


def topk_gap(bm: BM25, terms, k: int, docs, scores) -> float:
    """Widest gap of one ranked answer, as a share of the best reference
    score: at each rank, how far the answer's doc lies below the
    reference's score at that rank, and how far the answer's score lies
    from its doc's reference score.  An answer of the wrong length, with
    a doc twice, or with a doc that holds none of the terms reads 1."""
    ref_docs, ref_scores = bm.topk(terms, k)
    docs = np.asarray(docs, np.int64)
    scores = np.asarray(scores, np.float64)
    if docs.size != ref_docs.size or np.unique(docs).size != docs.size:
        return 1.0
    if docs.size == 0:
        return 0.0
    all_docs, all_scores = bm.scores(terms)
    pos = np.searchsorted(all_docs, docs)
    pos = np.minimum(pos, all_docs.size - 1)
    if not np.array_equal(all_docs[pos], docs):
        return 1.0
    true = all_scores[pos]
    gap = np.maximum(np.abs(ref_scores - true), np.abs(scores - true))
    return float(gap.max() / max(ref_scores[0], 1e-30))
