"""Seeded synthetic document collection shaped by one configuration file.

A document is a set of distinct term ids.  Its size is drawn from a
log-normal around the configuration's ``distinct_terms_per_doc``; its
terms are drawn, as tokens with repetition, from a Zipf unigram
distribution over ``vocab`` candidate terms, with a band of the
vocabulary boosted by the document's topic.  Topics change every
``topic_block`` documents, so neighbouring doc ids share terms as the
pages of one web site do in a crawl-ordered collection (docid
clustering).  The same seed gives the same collection; a cell
serves the one drawn from its configuration's ``collection_seed`` in
every run, and a run's own seed draws only its queries and their order.

The shape follows ``repro.data.pipeline.PostingsSource`` (topic bands
over a Zipf base), with every constant taken from the configuration.
"""

from __future__ import annotations

import numpy as np

CORPUS_KEYS = ("docs", "distinct_terms_per_doc", "doc_size_sigma", "vocab",
               "zipf_s", "topics", "topic_block", "topic_strength",
               "topic_drift")


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one use of ``seed`` (any whole number)."""
    return np.random.default_rng(
        np.random.SeedSequence([int(seed) % (1 << 64), *stream]))


def topic_cdfs(cfg: dict) -> np.ndarray:
    """(topics, vocab) cumulative unigram distributions: a Zipf base with
    one contiguous band of the vocabulary boosted per topic."""
    V, T = int(cfg["vocab"]), int(cfg["topics"])
    base = np.arange(1, V + 1, dtype=np.float64) ** -float(cfg["zipf_s"])
    cdfs = np.empty((T, V), np.float64)
    for t in range(T):
        p = base.copy()
        p[t * V // T:(t + 1) * V // T] *= float(cfg["topic_strength"])
        cdfs[t] = np.cumsum(p / p.sum())
    cdfs[:, -1] = 1.0
    return cdfs


def generate_docs(cfg: dict, seed: int) -> list[np.ndarray]:
    """The collection: one sorted array of distinct term ids per doc."""
    rng = rng_for(seed, 1)
    n_docs = int(cfg["docs"])
    V = int(cfg["vocab"])
    mean = float(cfg["distinct_terms_per_doc"])
    sigma = float(cfg["doc_size_sigma"])
    # log-normal sizes with the configured mean
    sizes = np.exp(rng.normal(np.log(mean) - sigma * sigma / 2, sigma,
                              n_docs))
    sizes = np.clip(np.rint(sizes), 4, V // 4).astype(np.int64)
    cdfs = topic_cdfs(cfg)
    topics = (np.arange(n_docs) // int(cfg["topic_block"])) % len(cdfs)
    drift = rng.random(n_docs) < float(cfg["topic_drift"])
    topics = np.where(drift, rng.integers(0, len(cdfs), n_docs), topics)
    docs = []
    for n, t in zip(sizes.tolist(), topics.tolist()):
        got = np.empty(0, np.int64)
        draw = 2 * n + 16
        while got.size < n:
            toks = np.searchsorted(cdfs[t], rng.random(draw), side="right")
            allt = np.concatenate([got, toks])
            _, first = np.unique(allt, return_index=True)
            got = allt[np.sort(first)][:n]
            draw *= 2
        docs.append(np.sort(got))
    return docs


def invert(docs: list[np.ndarray]) -> list[np.ndarray]:
    """Posting lists of the terms present, in term-id order, each a
    sorted int64 array of doc ids."""
    sizes = np.fromiter((d.size for d in docs), np.int64, len(docs))
    terms = np.concatenate(docs)
    doc_ids = np.repeat(np.arange(len(docs), dtype=np.int64), sizes)
    order = np.lexsort((doc_ids, terms))
    terms, doc_ids = terms[order], doc_ids[order]
    cuts = np.flatnonzero(np.diff(terms)) + 1
    return np.split(doc_ids, cuts)


def make_collection(cfg: dict, seed: int) -> tuple[list[np.ndarray], int]:
    """(posting lists, number of docs) of the configuration at ``seed``."""
    docs = generate_docs(cfg, seed)
    return invert(docs), len(docs)
