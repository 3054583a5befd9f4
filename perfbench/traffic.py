"""The one query generator: reads a mix's parameter file and makes its
queries from the seed.

A mix file (``traffic/<name>.json``) holds:

* ``source``: the public query log whose shape the mix follows, and
  ``assumed``: each value that the source does not give;
* ``kind``: the query kind, served and checked by ``kinds/<kind>.py``
  (``"and"``: conjunctive boolean; ``"topk"``: BM25 top-``k``);
* ``arrivals``: the arrival process, driven by
  ``arrivals/<arrivals>.py`` with its own parameters (``rate_qps``,
  ``clients``);
* ``terms_per_query``: shares of each query length, in twentieths;
* ``stopwords``: how many of the most frequent terms (the longest lists)
  never appear in a query, as an engine's stop list drops them;
* ``batch_window``, ``server``: the scheduler's in-flight window and
  server settings;
* ``warmup``: phases served before the window, each for ``seconds`` with
  its own ``rate_qps`` or ``clients``, or ``{"replay": true}``, which
  serves the window's own queries first.

Query terms follow the simulated known-item queries of Azzopardi, de
Rijke and Balog ("Building simulated queries for known-item topics",
SIGIR 2007) with uniform term selection: a document is picked uniformly,
and the query's terms are drawn uniformly, without repetition, from that
document's terms that are not stop words.  A term's chance follows its
document frequency, and every conjunctive query has an answer.

Each block of 20 queries of a stream holds the same queries for every
seed, in an order drawn from the seed, so every seed brings the same
work.
"""

from __future__ import annotations

import numpy as np

from corpus import rng_for

BLOCK = 20


def block_lengths(shares: dict) -> np.ndarray:
    """The query lengths of one block of ``BLOCK`` queries."""
    out = []
    for n, share in sorted(shares.items(), key=lambda kv: int(kv[0])):
        count = share * BLOCK
        if abs(count - round(count)) > 1e-9:
            raise ValueError(f"share {share} of {n} terms is not in "
                             f"twentieths")
        out += [int(n)] * int(round(count))
    if len(out) != BLOCK:
        raise ValueError(f"terms_per_query shares sum to {len(out)}/"
                         f"{BLOCK}")
    return np.asarray(out, np.int64)


def stopword_terms(lists, count: int) -> np.ndarray:
    """The ``count`` terms with the longest lists."""
    lens = np.fromiter((len(l) for l in lists), np.int64, len(lists))
    return np.argsort(-lens, kind="stable")[:int(count)]


class QueryStream:
    """Query ``i`` of one stream is fixed by (seed, stream, i)."""

    def __init__(self, mix: dict, lists, seed: int, stream: int):
        self.seed = seed
        self.stream = stream
        stop = np.zeros(len(lists), bool)
        stop[stopword_terms(lists, mix.get("stopwords", 0))] = True
        keep = np.flatnonzero(~stop)
        lens = np.fromiter((len(lists[t]) for t in keep), np.int64,
                           keep.size)
        # the forward index of the terms a query may hold
        terms = np.repeat(keep, lens)
        docs = np.concatenate([np.asarray(lists[t], np.int64)
                               for t in keep])
        order = np.lexsort((terms, docs))
        docs, self.terms = docs[order], terms[order]
        self.starts = np.flatnonzero(np.r_[True, np.diff(docs) != 0])
        self.ends = np.r_[self.starts[1:], docs.size]
        self.lengths = block_lengths(mix["terms_per_query"])
        if int((self.ends - self.starts).max()) < int(self.lengths.max()):
            raise ValueError("no document holds enough terms for the "
                             "longest query")
        self._blocks: dict[int, np.ndarray] = {}

    def _slot(self, i: int) -> tuple[int, int]:
        """(block, slot of the block's sorted lengths) of query ``i``."""
        b = i // BLOCK
        if b not in self._blocks:
            self._blocks[b] = rng_for(self.seed, 3, self.stream, b
                                      ).permutation(BLOCK)
        return b, int(self._blocks[b][i % BLOCK])

    def take(self, n: int) -> list[list[int]]:
        """The next ``n`` queries of the stream, each a sorted list of
        distinct term ids."""
        start = getattr(self, "_next", 0)
        out = []
        for i in range(start, start + n):
            b, j = self._slot(i)
            k = int(self.lengths[j])
            rng = rng_for(0, 5, self.stream, b, j)
            while True:
                d = int(rng.integers(self.starts.size))
                lo, hi = int(self.starts[d]), int(self.ends[d])
                if hi - lo >= k:
                    break
            pick = rng.choice(hi - lo, k, replace=False)
            out.append(sorted(int(t) for t in self.terms[lo + pick]))
        self._next = start + n
        return out
