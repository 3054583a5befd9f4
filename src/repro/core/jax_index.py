"""Device-resident form of the Re-Pair compressed inverted index.

This is the TPU adaptation of the paper's query-time structures (DESIGN.md
§2).  The host-side construction artifacts are flattened into fixed-width
int32 arrays that support *vectorized* versions of the paper's operations:

* the grammar becomes four symbol-indexed tables (``sym_left``, ``sym_right``,
  ``sym_sum``, ``sym_len``) — the paper's observation that "the dictionary
  ... can realistically fit in RAM" becomes *the dictionary fits in VMEM*;
* the compressed sequence ``C`` stays one int32 stream with per-list spans;
* the (b)-sampling becomes flattened bucket tables with a **static scan
  bound** (max symbols overlapping one bucket) and a **static descent bound**
  (max rule depth, O(log n) by §4) so every query runs the same instruction
  sequence — a fixed-trip-count program, which is exactly what the VPU wants.

Symbols are re-encoded densely: ids ``0..T-1`` are the distinct terminal gap
values that actually occur (value table ``term_value``), ids ``T..T+R-1`` are
rules.  This keeps tables small even when some gaps are huge.

``FlatIndex`` is a **registered JAX pytree** (DESIGN.md §2.3): the arrays are
pytree leaves, the static bounds (``num_terminals``, ``max_depth``,
``max_scan``, ``universe``) are hashable aux data.  Engines therefore take
the index as a *traced argument* instead of closure-capturing its arrays —
one jit cache entry serves every index rebuild that preserves the static
bounds, and ``jax.tree.flatten`` / ``unflatten`` round-trip it losslessly.
"""

from __future__ import annotations

import dataclasses
import os

import numpy as np

import jax
import jax.numpy as jnp

from .repair import RePairResult
from .sampling import BSampling, build_b_sampling, _phrase_sums_for

INT_INF = np.int32(2**31 - 1)

#: Default stream page size (symbols per page).  Must be a multiple of the
#: 128-lane width; overridable via REPRO_PAGE_SIZE so CI can force the
#: multi-page (grid-blocked) kernel path on tiny corpora.
DEFAULT_PAGE = int(os.environ.get("REPRO_PAGE_SIZE", "2048"))

#: BM25 parameters (DESIGN.md §9.1).  The postings are binary (tf == 1 for
#: every posting — doc ids, no positions at the doc level), so the classic
#: tf saturation term collapses to a per-document weight; k1/b keep their
#: standard roles through that weight.
BM25_K1 = 0.9
BM25_B = 0.4


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class FlatIndex:
    """All arrays are jnp int32 unless noted.  L lists, S symbols (dense
    re-encoding), R rules, total C length N.

    Pytree: array fields are leaves; the four ints are static aux data, so
    jit functions taking a ``FlatIndex`` retrace only when a *bound*
    changes, never when array contents change (DESIGN.md §2.3).
    """

    # grammar tables (size S = num_dense_terminals + R)
    sym_left: jax.Array     # child symbol id, -1 for terminals
    sym_right: jax.Array
    sym_sum: jax.Array      # phrase sum (terminal -> its gap value)
    sym_len: jax.Array      # expanded length (terminal -> 1)

    # compressed stream
    c: jax.Array            # (N,) dense symbol ids
    starts: jax.Array       # (L+1,)
    firsts: jax.Array       # (L,)
    lengths: jax.Array      # (L,) uncompressed lengths
    lasts: jax.Array        # (L,) last element of each list

    # (b)-sampling, flattened
    kbits: jax.Array        # (L,) per-list bucket shift
    bucket_offsets: jax.Array  # (L+1,) into the two arrays below
    bck_c_pos: jax.Array    # per-bucket symbol offset within the list span
    bck_abs: jax.Array      # per-bucket absolute value before that symbol

    # static bounds — aux data, not leaves
    num_terminals: int = dataclasses.field(metadata=dict(static=True))
    max_depth: int = dataclasses.field(metadata=dict(static=True))
    max_scan: int = dataclasses.field(metadata=dict(static=True))
    universe: int = dataclasses.field(metadata=dict(static=True))


def _dense_remap(syms: np.ndarray, term_values: np.ndarray,
                 nt: int) -> np.ndarray:
    """Old symbol ids -> dense ids: terminals map through ``term_values``
    (searchsorted — exact because every used terminal is in the table),
    rules shift down to ``T + rule_index``."""
    syms = np.asarray(syms, dtype=np.int64)
    T = term_values.size
    is_rule = syms >= nt
    out = np.empty(syms.shape, dtype=np.int32)
    out[~is_rule] = np.searchsorted(term_values, syms[~is_rule])
    out[is_rule] = (T + (syms[is_rule] - nt)).astype(np.int32)
    return out


def build_flat_index(res: RePairResult, B: int = 8,
                     bsamp: BSampling | None = None) -> FlatIndex:
    """Flatten a :class:`RePairResult` (+ its (b)-sampling) to device arrays.

    Fully vectorized: no per-rule or per-symbol Python loops — the grammar
    tables, dense re-encoding, bucket flattening, scan bound, and per-list
    lasts are all numpy index arithmetic, so index build is O(N + R + #buckets)
    in C, not O(R) interpreted.
    """
    g = res.grammar
    nt = g.num_terminals
    R = g.num_rules
    L = res.num_lists

    # Dense terminal re-encoding: distinct terminal values used in C or as
    # rule children.
    pools = [np.unique(res.seq)]
    if R:
        pools.append(np.unique(g.rules.reshape(-1)))
    used = np.unique(np.concatenate(pools))
    term_values = used[used < nt].astype(np.int64)
    T = term_values.size
    S = T + R

    sym_left = np.full(S, -1, dtype=np.int32)
    sym_right = np.full(S, -1, dtype=np.int32)
    sym_sum = np.zeros(S, dtype=np.int32)
    sym_len = np.ones(S, dtype=np.int32)
    sym_sum[:T] = term_values
    if R:
        sym_left[T:] = _dense_remap(g.rules[:, 0], term_values, nt)
        sym_right[T:] = _dense_remap(g.rules[:, 1], term_values, nt)
        sym_sum[T:] = g.sums.astype(np.int32)
        sym_len[T:] = g.lengths.astype(np.int32)

    c_dense = _dense_remap(res.seq, term_values, nt)

    bs = bsamp or build_b_sampling(res, B)
    kbits = np.asarray(bs.kbits, dtype=np.int32)
    bucket_counts = np.asarray([cp.size for cp in bs.c_pos], dtype=np.int64)
    bucket_offsets = np.zeros(L + 1, dtype=np.int32)
    np.cumsum(bucket_counts, out=bucket_offsets[1:])
    bck_c_pos = (np.concatenate(bs.c_pos) if L else
                 np.zeros(0)).astype(np.int32)
    bck_abs = (np.concatenate(bs.abs_before) if L else
               np.zeros(0)).astype(np.int32)

    # static scan bound: max symbols between consecutive bucket anchors,
    # plus the tail from the final anchor to the end of the list span.
    starts = res.starts.astype(np.int64)
    spans = starts[1:] - starts[:-1]
    max_scan = 1
    if bck_c_pos.size:
        diffs = np.diff(bck_c_pos.astype(np.int64))
        # mask out differences that straddle a list boundary
        keep = np.ones(diffs.size, dtype=bool)
        inner = bucket_offsets[1:-1].astype(np.int64) - 1
        keep[inner[(inner >= 0) & (inner < diffs.size)]] = False
        if keep.any():
            max_scan = max(max_scan, int(diffs[keep].max()) + 1)
    # tail per list: span - last anchor (0 when the list has no buckets)
    last_anchor = np.zeros(L, dtype=np.int64)
    has_b = bucket_counts > 0
    last_anchor[has_b] = bck_c_pos[bucket_offsets[1:][has_b] - 1]
    if L:
        max_scan = max(max_scan, int((spans - last_anchor).max()) + 1)

    sums = _phrase_sums_for(res.seq, g)
    csum = np.concatenate([[0], np.cumsum(sums)])
    lasts = (res.first_values.astype(np.int64)
             + (csum[starts[1:]] - csum[starts[:-1]])).astype(np.int32)

    return FlatIndex(
        sym_left=jnp.asarray(sym_left),
        sym_right=jnp.asarray(sym_right),
        sym_sum=jnp.asarray(sym_sum),
        sym_len=jnp.asarray(sym_len),
        c=jnp.asarray(c_dense),
        starts=jnp.asarray(res.starts.astype(np.int32)),
        firsts=jnp.asarray(res.first_values.astype(np.int32)),
        lengths=jnp.asarray(res.orig_lengths.astype(np.int32)),
        lasts=jnp.asarray(lasts),
        kbits=jnp.asarray(kbits),
        bucket_offsets=jnp.asarray(bucket_offsets),
        bck_c_pos=jnp.asarray(bck_c_pos),
        bck_abs=jnp.asarray(bck_abs),
        num_terminals=T,
        max_depth=max(1, int(g.max_depth())),
        max_scan=max_scan,
        universe=int(res.universe),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedIndex:
    """Paged view of a :class:`FlatIndex` (DESIGN.md §2.5).

    The compressed stream is reshaped into fixed-size pages so device
    consumers address ``(page, offset)`` instead of absolute stream
    positions — per-instance VMEM in the grid-blocked kernel is then a
    function of ``page_size`` and ``max_scan``, never of N.  Two paged
    copies of C are kept (the same trade as the flat kernel operands):
    dense symbol ids and pre-gathered phrase sums ``sym_sum[c]``.

    * ``c_syms_pg, c_sums_pg`` — ``(num_pages, page_size)``, zero-padded
      past N (padding is never selected: every in-kernel read is masked by
      the list span);
    * ``page_dir`` — ``(L+1,)`` per-list page directory: page of each
      list's span start (``starts // page_size``); entry L is the page
      one past the final list;
    * ``bck_page, bck_off`` — the (b)-sampling bucket tables re-addressed
      as (page, offset) of the anchor symbol (absolute position
      ``bck_page * page_size + bck_off == starts[list] + bck_c_pos``).

    Like ``FlatIndex`` it is a registered pytree: arrays are leaves,
    ``page_size`` and ``stored_pages`` are static aux data (``num_pages``
    is just ``c_syms_pg.shape[0]``).  The flat index travels along as a
    nested pytree so paged consumers still see the grammar, spans, and
    static bounds.

    Out-of-core (DESIGN.md §11): when ``stored_pages`` is set, the stream
    pages live behind the engine's :class:`repro.store.PageStore` and
    ``c_syms_pg`` / ``c_sums_pg`` shrink to a ``(1, page_size)``
    placeholder — consumers must dispatch against a resident pool instead
    of these leaves, and ``num_pages`` reports the store's geometry.  The
    store itself is not part of the index, so one trace serves every store
    of a geometry.  The directory/bucket arrays stay real (they are the
    RAM tier, per the paper).
    """

    flat: FlatIndex
    c_syms_pg: jax.Array    # (num_pages, page_size) dense symbol ids
    c_sums_pg: jax.Array    # (num_pages, page_size) phrase sums sym_sum[c]
    page_dir: jax.Array     # (L+1,) first page of each list span
    bck_page: jax.Array     # per-bucket anchor page
    bck_off: jax.Array      # per-bucket offset within the page

    page_size: int = dataclasses.field(metadata=dict(static=True))
    #: Pages behind the engine's page store (0: the stream is on device).
    stored_pages: int = dataclasses.field(default=0,
                                          metadata=dict(static=True))

    @property
    def num_pages(self) -> int:
        if self.stored_pages:
            return self.stored_pages
        return int(self.c_syms_pg.shape[0])


def as_store_backed(pi: PagedIndex, store) -> PagedIndex:
    """Swap a paged index's stream leaves for a placeholder and attach the
    page store that now owns them — after this, any consumer that still
    reads ``c_syms_pg``/``c_sums_pg`` directly sees shapes it cannot miss
    (and the out-of-core differential gate poisons the original arrays to
    prove nothing does)."""
    z = jnp.zeros((1, pi.page_size), jnp.int32)
    return dataclasses.replace(pi, c_syms_pg=z, c_sums_pg=z,
                               stored_pages=int(store.num_pages))


def build_paged_index(fi: FlatIndex,
                      page_size: int = DEFAULT_PAGE) -> PagedIndex:
    """Reshape a flat index's stream into ``(num_pages, page_size)`` pages
    and re-address the bucket tables as (page, offset).  Pure reshaping —
    values are untouched, so paged and flat consumers agree bit-exactly.
    The engines move the stream behind a page store themselves
    (:func:`as_store_backed`)."""
    page_size = max(128, -(-page_size // 128) * 128)  # lane multiple
    c = np.asarray(fi.c, dtype=np.int32)
    sums = np.asarray(fi.sym_sum, dtype=np.int32)[c]
    N = c.size
    num_pages = max(1, -(-N // page_size))
    pad = num_pages * page_size - N
    c_pg = np.pad(c, (0, pad)).reshape(num_pages, page_size)
    s_pg = np.pad(sums, (0, pad)).reshape(num_pages, page_size)

    starts = np.asarray(fi.starts, dtype=np.int64)
    boffs = np.asarray(fi.bucket_offsets, dtype=np.int64)
    bpos = np.asarray(fi.bck_c_pos, dtype=np.int64)
    # absolute anchor position of every bucket: span start + in-span offset
    owner = np.repeat(np.arange(starts.size - 1), np.diff(boffs))
    abs_pos = starts[owner] + bpos

    return PagedIndex(
        flat=fi,
        c_syms_pg=jnp.asarray(c_pg),
        c_sums_pg=jnp.asarray(s_pg),
        page_dir=jnp.asarray((starts // page_size).astype(np.int32)),
        bck_page=jnp.asarray((abs_pos // page_size).astype(np.int32)),
        bck_off=jnp.asarray((abs_pos % page_size).astype(np.int32)),
        page_size=page_size,
    )


# -- ranked scoring: BM25 tables + block-max page directory (DESIGN.md §9) ---

def bm25_idf(df: np.ndarray, ndocs: int) -> np.ndarray:
    """Per-term idf, float64 math rounded ONCE to float32 — the one shared
    rounding point that keeps engine scoring and the brute-force oracle
    bit-identical.  ``log(1 + (N - df + 0.5) / (df + 0.5))`` is the
    non-negative BM25+ variant (df can approach N on Zipf heads)."""
    df = np.asarray(df, np.float64)
    return np.log1p((float(ndocs) - df + 0.5) / (df + 0.5)).astype(np.float32)


def bm25_doc_weights(dl: np.ndarray, avgdl: float, k1: float = BM25_K1,
                     b: float = BM25_B) -> np.ndarray:
    """Per-document BM25 weight under binary postings: with tf == 1 the
    score factorizes as ``score(d) = doc_w[d] * sum(idf[t] : d in list t)``
    where ``doc_w = (k1+1) / (1 + k1*(1 - b + b*dl/avgdl))``.  float64
    math, one float32 rounding; 0 for documents in no list."""
    dl = np.asarray(dl, np.float64)
    w = (k1 + 1.0) / (1.0 + k1 * (1.0 - b + b * dl / max(avgdl, 1e-12)))
    return np.where(dl > 0, w, 0.0).astype(np.float32)


def accumulate_scores(si: "ScoreIndex", terms: np.ndarray,
                      member: np.ndarray, docs: np.ndarray) -> np.ndarray:
    """The ONE scoring reduction (DESIGN.md §9.3): float32 sum of idf over
    the member terms in ASCENDING term-id order, then one float32 multiply
    by the doc weight.  Every backend and the oracle run this exact
    operation sequence, so ranked scores are bit-comparable — float32
    addition is not associative, the fixed order is what buys equality.

    ``terms`` (K,) ascending ids, ``member`` (K, D) bool, ``docs`` (D,)."""
    acc = np.zeros(docs.size, np.float32)
    for j in range(int(terms.size)):
        acc = acc + np.where(member[j], si.idf[int(terms[j])],
                             np.float32(0.0))
    return (si.doc_w[docs] * acc).astype(np.float32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScoreIndex:
    """BM25 scoring tables + the block-max page directory (DESIGN.md §9).

    Piggybacks on the paged stream layout: every posting list is cut at
    the SAME page boundaries the paged kernels DMA by, and each (list,
    page) intersection becomes one *page entry* carrying everything a
    device decode of just that page needs (symbol range, running base
    value, head flag) plus the float32 **upper bound** of any single-term
    contribution ``idf[t] * doc_w[d]`` inside it — the WAND block max.

    The bound survives quantization by construction: ``idf`` and ``doc_w``
    are rounded to float32 FIRST, and the per-page max is taken over the
    already-rounded products, so it is a true upper bound of the float32
    scores the engines produce (§9.2's safety argument adds a slack factor
    for the float32 accumulation error, not for these tables).

    Registered pytree like :class:`FlatIndex`: the tables are leaves
    (numpy on host; engines move what they need to device), the scalar
    configuration is static aux data.
    """

    # global tables
    idf: np.ndarray         # (L,) f32 per-term idf
    doc_w: np.ndarray       # (U,) f32 per-doc BM25 weight (0: in no list)
    list_max: np.ndarray    # (L,) f32 max single-term contribution per list

    # block-max page directory: one entry per (list, stream page)
    page_off: np.ndarray    # (L+1,) entry span of each list
    pg_list: np.ndarray     # (E,) owning list id
    pg_page: np.ndarray     # (E,) global stream page id
    pg_sym_lo: np.ndarray   # (E,) absolute symbol range within the page
    pg_sym_hi: np.ndarray   # (E,)
    pg_base: np.ndarray     # (E,) absolute value before the first element
    pg_last: np.ndarray     # (E,) last element — [base, last] is the doc-id
                            #      range the Block-Max rest aligns on
    pg_head: np.ndarray     # (E,) 1 iff the entry emits the list head
    pg_elem_lo: np.ndarray  # (E,) first decoded-element index (host slicing)
    pg_count: np.ndarray    # (E,) elements the entry decodes to
    pg_ub: np.ndarray       # (E,) f32 block max of idf*doc_w in the entry
    pg_wmax: np.ndarray     # (E,) f32 block max of doc_w alone — the
                            #      second admission bound (wmax * sum idf)

    # static configuration — aux data, not leaves
    page_size: int = dataclasses.field(metadata=dict(static=True))
    max_page_elems: int = dataclasses.field(metadata=dict(static=True))
    ndocs: int = dataclasses.field(metadata=dict(static=True))
    k1: float = dataclasses.field(metadata=dict(static=True))
    b: float = dataclasses.field(metadata=dict(static=True))
    avgdl: float = dataclasses.field(metadata=dict(static=True))


def build_score_index(res: RePairResult, page_size: int | None = None,
                      k1: float = BM25_K1, b: float = BM25_B) -> ScoreIndex:
    """Precompute the scoring tier for one compressed index (host numpy,
    once per index build — the ranked-retrieval analogue of the
    (b)-sampling pass).

    ``page_size`` must match the layout of the engine that will decode the
    page entries (``None`` = ``DEFAULT_PAGE``); document length here is
    the number of lists containing the document (binary postings)."""
    P = DEFAULT_PAGE if page_size is None else \
        max(128, -(-int(page_size) // 128) * 128)
    g = res.grammar
    nt = g.num_terminals
    L = res.num_lists
    starts = np.asarray(res.starts, np.int64)
    N = int(starts[-1])
    num_pages = max(1, -(-N // P))

    decoded = [res.decode_list(i) for i in range(L)]
    dl = np.zeros(max(1, int(res.universe)), np.int64)
    for d in decoded:
        dl[d] += 1
    ndocs = int((dl > 0).sum())
    avgdl = float(dl.sum() / max(ndocs, 1))
    idf = bm25_idf(np.asarray(res.orig_lengths, np.int64), ndocs)
    doc_w = bm25_doc_weights(dl, avgdl, k1, b)

    # expansion length of every stream symbol (gaps it decodes to)
    seq = np.asarray(res.seq, np.int64)
    sym_lens = np.ones(N, np.int64)
    if g.num_rules:
        is_rule = seq >= nt
        sym_lens[is_rule] = np.asarray(g.lengths,
                                       np.int64)[seq[is_rule] - nt]

    page_off = np.zeros(L + 1, np.int64)
    cols: dict[str, list] = {k: [] for k in
                             ("list", "page", "sym_lo", "sym_hi", "base",
                              "last", "head", "elem_lo", "count", "ub",
                              "wmax")}
    list_max = np.zeros(L, np.float32)
    for i in range(L):
        docs = decoded[i]
        n = docs.size
        if n == 0:
            page_off[i + 1] = len(cols["page"])
            continue
        lo, hi = int(starts[i]), int(starts[i + 1])
        # gaps decoded before each span-symbol boundary (element j of the
        # list is the head for j == 0, else the (j-1)-th gap)
        gcb = np.concatenate([[0], np.cumsum(sym_lens[lo:hi])])
        contrib = (np.float32(idf[i]) * doc_w[docs]).astype(np.float32)
        list_max[i] = contrib.max()
        p0 = min(lo // P, num_pages - 1)
        p1 = (hi - 1) // P if hi > lo else p0
        for p in range(p0, p1 + 1):
            slo, shi = max(lo, p * P), min(hi, (p + 1) * P)
            head = 1 if p == p0 else 0
            glo = int(gcb[slo - lo]) if shi > slo else 0
            ghi = int(gcb[shi - lo]) if shi > slo else 0
            elem_lo = 0 if head else 1 + glo
            count = head + (ghi - glo)
            cols["list"].append(i)
            cols["page"].append(p)
            cols["sym_lo"].append(slo)
            cols["sym_hi"].append(shi)
            cols["base"].append(int(docs[0]) if head else int(docs[glo]))
            cols["last"].append(int(docs[elem_lo + count - 1]))
            cols["head"].append(head)
            cols["elem_lo"].append(elem_lo)
            cols["count"].append(count)
            cols["ub"].append(contrib[elem_lo:elem_lo + count].max())
            cols["wmax"].append(doc_w[docs[elem_lo:elem_lo + count]].max())
        page_off[i + 1] = len(cols["page"])

    counts = np.asarray(cols["count"], np.int64)
    return ScoreIndex(
        idf=idf, doc_w=doc_w, list_max=list_max,
        page_off=page_off.astype(np.int32),
        pg_list=np.asarray(cols["list"], np.int32),
        pg_page=np.asarray(cols["page"], np.int32),
        pg_sym_lo=np.asarray(cols["sym_lo"], np.int32),
        pg_sym_hi=np.asarray(cols["sym_hi"], np.int32),
        pg_base=np.asarray(cols["base"], np.int32),
        pg_last=np.asarray(cols["last"], np.int32),
        pg_head=np.asarray(cols["head"], np.int32),
        pg_elem_lo=np.asarray(cols["elem_lo"], np.int32),
        pg_count=counts.astype(np.int32),
        pg_ub=np.asarray(cols["ub"], np.float32),
        pg_wmax=np.asarray(cols["wmax"], np.float32),
        page_size=P,
        max_page_elems=int(counts.max(initial=1)),
        ndocs=ndocs, k1=float(k1), b=float(b), avgdl=avgdl,
    )
