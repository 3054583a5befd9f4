"""Pure-jnp implementation of the engine operations (DESIGN.md §2.4).

This module absorbs the old ``core/batched.py`` closure factories into
module-level jitted functions that take the :class:`FlatIndex` **as a traced
pytree argument**: the static bounds (``max_scan``, ``max_depth``,
``num_terminals``) travel as aux data, the arrays as tracers, so one jit
cache entry serves every index whose bounds agree — rebuilding the index
does not retrace.

All functions are fixed-trip-count (no data-dependent shapes); this is the
reference implementation the fused Pallas kernel is checked against
bit-exactly.

Semantics mirror ``core/intersect.py::LookupList.next_geq``:
  * bucket lookup gives a start state (symbol offset j, absolute value s),
  * phrase-sum skipping advances while s + sum < x,
  * a fixed-depth descent resolves the answer inside the phrase.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..core.jax_index import FlatIndex, PagedIndex, INT_INF


def _next_geq_one(fi: FlatIndex, list_id: jax.Array, x: jax.Array) -> jax.Array:
    """Smallest element >= x in list ``list_id``; INT_INF if none."""
    T = fi.num_terminals

    start = fi.starts[list_id]
    end = fi.starts[list_id + 1]
    first = fi.firsts[list_id]
    last = fi.lasts[list_id]

    # bucket lookup — direct addressing, the [ST07] "lookup" strategy
    b = jax.lax.shift_right_logical(x, fi.kbits[list_id])
    boff = fi.bucket_offsets[list_id]
    bnum = fi.bucket_offsets[list_id + 1] - boff
    b = jnp.minimum(b, bnum - 1)
    j = fi.bck_c_pos[boff + b]
    s = fi.bck_abs[boff + b]
    # if x <= first, the head answers
    j = jnp.where(x <= first, 0, j)
    s = jnp.where(x <= first, first, s)

    # phrase-sum skipping: fixed trip count, masked updates
    def scan_body(_, js):
        j, s = js
        in_range = start + j < end
        sym = jnp.where(in_range,
                        fi.c[jnp.minimum(start + j, fi.c.shape[0] - 1)], 0)
        ps = jnp.where(in_range, fi.sym_sum[sym], 0)
        take = in_range & (s + ps < x)
        return (j + jnp.where(take, 1, 0), s + jnp.where(take, ps, 0))

    j, s = jax.lax.fori_loop(0, fi.max_scan, scan_body, (j, s))

    # if s >= x the previous element already answers (possible when the
    # bucket anchor lands exactly on an element >= x)
    done_early = s >= x
    past_end = start + j >= end

    # descent: choose left while s+sum(left) >= x else consume left
    sym0 = fi.c[jnp.minimum(start + j, fi.c.shape[0] - 1)]

    def descend_body(_, state):
        sym, s = state
        is_rule = sym >= T
        l = jnp.where(is_rule, fi.sym_left[sym], sym)
        r = jnp.where(is_rule, fi.sym_right[sym], sym)
        ls = fi.sym_sum[l]
        go_left = s + ls >= x
        new_sym = jnp.where(go_left, l, r)
        new_s = jnp.where(go_left, s, s + ls)
        return (jnp.where(is_rule, new_sym, sym),
                jnp.where(is_rule, new_s, s))

    sym_f, s_f = jax.lax.fori_loop(0, fi.max_depth, descend_body, (sym0, s))
    answer = s_f + fi.sym_sum[sym_f]  # terminal closes the element

    out = jnp.where(done_early, s, answer)
    out = jnp.where(past_end & ~done_early, INT_INF, out)
    out = jnp.where(x > last, INT_INF, out)
    return out.astype(jnp.int32)


@jax.jit
def next_geq_batch(fi: FlatIndex, list_ids: jax.Array,
                   xs: jax.Array) -> jax.Array:
    """(Q,) list ids × (Q,) probes -> (Q,) smallest element >= x (INT_INF)."""
    return jax.vmap(partial(_next_geq_one, fi))(list_ids, xs)


def _next_geq_one_paged(pi: PagedIndex, list_id: jax.Array,
                        x: jax.Array) -> jax.Array:
    """Paged-addressing mirror of :func:`_next_geq_one` (DESIGN.md §2.5):
    the bucket tables hand out (page, offset) anchors and every stream read
    goes through ``c_*_pg[pos // PAGE, pos % PAGE]``.  Same arithmetic on
    the same values as the flat program, so the two agree bit-exactly —
    this is the reference the grid-blocked Pallas kernel is checked
    against."""
    fl = pi.flat
    T = fl.num_terminals
    PAGE = pi.page_size
    npg = pi.c_syms_pg.shape[0]

    start = fl.starts[list_id]
    end = fl.starts[list_id + 1]
    first = fl.firsts[list_id]
    last = fl.lasts[list_id]

    # bucket lookup in (page, offset) form
    b = jax.lax.shift_right_logical(x, fl.kbits[list_id])
    boff = fl.bucket_offsets[list_id]
    bnum = fl.bucket_offsets[list_id + 1] - boff
    b = jnp.minimum(b, bnum - 1)
    pos = pi.bck_page[boff + b] * PAGE + pi.bck_off[boff + b]
    s = fl.bck_abs[boff + b]
    pos = jnp.where(x <= first, start, pos)
    s = jnp.where(x <= first, first, s)

    def page_read(table, p):
        return table[jnp.minimum(p // PAGE, npg - 1), p % PAGE]

    # phrase-sum skipping over paged reads
    def scan_body(_, ps_state):
        pos, s = ps_state
        in_range = pos < end
        ps = jnp.where(in_range, page_read(pi.c_sums_pg, pos), 0)
        take = in_range & (s + ps < x)
        return (pos + jnp.where(take, 1, 0), s + jnp.where(take, ps, 0))

    pos, s = jax.lax.fori_loop(0, fl.max_scan, scan_body, (pos, s))
    done_early = s >= x
    past_end = pos >= end

    # fixed-depth descent inside the halting phrase
    sym0 = page_read(pi.c_syms_pg, jnp.minimum(pos, npg * PAGE - 1))

    def descend_body(_, state):
        sym, s = state
        is_rule = sym >= T
        l = jnp.where(is_rule, fl.sym_left[sym], sym)
        r = jnp.where(is_rule, fl.sym_right[sym], sym)
        ls = fl.sym_sum[l]
        go_left = s + ls >= x
        new_sym = jnp.where(go_left, l, r)
        new_s = jnp.where(go_left, s, s + ls)
        return (jnp.where(is_rule, new_sym, sym),
                jnp.where(is_rule, new_s, s))

    sym_f, s_f = jax.lax.fori_loop(0, fl.max_depth, descend_body, (sym0, s))
    answer = s_f + fl.sym_sum[sym_f]

    out = jnp.where(done_early, s, answer)
    out = jnp.where(past_end & ~done_early, INT_INF, out)
    out = jnp.where(x > last, INT_INF, out)
    return out.astype(jnp.int32)


@jax.jit
def next_geq_batch_paged(pi: PagedIndex, list_ids: jax.Array,
                         xs: jax.Array) -> jax.Array:
    """Paged twin of :func:`next_geq_batch` — bit-exact vs the flat path."""
    return jax.vmap(partial(_next_geq_one_paged, pi))(list_ids, xs)


@jax.jit
def member_batch_paged(pi: PagedIndex, list_ids: jax.Array,
                       xs: jax.Array) -> jax.Array:
    return next_geq_batch_paged(pi, list_ids, xs) == xs


@jax.jit
def probe_batch_paged(pi: PagedIndex, long_ids: jax.Array,
                      xs: jax.Array) -> jax.Array:
    """Row-wise paged next_geq: (B,) ids × (B, M) probes -> (B, M)."""

    def one(lid, row):
        return jax.vmap(lambda x: _next_geq_one_paged(pi, lid, x))(row)

    return jax.vmap(one)(long_ids, xs)


# -- out-of-core mirrors (DESIGN.md §11.2) -----------------------------------
#
# Each program below is the resident-pool twin of a fully-resident program
# above: identical arithmetic on identical values, with every stream read
# routed ``global page -> slot_tab -> pool row``.  Pages absent from the
# pool map to slot -1, clamped to row 0 — such a read can only happen on a
# lane that is already settled (or at a masked position), where the final
# selects discard the value, so the differential gates hold bit-exactly
# with ANY pool contents outside the faulted working set.

def _pool_read(pool: jax.Array, slot_tab: jax.Array, PAGE: int,
               p: jax.Array) -> jax.Array:
    """Read absolute stream position ``p`` through the resident slot
    table.  ``pool`` is ``(slots, 1, PAGE)``, the kernels' page layout;
    ``slot_tab`` (num_pages,) global page -> pool row (-1 absent,
    clamped to 0: reachable only at masked positions)."""
    npg = slot_tab.shape[0]
    slot = slot_tab[jnp.minimum(p // PAGE, npg - 1)]
    return pool[jnp.maximum(slot, 0), 0, p % PAGE]


def _next_geq_one_resident(pi: PagedIndex, pool_syms: jax.Array,
                           pool_sums: jax.Array, slot_tab: jax.Array,
                           list_id: jax.Array, x: jax.Array) -> jax.Array:
    """Resident-pool mirror of :func:`_next_geq_one_paged`."""
    fl = pi.flat
    T = fl.num_terminals
    PAGE = pi.page_size
    npg = slot_tab.shape[0]

    start = fl.starts[list_id]
    end = fl.starts[list_id + 1]
    first = fl.firsts[list_id]
    last = fl.lasts[list_id]

    b = jax.lax.shift_right_logical(x, fl.kbits[list_id])
    boff = fl.bucket_offsets[list_id]
    bnum = fl.bucket_offsets[list_id + 1] - boff
    b = jnp.minimum(b, bnum - 1)
    pos = pi.bck_page[boff + b] * PAGE + pi.bck_off[boff + b]
    s = fl.bck_abs[boff + b]
    pos = jnp.where(x <= first, start, pos)
    s = jnp.where(x <= first, first, s)

    def scan_body(_, ps_state):
        pos, s = ps_state
        in_range = pos < end
        ps = jnp.where(in_range,
                       _pool_read(pool_sums, slot_tab, PAGE, pos), 0)
        take = in_range & (s + ps < x)
        return (pos + jnp.where(take, 1, 0), s + jnp.where(take, ps, 0))

    pos, s = jax.lax.fori_loop(0, fl.max_scan, scan_body, (pos, s))
    done_early = s >= x
    past_end = pos >= end

    sym0 = _pool_read(pool_syms, slot_tab, PAGE,
                      jnp.minimum(pos, npg * PAGE - 1))

    def descend_body(_, state):
        sym, s = state
        is_rule = sym >= T
        l = jnp.where(is_rule, fl.sym_left[sym], sym)
        r = jnp.where(is_rule, fl.sym_right[sym], sym)
        ls = fl.sym_sum[l]
        go_left = s + ls >= x
        new_sym = jnp.where(go_left, l, r)
        new_s = jnp.where(go_left, s, s + ls)
        return (jnp.where(is_rule, new_sym, sym),
                jnp.where(is_rule, new_s, s))

    sym_f, s_f = jax.lax.fori_loop(0, fl.max_depth, descend_body, (sym0, s))
    answer = s_f + fl.sym_sum[sym_f]

    out = jnp.where(done_early, s, answer)
    out = jnp.where(past_end & ~done_early, INT_INF, out)
    out = jnp.where(x > last, INT_INF, out)
    return out.astype(jnp.int32)


@jax.jit
def next_geq_batch_resident(pi: PagedIndex, pool_syms: jax.Array,
                            pool_sums: jax.Array, slot_tab: jax.Array,
                            list_ids: jax.Array, xs: jax.Array) -> jax.Array:
    """Out-of-core twin of :func:`next_geq_batch_paged` — bit-exact
    provided the probes' working set is resident (the engine faults it in
    before launching)."""
    return jax.vmap(partial(_next_geq_one_resident, pi, pool_syms,
                            pool_sums, slot_tab))(list_ids, xs)


@partial(jax.jit, static_argnames=("max_len",))
def expand_batch_resident(pi: PagedIndex, pool_syms: jax.Array,
                          pool_sums: jax.Array, slot_tab: jax.Array,
                          list_ids: jax.Array, max_len: int) -> jax.Array:
    """Out-of-core twin of :func:`expand_batch`: same positional descent,
    stream symbols read through the pool, phrase sums read from the
    pre-gathered sums pages (``sym_sum[c]`` by construction)."""
    fl = pi.flat
    T = fl.num_terminals
    PAGE = pi.page_size
    npg = slot_tab.shape[0]

    def one(list_id):
        start = fl.starts[list_id]
        end = fl.starts[list_id + 1]
        first = fl.firsts[list_id]
        length = fl.lengths[list_id]

        win = max_len
        idx = start + jnp.arange(win, dtype=jnp.int32)
        valid = idx < end
        safe = jnp.minimum(idx, npg * PAGE - 1)
        syms = jnp.where(valid, _pool_read(pool_syms, slot_tab, PAGE, safe),
                         0)
        lens = jnp.where(valid, fl.sym_len[syms], 0)
        sums = jnp.where(valid, _pool_read(pool_sums, slot_tab, PAGE, safe),
                         0)
        cum_len = jnp.cumsum(lens)
        cum_sum = jnp.cumsum(sums) + first

        t = jnp.arange(1, max_len + 1, dtype=jnp.int32)
        k = jnp.searchsorted(cum_len, t, side="left").astype(jnp.int32)
        k = jnp.minimum(k, win - 1)
        base_s = jnp.where(k > 0, cum_sum[jnp.maximum(k - 1, 0)], first)
        base_t = jnp.where(k > 0, cum_len[jnp.maximum(k - 1, 0)], 0)
        sym0 = syms[k]
        want = t - base_t

        def body(_, state):
            sym, s, w = state
            is_rule = sym >= T
            l = jnp.where(is_rule, fl.sym_left[sym], sym)
            r = jnp.where(is_rule, fl.sym_right[sym], sym)
            ll = fl.sym_len[l]
            go_left = w <= ll
            nsym = jnp.where(go_left, l, r)
            ns = jnp.where(go_left, s, s + fl.sym_sum[l])
            nw = jnp.where(go_left, w, w - ll)
            return (jnp.where(is_rule, nsym, sym),
                    jnp.where(is_rule, ns, s),
                    jnp.where(is_rule, nw, w))

        symf, sf, _ = jax.lax.fori_loop(
            0, fl.max_depth, body, (sym0, base_s, want))
        vals = sf + fl.sym_sum[symf]
        out = jnp.concatenate([first[None], vals[: max_len - 1]])
        pos = jnp.arange(max_len, dtype=jnp.int32)
        return jnp.where(pos < length, out, INT_INF).astype(jnp.int32)

    return jax.vmap(one)(list_ids)


@partial(jax.jit, static_argnames=("win", "max_elems"))
def decode_pages_resident(pi: PagedIndex, pool_syms: jax.Array,
                          pool_sums: jax.Array, slot_tab: jax.Array,
                          sym_lo: jax.Array, sym_hi: jax.Array,
                          base: jax.Array, head: jax.Array, *, win: int,
                          max_elems: int) -> jax.Array:
    """Out-of-core twin of :func:`decode_pages_batch` (block-max page-entry
    decode for the ranked tier) over the resident pool."""
    fl = pi.flat
    T = fl.num_terminals
    PAGE = pi.page_size
    npg = slot_tab.shape[0]

    def one(lo, hi, base, head):
        idx = lo + jnp.arange(win, dtype=jnp.int32)
        valid = idx < hi
        safe = jnp.minimum(idx, npg * PAGE - 1)
        syms = jnp.where(valid, _pool_read(pool_syms, slot_tab, PAGE, safe),
                         0)
        lens = jnp.where(valid, fl.sym_len[syms], 0)
        sums = jnp.where(valid, _pool_read(pool_sums, slot_tab, PAGE, safe),
                         0)
        cum_len = jnp.cumsum(lens)
        cum_sum = jnp.cumsum(sums) + base
        total = head + cum_len[win - 1]

        j = jnp.arange(max_elems, dtype=jnp.int32)
        want = j - head + 1
        w = jnp.maximum(want, 1)
        k = jnp.searchsorted(cum_len, w, side="left").astype(jnp.int32)
        k = jnp.minimum(k, win - 1)
        base_s = jnp.where(k > 0, cum_sum[jnp.maximum(k - 1, 0)], base)
        base_t = jnp.where(k > 0, cum_len[jnp.maximum(k - 1, 0)], 0)
        sym0 = syms[k]

        def body(_, state):
            sym, s, wrem = state
            is_rule = sym >= T
            l = jnp.where(is_rule, fl.sym_left[sym], sym)
            r = jnp.where(is_rule, fl.sym_right[sym], sym)
            ll = fl.sym_len[l]
            go_left = wrem <= ll
            nsym = jnp.where(go_left, l, r)
            ns = jnp.where(go_left, s, s + fl.sym_sum[l])
            nw = jnp.where(go_left, wrem, wrem - ll)
            return (jnp.where(is_rule, nsym, sym),
                    jnp.where(is_rule, ns, s),
                    jnp.where(is_rule, nw, wrem))

        symf, sf, _ = jax.lax.fori_loop(
            0, fl.max_depth, body, (sym0, base_s, w - base_t))
        vals = sf + fl.sym_sum[symf]
        out = jnp.where(want < 1, base, vals)
        return jnp.where(j < total, out, INT_INF).astype(jnp.int32)

    return jax.vmap(one)(sym_lo, sym_hi, base, head)


def build_bys_table(fi: FlatIndex) -> jnp.ndarray:
    """Phrase-sum prefix table for the batched binary-search path:
    ``incl[pos]`` = absolute value of the LAST element expanded by the
    stream symbol at ``pos`` (strictly increasing within each list span,
    because gaps are positive).  One (N,) int32 array aligned with
    ``fi.c``, built once per index on host — the auxiliary [BY04]
    structure, deliberately OUTSIDE FlatIndex so the pytree/sharding
    layout is untouched."""
    import numpy as np
    c = np.asarray(fi.c)
    starts = np.asarray(fi.starts, np.int64)
    firsts = np.asarray(fi.firsts, np.int64)
    cs = np.cumsum(np.asarray(fi.sym_sum, np.int64)[c])
    span_lens = np.diff(starts)
    # per-position offset so each span's cumsum restarts at its first value
    before = np.where(starts[:-1] > 0, cs[np.maximum(starts[:-1] - 1, 0)], 0)
    offset = np.repeat(firsts - before, span_lens)
    return jnp.asarray((cs + offset).astype(np.int32))


def _next_geq_bys_one(fi: FlatIndex, incl: jax.Array, list_id: jax.Array,
                      x: jax.Array) -> jax.Array:
    """Binary-search twin of :func:`_next_geq_one` ([BY04] / the "bys"
    planner algorithm): lower-bound the span's phrase-sum prefix table
    (32 fixed bisection steps — the span fits int32), then one fixed-depth
    descent inside the halting phrase.  Searches the COMPRESSED domain:
    log2(span symbols), not log2(elements)."""
    T = fi.num_terminals
    start = fi.starts[list_id]
    end = fi.starts[list_id + 1]
    first = fi.firsts[list_id]
    last = fi.lasts[list_id]
    N = incl.shape[0]

    def bisect(_, lh):
        lo, hi = lh
        done = lo >= hi
        mid = (lo + hi) // 2
        ge = incl[jnp.minimum(mid, N - 1)] >= x
        nlo = jnp.where(ge, lo, mid + 1)
        nhi = jnp.where(ge, mid, hi)
        return (jnp.where(done, lo, nlo), jnp.where(done, hi, nhi))

    pos, _ = jax.lax.fori_loop(0, 32, bisect, (start, end))
    s = jnp.where(pos == start, first,
                  incl[jnp.minimum(jnp.maximum(pos - 1, 0), N - 1)])
    sym0 = fi.c[jnp.minimum(pos, fi.c.shape[0] - 1)]

    def descend_body(_, state):
        sym, s = state
        is_rule = sym >= T
        l = jnp.where(is_rule, fi.sym_left[sym], sym)
        r = jnp.where(is_rule, fi.sym_right[sym], sym)
        ls = fi.sym_sum[l]
        go_left = s + ls >= x
        new_sym = jnp.where(go_left, l, r)
        new_s = jnp.where(go_left, s, s + ls)
        return (jnp.where(is_rule, new_sym, sym),
                jnp.where(is_rule, new_s, s))

    sym_f, s_f = jax.lax.fori_loop(0, fi.max_depth, descend_body, (sym0, s))
    answer = s_f + fi.sym_sum[sym_f]

    out = jnp.where(pos >= end, INT_INF, answer)
    out = jnp.where(x <= first, first, out)   # the head answers (even when
    out = jnp.where(x > last, INT_INF, out)   # the span is empty)
    return out.astype(jnp.int32)


@jax.jit
def next_geq_bys_batch(fi: FlatIndex, incl: jax.Array, list_ids: jax.Array,
                       xs: jax.Array) -> jax.Array:
    """Batched binary-search next_geq — same contract as
    :func:`next_geq_batch`, different algorithm (the planner's "bys")."""
    return jax.vmap(partial(_next_geq_bys_one, fi, incl))(list_ids, xs)


@jax.jit
def member_batch(fi: FlatIndex, list_ids: jax.Array,
                 xs: jax.Array) -> jax.Array:
    return next_geq_batch(fi, list_ids, xs) == xs


@jax.jit
def probe_batch(fi: FlatIndex, long_ids: jax.Array,
                xs: jax.Array) -> jax.Array:
    """Row-wise next_geq: (B,) list ids × (B, M) probes -> (B, M) values."""

    def one(lid, row):
        return jax.vmap(lambda x: _next_geq_one(fi, lid, x))(row)

    return jax.vmap(one)(long_ids, xs)


@partial(jax.jit, static_argnames=("max_len",))
def expand_batch(fi: FlatIndex, list_ids: jax.Array, max_len: int) -> jax.Array:
    """Batched full-list expansion: decode list -> (max_len,) absolute ids
    padded with INT_INF.  Pointer-free positional descent: output slot t
    finds the t-th element by walking the grammar with per-node length
    counters (sym_len) — O(max_depth) per element, fully parallel."""
    T = fi.num_terminals

    def one(list_id):
        start = fi.starts[list_id]
        end = fi.starts[list_id + 1]
        first = fi.firsts[list_id]
        length = fi.lengths[list_id]

        # per-symbol expanded lengths and their prefix sums over a fixed
        # window of the span (padded with zeros)
        win = max_len  # symbols <= elements
        idx = start + jnp.arange(win, dtype=jnp.int32)
        valid = idx < end
        syms = jnp.where(valid, fi.c[jnp.minimum(idx, fi.c.shape[0] - 1)], 0)
        lens = jnp.where(valid, fi.sym_len[syms], 0)
        sums = jnp.where(valid, fi.sym_sum[syms], 0)
        cum_len = jnp.cumsum(lens)           # elements after symbol i
        cum_sum = jnp.cumsum(sums) + first   # abs value after symbol i

        # element t (1-based among gap-elements) lives in the symbol whose
        # cum_len first reaches t
        t = jnp.arange(1, max_len + 1, dtype=jnp.int32)
        k = jnp.searchsorted(cum_len, t, side="left").astype(jnp.int32)
        k = jnp.minimum(k, win - 1)
        base_s = jnp.where(k > 0, cum_sum[jnp.maximum(k - 1, 0)], first)
        base_t = jnp.where(k > 0, cum_len[jnp.maximum(k - 1, 0)], 0)
        sym0 = syms[k]
        # positional descent: want the (t - base_t)-th element of sym0
        want = t - base_t  # 1-based within the phrase

        def body(_, state):
            sym, s, w = state
            is_rule = sym >= T
            l = jnp.where(is_rule, fi.sym_left[sym], sym)
            r = jnp.where(is_rule, fi.sym_right[sym], sym)
            ll = fi.sym_len[l]
            go_left = w <= ll
            nsym = jnp.where(go_left, l, r)
            ns = jnp.where(go_left, s, s + fi.sym_sum[l])
            nw = jnp.where(go_left, w, w - ll)
            return (jnp.where(is_rule, nsym, sym),
                    jnp.where(is_rule, ns, s),
                    jnp.where(is_rule, nw, w))

        symf, sf, _ = jax.lax.fori_loop(
            0, fi.max_depth, body, (sym0, base_s, want))
        vals = sf + fi.sym_sum[symf]
        # element 0 is the head; shift: output[0]=first, output[i]=vals[i-1]
        out = jnp.concatenate([first[None], vals[: max_len - 1]])
        pos = jnp.arange(max_len, dtype=jnp.int32)
        return jnp.where(pos < length, out, INT_INF).astype(jnp.int32)

    return jax.vmap(one)(list_ids)


@partial(jax.jit, static_argnames=("win", "max_elems"))
def decode_pages_batch(fi: FlatIndex, sym_lo: jax.Array, sym_hi: jax.Array,
                       base: jax.Array, head: jax.Array, *, win: int,
                       max_elems: int) -> jax.Array:
    """Batched block-max page-entry decode (DESIGN.md §9): each lane
    expands ONE entry of the score directory — the stream symbols
    ``[sym_lo, sym_hi)`` of a single page — to its absolute doc ids,
    starting from the entry's precomputed running ``base`` value.  The
    same pointer-free positional descent as :func:`expand_batch`, but
    windowed to one page (``win`` = page size ≥ span symbols) instead of
    a whole list, so work per lane is O(page), not O(list).

    ``head`` = 1 emits the list head (``base`` itself) in slot 0 before
    the gap elements.  Output (Q, max_elems) int32, INT_INF padded."""
    T = fi.num_terminals

    def one(lo, hi, base, head):
        idx = lo + jnp.arange(win, dtype=jnp.int32)
        valid = idx < hi
        syms = jnp.where(valid, fi.c[jnp.minimum(idx, fi.c.shape[0] - 1)], 0)
        lens = jnp.where(valid, fi.sym_len[syms], 0)
        sums = jnp.where(valid, fi.sym_sum[syms], 0)
        cum_len = jnp.cumsum(lens)           # gap elements after symbol i
        cum_sum = jnp.cumsum(sums) + base    # abs value after symbol i
        total = head + cum_len[win - 1]

        j = jnp.arange(max_elems, dtype=jnp.int32)
        want = j - head + 1   # 1-based gap-element index; < 1 -> emit base
        w = jnp.maximum(want, 1)
        k = jnp.searchsorted(cum_len, w, side="left").astype(jnp.int32)
        k = jnp.minimum(k, win - 1)
        base_s = jnp.where(k > 0, cum_sum[jnp.maximum(k - 1, 0)], base)
        base_t = jnp.where(k > 0, cum_len[jnp.maximum(k - 1, 0)], 0)
        sym0 = syms[k]

        def body(_, state):
            sym, s, wrem = state
            is_rule = sym >= T
            l = jnp.where(is_rule, fi.sym_left[sym], sym)
            r = jnp.where(is_rule, fi.sym_right[sym], sym)
            ll = fi.sym_len[l]
            go_left = wrem <= ll
            nsym = jnp.where(go_left, l, r)
            ns = jnp.where(go_left, s, s + fi.sym_sum[l])
            nw = jnp.where(go_left, wrem, wrem - ll)
            return (jnp.where(is_rule, nsym, sym),
                    jnp.where(is_rule, ns, s),
                    jnp.where(is_rule, nw, wrem))

        symf, sf, _ = jax.lax.fori_loop(
            0, fi.max_depth, body, (sym0, base_s, w - base_t))
        vals = sf + fi.sym_sum[symf]
        out = jnp.where(want < 1, base, vals)
        return jnp.where(j < total, out, INT_INF).astype(jnp.int32)

    return jax.vmap(one)(sym_lo, sym_hi, base, head)


@jax.jit
def accumulate_scores_device(idf_terms: jax.Array, doc_w_docs: jax.Array,
                             member: jax.Array) -> jax.Array:
    """Device twin of :func:`repro.core.jax_index.accumulate_scores`: the
    same SEQUENTIAL float32 idf sum (segment-style masked adds in the
    fixed ascending-term order — ``fori_loop`` keeps XLA from reassociating
    it) followed by the single doc-weight multiply, so device scores are
    bit-identical to the host reduction.  ``idf_terms`` (K,) f32 already
    gathered per query term, ``doc_w_docs`` (D,) f32 per candidate doc,
    ``member`` (K, D) bool."""
    acc0 = jnp.zeros(member.shape[1], jnp.float32)

    def body(k, acc):
        return acc + jnp.where(member[k], idf_terms[k], jnp.float32(0.0))

    acc = jax.lax.fori_loop(0, member.shape[0], body, acc0)
    return (doc_w_docs * acc).astype(jnp.float32)


def match_mask(vals: jax.Array, xs: jax.Array) -> jax.Array:
    """Keep probes that hit: INT_INF padding never matches."""
    return jnp.where((vals == xs) & (xs != INT_INF), xs, INT_INF)


@partial(jax.jit, static_argnames=("max_len",))
def pair_intersect(fi: FlatIndex, short_ids: jax.Array, long_ids: jax.Array,
                   max_len: int) -> jax.Array:
    """Batched pairwise svs: expand the short list (padded) and probe the
    long one.  Returns (B, max_len) int32 with INT_INF at non-members /
    padding — callers compact on host or count via (res != INT_INF).sum(-1)."""
    shorts = expand_batch(fi, short_ids, max_len)       # (B, M)
    vals = probe_batch(fi, long_ids, shorts)
    return match_mask(vals, shorts)
