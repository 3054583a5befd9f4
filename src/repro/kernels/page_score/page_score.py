"""Pallas TPU kernel: block-max page-entry decode over the paged stream.

The device half of ranked retrieval's ScoreRound (DESIGN.md §9): each
entry of the block-max directory names one (list, stream page) slice —
symbol window, running base value, head flag — and the kernel expands it
to absolute doc ids without touching any other page.  This is the
pruning payoff made physical: a skipped entry is a page that never
enters VMEM.

Grid ``(Q, b_pad // TILE_B)``:

* axis 0 — one page entry per step; the entry's stream page id and its
  metadata (symbol offset, window length, base, head flag, element count)
  ride the ``PrefetchScalarGridSpec`` scalar-prefetch operands, and the
  page id drives the BlockSpec index_map of the three paged stream tables
  (symbols, per-page prefix sums of phrase sums and of phrase lengths), so
  exactly ONE page per table is resident per instance — the same DMA
  discipline as ``list_intersect``;
* axis 1 — tiles of TILE_B output slots, so the one-hot gather matrices
  stay (TILE_B, width) like the probe kernel's, never (b_pad, width).

Per tile the kernel mirrors the jnp reference exactly: the element count /
absolute value after each symbol of the entry's window come from the
page's prefix sums rebased at the window start (Mosaic has no cumsum;
int32 wraparound cancels in the difference), a compare-count
``searchsorted`` locates each output slot's owning symbol, then the
fixed-depth positional descent with per-node length counters.  Page
gathers are one-hot masked sums on the VPU; grammar lookups run on the
MXU (``kernels.gather``, exact in int32), with each level's two pairs of
same-index lookups (``sym_left``/``sym_right``, ``sym_len``/``sym_sum``)
one dot each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gather import plane_gather, row_gather, table_planes

TILE_B = 128
INT_INF = 2**31 - 1  # plain int: jnp array constants can't be captured


def _page_decode_kernel(pages_ref, slo_ref, nsym_ref, base_ref, head_ref,
                        cnt_ref, sleft_ref, sright_ref, ssum_ref, slen_ref,
                        csyms_ref, pfsum_ref, pflen_ref, out_ref, *,
                        max_depth: int, T: int, page: int):
    q = pl.program_id(0)
    tb = pl.program_id(1)
    # tile guard: rows are padded to the directory-wide max element count,
    # but THIS entry decodes exactly cnt elements — tiles past it skip the
    # prefix sums and the whole descent and just emit padding
    out_ref[...] = jnp.full((1, TILE_B), INT_INF, jnp.int32)

    @pl.when(tb * TILE_B < cnt_ref[q])
    def _decode():
        _page_decode_tile(tb, slo_ref[q], nsym_ref[q], base_ref[q],
                          head_ref[q], sleft_ref, sright_ref, ssum_ref,
                          slen_ref, csyms_ref, pfsum_ref, pflen_ref,
                          out_ref, max_depth=max_depth, T=T, page=page)


def _page_decode_tile(tb, off0, n, base, head, sleft_ref, sright_ref,
                      ssum_ref, slen_ref, csyms_ref, pfsum_ref, pflen_ref,
                      out_ref, *, max_depth: int, T: int, page: int):
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
    last = off0 + n - 1                          # window's last symbol

    def windowed(pf):
        # inclusive cumsum over the window [off0, last], 0 before it and
        # the window total after it, from the page's prefix sums
        pf = pf[...]
        before = jnp.sum(jnp.where(pos == off0 - 1, pf, 0))
        upto = jnp.sum(jnp.where(pos == last, pf, 0)) - before
        return jnp.where(pos < off0, 0,
                         jnp.where(pos <= last, pf - before, upto)), upto

    cum_len, n_elems = windowed(pflen_ref)       # gap elements after symbol
    cum_sum, _ = windowed(pfsum_ref)
    cum_sum = cum_sum + base                     # abs value after symbol
    syms = jnp.where((pos >= off0) & (pos <= last), csyms_ref[...], 0)
    total = head + n_elems

    j = (jax.lax.broadcasted_iota(jnp.int32, (1, TILE_B), 1)
         + tb * TILE_B)                          # (1, TILE_B) output slots
    want = j - head + 1    # 1-based gap-element index; < 1 -> emit base
    w = jnp.maximum(want, 1)
    # searchsorted-left as a compare-count: first symbol whose cumulative
    # element count reaches w (positions before the window count 0)
    k = jnp.sum((cum_len < w.T).astype(jnp.int32), axis=1, keepdims=True).T
    k = jnp.minimum(k, page - 1)
    base_s = jnp.where(k > 0, row_gather(cum_sum, k - 1), base)
    base_t = jnp.where(k > 0, row_gather(cum_len, k - 1), 0)
    sym0 = row_gather(syms, k)
    kids = table_planes(sleft_ref, sright_ref)
    lens_sums = table_planes(slen_ref, ssum_ref)

    def body(_, state):
        sym, s, wrem = state
        is_rule = sym >= T
        left, right = plane_gather(kids, sym)
        l = jnp.where(is_rule, left, sym)
        r = jnp.where(is_rule, right, sym)
        ll, ls = plane_gather(lens_sums, l)
        go_left = wrem <= ll
        nsym = jnp.where(go_left, l, r)
        ns = jnp.where(go_left, s, s + ls)
        nw = jnp.where(go_left, wrem, wrem - ll)
        return (jnp.where(is_rule, nsym, sym),
                jnp.where(is_rule, ns, s),
                jnp.where(is_rule, nw, wrem))

    symf, sf, _ = jax.lax.fori_loop(0, max_depth, body,
                                    (sym0, base_s, w - base_t))
    vals = sf + plane_gather(lens_sums, symf)[1]
    out = jnp.where(want < 1, base, vals)
    out_ref[...] = jnp.where(j < total, out, INT_INF).astype(jnp.int32)


def page_decode_pallas(pages: jax.Array, slo: jax.Array, nsym: jax.Array,
                       base: jax.Array, head: jax.Array, cnt: jax.Array,
                       sleft: jax.Array,
                       sright: jax.Array, ssum: jax.Array, slen: jax.Array,
                       csyms_pg: jax.Array, pfsum_pg: jax.Array,
                       pflen_pg: jax.Array, *, max_depth: int, T: int,
                       b_pad: int, interpret: bool = False) -> jax.Array:
    """Fused page-entry decode.

    ``pages`` (Q,) int32 stream page per entry and ``slo/nsym/base/head/
    cnt`` (Q,) int32 per-entry metadata (symbol offset IN the page, window
    length, running base, head flag, element count — the tile guard), all
    scalar-prefetched; grammar tables ``gather.pack_table`` packed;
    ``csyms_pg`` (num_pages, 1, PAGE) paged symbols and ``pfsum_pg/
    pflen_pg`` the same layout holding each page's inclusive prefix sums
    of phrase sums / phrase lengths.  Returns (Q, b_pad) int32 doc ids,
    INT_INF padded — bit-exact vs
    ``engine.jnp_backend.decode_pages_batch``."""
    Q = slo.shape[0]
    page = csyms_pg.shape[-1]
    def page_score(*refs):
        # Mosaic names the kernel after this function; the op in the
        # trace keeps the jitted wrapper's name (``_call``)
        _page_decode_kernel(*refs, max_depth=max_depth, T=T, page=page)
    tspec = lambda a: pl.BlockSpec(a.shape, lambda q, tb, *_: (0, 0))
    pgspec = pl.BlockSpec((None, 1, page), lambda q, tb, b, *_: (b[q], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(Q, b_pad // TILE_B),
        in_specs=[tspec(sleft), tspec(sright), tspec(ssum), tspec(slen),
                  pgspec, pgspec, pgspec],
        out_specs=pl.BlockSpec((None, 1, TILE_B),
                               lambda q, tb, *_: (q, 0, tb)),
    )
    return pl.pallas_call(
        page_score,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((Q, 1, b_pad), jnp.int32),
        interpret=interpret,
    )(pages, slo, nsym, base, head, cnt, sleft, sright, ssum, slen,
      csyms_pg, pfsum_pg, pflen_pg).reshape(Q, b_pad)
