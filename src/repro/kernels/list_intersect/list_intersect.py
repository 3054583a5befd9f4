"""Pallas TPU kernel: grid-blocked fused next_geq over paged Re-Pair lists.

The query-time operation of the paper (§3.2–3.3) over the **paged** stream
layout (DESIGN.md §2.5).  The compressed stream lives in HBM as fixed-size
pages ``(num_pages, PAGE)``; each kernel instance sees exactly ONE page of
it, so per-instance VMEM is a function of ``PAGE`` and ``max_scan`` — never
of N.  The grid is ``(num_query_tiles, K)``:

* axis 0 — tiles of TILE_Q queries, pre-sorted by anchor page (the ops
  wrapper does the page routing on the host from the per-list page
  directory + (page, offset) bucket tables);
* axis 1 — the K consecutive stream pages the tile's skip windows can
  touch, DMA'd one per step via ``PrefetchScalarGridSpec`` scalar prefetch:
  the per-tile base page ``tile_base[i]`` drives the BlockSpec index_map,
  so only pages ``[tile_base[i], tile_base[i] + K)`` ever enter VMEM.

Each query lane runs a resumable state machine carried in VMEM scratch
across the K page steps (the TPU grid iterates the trailing axis
innermost, so scratch written at step (i, k) is live at (i, k+1)):

  1. **start state** (symbol position ``pos``, absolute value ``s``) comes
     in precomputed from the (b)-sampling bucket tables — the same lookup
     the page router already performed; degenerate lanes (head hit,
     ``x > last``, empty suffix) finalize at k == 0 without touching any
     page;
  2. **phrase-sum skipping** (§3.2) advances ``pos`` while
     ``s + sum < x``, masked to the current page — a lane that runs off
     the page edge resumes on the next grid step when its page arrives;
  3. **fixed-depth grammar descent** (Theorem 1) fires on the step where
     the lane halts inside the resident page; grammar tables are broadcast
     whole (the paper's "dictionary fits in RAM", one level down) since
     they are O(S), not O(N).

Table lookups (``kernels.gather``): the resident page with one
(TILE_Q, PAGE) one-hot compare on the VPU; the list and grammar tables on
the MXU, a (TILE_Q, rows) row one-hot times the table's byte planes, the
planes of the descent's tables built once per step and ``sym_left`` /
``sym_right`` read by one dot.  The stream arrives as ``(num_pages, 1, PAGE)`` so
each page block's last two dims equal the array's (the TPU block-shape
rule); the leading page dim is squeezed away inside the kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gather import plane_gather, row_gather, table_gather, table_planes

TILE_Q = 128
INT_INF = 2**31 - 1  # plain int: jnp array constants can't be captured


def _paged_intersect_kernel(base_ref, slots_ref, lids_ref, xs_ref,
                            pos0_ref, s0_ref,
                            starts_ref, lasts_ref, sleft_ref, sright_ref,
                            ssum_ref, csyms_ref, csums_ref, out_ref,
                            pos_sc, s_sc, val_sc, done_sc, *,
                            max_scan: int, max_depth: int, T: int,
                            page: int, k_pages: int):
    i = pl.program_id(0)
    k = pl.program_id(1)
    lid = lids_ref[...]                        # (1, TILE_Q) lane rows
    x = xs_ref[...]

    @pl.when(k == 0)
    def _init():
        end = table_gather(starts_ref, lid + 1)
        pos = pos0_ref[...]
        s = s0_ref[...]
        last = table_gather(lasts_ref, lid)
        # lanes that need no page data settle immediately: the start state
        # already answers (s >= x, covers the head case), the suffix is
        # empty (pos >= end), or x exceeds the list entirely.
        done_early = s >= x
        done = done_early | (pos >= end) | (x > last)
        val = jnp.where(done_early, s, INT_INF)
        val = jnp.where(x > last, INT_INF, val)
        pos_sc[...] = pos
        s_sc[...] = s
        val_sc[...] = jnp.where(done, val, INT_INF)
        done_sc[...] = done.astype(jnp.int32)

    # a tile whose lanes have all settled has nothing left to read: its
    # remaining page steps leave every scratch row as it is
    @pl.when(jnp.min(done_sc[...]) == 0)
    def _page():
        # GLOBAL page id: offset math stays in stream coordinates;
        # slots_ref only steers which storage row the DMA reads
        cur = base_ref[i] + k
        end = table_gather(starts_ref, lid + 1)
        pos = pos_sc[...]
        s = s_sc[...]
        done = done_sc[...] != 0
        anchor = pos0_ref[...]
        csums = csums_ref[...]                 # (1, PAGE) resident page
        csyms = csyms_ref[...]

        # -- phrase-sum skipping, masked to the resident page --------------
        # total advancement is capped at max_scan from the anchor — the
        # same trip budget as the flat reference, and what bounds the page
        # router's window to (anchor + max_scan) // PAGE.
        def scan_body(_, ps_state):
            pos, s = ps_state
            off = pos - cur * page
            in_page = (off >= 0) & (off < page)
            ps = row_gather(csums, jnp.where(in_page, off, -1))
            take = (~done & in_page & (pos < end)
                    & (pos - anchor < max_scan) & (s + ps < x))
            return (pos + jnp.where(take, 1, 0),
                    s + jnp.where(take, ps, 0))

        pos, s = jax.lax.fori_loop(0, min(max_scan, page), scan_body,
                                   (pos, s))

        # a lane is settled by this page iff it halted inside it (the skip
        # window can straddle pages: a lane parked on the page edge resumes
        # next step) or ran out of list.
        off = pos - cur * page
        in_page = (off >= 0) & (off < page)
        past_end = pos >= end
        newly = ~done & (in_page | past_end)
        done_early = s >= x

        # -- fixed-depth grammar descent inside the resident page ----------
        sym0 = row_gather(csyms, jnp.where(in_page, off, -1))
        kids = table_planes(sleft_ref, sright_ref)
        sums = table_planes(ssum_ref)

        def descend_body(_, state):
            sym, s = state
            is_rule = sym >= T
            left, right = plane_gather(kids, sym)
            l = jnp.where(is_rule, left, sym)
            r = jnp.where(is_rule, right, sym)
            (ls,) = plane_gather(sums, l)
            go_left = s + ls >= x
            new_sym = jnp.where(go_left, l, r)
            new_s = jnp.where(go_left, s, s + ls)
            return (jnp.where(is_rule, new_sym, sym),
                    jnp.where(is_rule, new_s, s))

        sym_f, s_f = jax.lax.fori_loop(0, max_depth, descend_body,
                                       (sym0, s))
        answer = s_f + plane_gather(sums, sym_f)[0]

        val = jnp.where(done_early, s, answer)
        val = jnp.where(past_end & ~done_early, INT_INF, val)
        val_sc[...] = jnp.where(newly, val, val_sc[...])
        done_sc[...] = (done | newly).astype(jnp.int32)
        pos_sc[...] = pos
        s_sc[...] = s

    @pl.when(k == k_pages - 1)
    def _flush():
        out_ref[...] = val_sc[...]


def paged_intersect_pallas(tile_base: jax.Array, tile_slots: jax.Array,
                           lids: jax.Array,
                           xs: jax.Array, pos0: jax.Array, s0: jax.Array,
                           starts: jax.Array, lasts: jax.Array,
                           sleft: jax.Array, sright: jax.Array,
                           ssum: jax.Array, csyms_pg: jax.Array,
                           csums_pg: jax.Array, *, max_scan: int,
                           max_depth: int, T: int, k_pages: int,
                           interpret: bool = False) -> jax.Array:
    """Grid-blocked fused next_geq.

    ``tile_base`` (Q // TILE_Q,) int32 — first stream page each query tile
    may touch (host page routing guarantees ``tile_base[i] + k_pages`` never
    exceeds ``num_pages``); ``tile_slots`` (Q // TILE_Q, k_pages) int32 —
    the STORAGE row holding page ``tile_base[i] + k``: the identity map
    ``tile_base[i] + k`` when the stream is fully resident, or the
    admission cache's slot table when ``csyms_pg/csums_pg`` are the
    bounded resident pool (DESIGN.md §11.2 — the kernel's offset math
    stays in global stream coordinates either way, only the BlockSpec
    index_map reads the remap); ``lids/xs/pos0/s0`` (Q,) int32 queries
    sorted by anchor page with their bucket-lookup start state;
    ``csyms_pg/csums_pg`` (num_rows, 1, PAGE) paged stream or pool;
    ``starts/lasts/sleft/sright/ssum`` ``gather.pack_table`` tables.
    Returns (Q,) int32 next_geq values (INT_INF past the end), bit-exact vs
    ``engine.jnp_backend.next_geq_batch_paged``."""
    Q = lids.shape[0]
    page = csyms_pg.shape[-1]
    def list_intersect(*refs):
        # Mosaic names the kernel after this function; a ``name=`` on the
        # pallas_call would also rename the op the trace shows
        # (``_paged_call``, the jitted wrapper's name)
        _paged_intersect_kernel(*refs, max_scan=max_scan,
                                max_depth=max_depth, T=T, page=page,
                                k_pages=k_pages)
    qspec = pl.BlockSpec((1, TILE_Q), lambda i, k, b, sl: (0, i))
    tspec = lambda a: pl.BlockSpec(a.shape, lambda i, k, b, sl: (0, 0))
    pgspec = pl.BlockSpec((None, 1, page),
                          lambda i, k, b, sl: (sl[i, k], 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Q // TILE_Q, k_pages),
        in_specs=[qspec, qspec, qspec, qspec,
                  tspec(starts), tspec(lasts), tspec(sleft), tspec(sright),
                  tspec(ssum), pgspec, pgspec],
        out_specs=pl.BlockSpec((1, TILE_Q), lambda i, k, b, sl: (0, i)),
        scratch_shapes=[pltpu.VMEM((1, TILE_Q), jnp.int32)
                        for _ in range(4)],
    )
    return pl.pallas_call(
        list_intersect,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, Q), jnp.int32),
        interpret=interpret,
    )(tile_base, tile_slots, lids[None, :], xs[None, :], pos0[None, :],
      s0[None, :], starts, lasts, sleft, sright, ssum, csyms_pg,
      csums_pg)[0]
