"""Pallas TPU kernel: grid-blocked Elias-Fano next_geq low-bits search.

The EF ``next_geq`` splits into two halves (DESIGN.md §10.4), mirroring
the host/device split of ``list_intersect``:

* the HOST router (``ops.route_low_pages``) runs ``ef_probe_state_np`` —
  the three high-bits selects over the page-sample directory — exactly as
  the numpy reference does, then schedules each lane's **low-bits
  window**: with bucket ``[i0, i1)`` and miss element ``i1m``, the lane
  only ever reads the ``l``-bit fields of elements ``i0 .. max(i1-1,
  i1m)`` — at most ``max_bucket + 1`` consecutive fields, i.e. a bounded
  run of consecutive words of the packed low-bits array;
* the KERNEL finishes the search over the **paged** low-bits array.  The
  grid is ``(num_query_tiles, K)``: axis 0 tiles of TILE_Q lanes sorted
  by first low-bits page, axis 1 the K consecutive pages a tile's windows
  can touch, DMA'd one per step via ``PrefetchScalarGridSpec`` — the same
  scalar-prefetch page scheduling as ``list_intersect``.

Each lane scans its window LINEARLY (the lows inside one high bucket are
non-decreasing, so first-geq by linear scan equals the reference's
bisection result bit for bit), carrying a resumable cursor in VMEM
scratch across the K page steps.  An ``l``-bit field can straddle one
word boundary (``l <= 31``), so the element is processed at the step
where its HIGH word is resident; the low word is then either also
resident or the last word of the PREVIOUS page, held in a carry scratch
written at the end of every step.  When the field fits in one word the
second read is masked off by ``& ((1 << l) - 1)`` — any value may be
substituted, so the masked gather's out-of-range 0 is exact.

Lanes the host already answered (empty list, head hit, ``x > last``, and
``l == 0`` lists whose answer needs no low bits at all) carry
``cnt == 0`` and a precomputed ``val0``; they park at the tile's lowest
active page and flush ``val0`` untouched.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gather import row_gather

TILE_Q = 128
#: words of the packed low-bits array per grid page
EF_PAGE = 128


def _ef_kernel(base_ref, done_ref, val0_ref, i0_ref, cnt_ref, i1_ref,
               i1m_ref, hx_ref, hi1_ref, l_ref, xlo_ref, gb0_ref,
               pg_ref, out_ref, t_sc, found_sc, flow_sc, li1_sc, carry_sc,
               *, max_win: int, k_pages: int):
    i = pl.program_id(0)
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        zero = jnp.zeros((1, TILE_Q), jnp.int32)
        t_sc[...] = zero
        found_sc[...] = zero
        flow_sc[...] = zero
        li1_sc[...] = zero
        carry_sc[...] = zero

    cur0 = (base_ref[i] + k) * EF_PAGE        # global word id of page start
    pg = pg_ref[...]                          # (1, EF_PAGE) resident words
    i0 = i0_ref[...]                          # (1, TILE_Q) lane rows
    cnt = cnt_ref[...]
    i1 = i1_ref[...]
    i1m = i1m_ref[...]
    l = l_ref[...]
    xlo = xlo_ref[...]
    gb0 = gb0_ref[...]
    carry = carry_sc[...]

    def read_word(wi):
        # global word index -> value: resident page, else the previous
        # page's last word (carry), else 0 (only reached masked)
        off = wi - cur0
        in_pg = (off >= 0) & (off < EF_PAGE)
        v = row_gather(pg, jnp.where(in_pg, off, -1))
        return jnp.where(off == -1, carry, v)

    def body(_, st):
        t, found, flow, li1 = st
        e = i0 + t
        gb = gb0 + e * l
        w_lo = lax.shift_right_logical(gb, 5)
        off = gb & 31
        w_hi = lax.shift_right_logical(gb + l - 1, 5)
        resident = (w_hi >= cur0) & (w_hi < cur0 + EF_PAGE)
        doit = (t < cnt) & resident
        w0v = read_word(w_lo)
        w1v = read_word(w_lo + 1)
        lowpart = lax.shift_right_logical(w0v, off)
        hipart = jnp.where(off == 0, 0,
                           lax.shift_left(w1v, (32 - off) & 31))
        lv = (lowpart | hipart) & (lax.shift_left(jnp.int32(1), l) - 1)
        hit = doit & (e < i1) & (found == 0) & (lv >= xlo)
        flow = jnp.where(hit, lv, flow)
        found = jnp.where(hit, 1, found)
        li1 = jnp.where(doit & (e == i1m), lv, li1)
        return (t + jnp.where(doit, 1, 0), found, flow, li1)

    t, found, flow, li1 = lax.fori_loop(
        0, max_win, body,
        (t_sc[...], found_sc[...], flow_sc[...], li1_sc[...]))
    t_sc[...] = t
    found_sc[...] = found
    flow_sc[...] = flow
    li1_sc[...] = li1
    carry_sc[...] = jnp.broadcast_to(pg[:, EF_PAGE - 1:], (1, TILE_Q))

    @pl.when(k == k_pages - 1)
    def _flush():
        hfin = jnp.where(found != 0, hx_ref[...], hi1_ref[...])
        lowe = jnp.where(found != 0, flow, li1)
        val = lax.shift_left(hfin, l) | lowe
        out_ref[...] = jnp.where(done_ref[...] != 0, val0_ref[...], val)


def ef_intersect_pallas(tile_base: jax.Array, done: jax.Array,
                        val0: jax.Array, i0: jax.Array, cnt: jax.Array,
                        i1: jax.Array, i1m: jax.Array, hx: jax.Array,
                        hi1: jax.Array, l: jax.Array, xlo: jax.Array,
                        gb0: jax.Array, lo_pg: jax.Array, *,
                        max_win: int, k_pages: int,
                        interpret: bool = False) -> jax.Array:
    """Grid-blocked EF low-bits search.

    ``tile_base`` (Q // TILE_Q,) int32 — first low-bits page each tile may
    touch; the remaining query arrays are (Q,) int32 lanes sorted by first
    page with their host-computed probe state; ``lo_pg``
    (num_pages, 1, EF_PAGE) is the paged packed low-bits array.  Returns (Q,)
    int32 next_geq values, bit-exact vs ``core.ef.ef_next_geq_np``."""
    Q = done.shape[0]
    def ef_next_geq(*refs):
        # Mosaic names the kernel after this function; the op in the
        # trace keeps the jitted wrapper's name (``_ef_call``)
        _ef_kernel(*refs, max_win=max_win, k_pages=k_pages)
    qspec = pl.BlockSpec((1, TILE_Q), lambda i, k, b: (0, i))
    pgspec = pl.BlockSpec((None, 1, EF_PAGE),
                          lambda i, k, b: (b[i] + k, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(Q // TILE_Q, k_pages),
        in_specs=[qspec] * 11 + [pgspec],
        out_specs=pl.BlockSpec((1, TILE_Q), lambda i, k, b: (0, i)),
        scratch_shapes=[pltpu.VMEM((1, TILE_Q), jnp.int32)
                        for _ in range(5)],
    )
    return pl.pallas_call(
        ef_next_geq,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, Q), jnp.int32),
        interpret=interpret,
    )(tile_base, done[None, :], val0[None, :], i0[None, :], cnt[None, :],
      i1[None, :], i1m[None, :], hx[None, :], hi1[None, :], l[None, :],
      xlo[None, :], gb0[None, :], lo_pg)[0]
