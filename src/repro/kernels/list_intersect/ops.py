"""Wrappers for the grid-blocked paged list_intersect kernel.

Two tiers:

* ``pad_paged_operands(pi)`` + ``next_geq_paged(...)`` — the serving path.
  Lane-padding the broadcast tables and snapshotting the host-side routing
  tables is O(index size); engines do it ONCE per index and reuse the
  operand pack for every launch.
* ``next_geq`` / ``next_geq_probe`` / ``list_intersect`` — conveniences
  that accept a FlatIndex or PagedIndex and pack on the fly; fine for
  tests and one-shot calls.

The **page router** (``route_pages``) is the host half of the paged design
(DESIGN.md §2.5): it performs the (b)-sampling bucket lookup in numpy
(bit-identical arithmetic to the device paths), derives each query's skip
window ``[anchor, anchor + max_scan]``, sorts queries by anchor page, and
emits per-tile base pages for the kernel's scalar-prefetch BlockSpec.  The
kernel then DMAs exactly the pages each tile's windows can touch — K
consecutive pages per tile, where K is the worst tile's page spread
(rounded up to a power of two so the jit cache stays small).
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ... import obs
from .. import count_launch, should_interpret
from ...core.jax_index import (FlatIndex, PagedIndex, build_paged_index,
                               INT_INF)
from ..gather import pack_table
from .list_intersect import TILE_Q, paged_intersect_pallas


def paged_rows(pg: jax.Array) -> jax.Array:
    """(num_pages, PAGE) stream table -> the kernels' (num_pages, 1, PAGE)
    int32 layout (one page per block, block dims equal to the array's)."""
    return pg.astype(jnp.int32).reshape(pg.shape[0], 1, pg.shape[-1])


def routing_snapshot(pi: PagedIndex) -> dict:
    """Numpy snapshot of the routing tables — everything the host page
    router (and the out-of-core working-set computation) needs.  These are
    the RAM-tier directories of the paper's secondary-memory split; only
    the stream itself may live behind a page store."""
    fl = pi.flat
    return dict(
        starts=np.asarray(fl.starts, np.int64),
        firsts=np.asarray(fl.firsts, np.int64),
        lasts=np.asarray(fl.lasts, np.int64),
        kbits=np.asarray(fl.kbits, np.int64),
        boffs=np.asarray(fl.bucket_offsets, np.int64),
        babs=np.asarray(fl.bck_abs, np.int64),
        banchor=(np.asarray(pi.bck_page, np.int64) * pi.page_size
                 + np.asarray(pi.bck_off, np.int64)),
        page_dir=np.asarray(pi.page_dir, np.int64),
        page=pi.page_size,
        num_pages=pi.num_pages,
        max_scan=fl.max_scan,
    )


def pad_paged_operands(pi: PagedIndex, include_stream: bool = True
                       ) -> tuple[tuple[jax.Array, ...], dict, dict]:
    """Kernel operand pack for one paged index: device tables (the list
    and grammar tables packed ``(rows, 128)`` + the paged stream in its
    ``(num_pages, 1, PAGE)`` kernel layout), static bounds, and the numpy
    routing snapshot.  Compute once per index (PallasEngine caches this at
    construction).  ``include_stream=False`` omits the two paged stream
    tables — the out-of-core path substitutes the resident pool per launch
    (DESIGN.md §11.2)."""
    fl = pi.flat
    tables = tuple(pack_table(a) for a in (
        fl.starts, fl.lasts, fl.sym_left, fl.sym_right, fl.sym_sum))
    if include_stream:
        tables += (paged_rows(pi.c_syms_pg), paged_rows(pi.c_sums_pg))
    statics = dict(max_scan=fl.max_scan, max_depth=fl.max_depth,
                   T=fl.num_terminals)
    return tables, statics, routing_snapshot(pi)


def _probe_windows(host: dict, lids: np.ndarray, xq: np.ndarray):
    """Shared host half of the bucket lookup: start state + per-lane page
    windows.  Returns ``(needs, act_lo, act_hi, end_page, pos0, s0)`` —
    ``needs`` lanes will read pages ``[act_lo, act_hi]``; settled lanes
    read nothing (bit-identical arithmetic to the device paths)."""
    page = host["page"]
    num_pages = host["num_pages"]
    max_scan = host["max_scan"]

    start = host["starts"][lids]
    end = host["starts"][lids + 1]
    first = host["firsts"][lids]
    last = host["lasts"][lids]
    boff = host["boffs"][lids]
    bnum = host["boffs"][lids + 1] - boff
    b = np.minimum(xq >> host["kbits"][lids], bnum - 1)
    idx = boff + b
    # mirror the kernel's masked gather: out-of-range index reads 0
    nb = host["banchor"].size
    ok = (idx >= 0) & (idx < nb)
    safe = np.clip(idx, 0, max(nb - 1, 0))
    pos0 = np.where(ok, host["banchor"][safe] if nb else 0, 0)
    s0 = np.where(ok, host["babs"][safe] if nb else 0, 0)
    head = xq <= first
    pos0 = np.where(head, start, pos0)
    s0 = np.where(head, first, s0)

    # A lane's window is capped both by the skip budget and by the list's
    # final page from the page directory (reads stop strictly before
    # ``end``, and ``page_dir[lid + 1]`` is ``starts[lid + 1] // page`` —
    # a list ending early in a page never drags later pages in).
    needs = (s0 < xq) & (pos0 < end) & (xq <= last)
    act_lo = np.clip(pos0 // page, 0, num_pages - 1)
    end_page = np.clip(host["page_dir"][lids + 1], 0, num_pages - 1)
    act_hi = np.minimum((pos0 + max_scan) // page, end_page)
    return needs, act_lo, act_hi, pos0, s0


def probe_working_set(host: dict, list_ids, xs) -> np.ndarray:
    """Unique stream pages the probe batch can touch — exactly the union
    of the active lanes' ``[act_lo, act_hi]`` windows the router schedules
    (settled lanes never read).  This is what the scheduler faults between
    ticks (DESIGN.md §11.3)."""
    lids = np.asarray(list_ids, np.int64)
    xq = np.asarray(xs, np.int64)
    if lids.size == 0:
        return np.zeros(0, np.int64)
    needs, lo, hi, _, _ = _probe_windows(host, lids, xq)
    if not needs.any():
        return np.zeros(0, np.int64)
    lo, hi = lo[needs], hi[needs]
    width = int((hi - lo).max()) + 1
    grid = lo[:, None] + np.arange(width, dtype=np.int64)
    return np.unique(grid[grid <= hi[:, None]])


def route_pages(host: dict, list_ids: np.ndarray, xs: np.ndarray):
    """Host half of the paged query path: bucket lookup + page scheduling.

    Returns ``(order, tile_base, k_pages, lids, xs, pos0, s0)`` where the
    query arrays are sorted by anchor page and padded to a TILE_Q multiple
    (by repeating the final query), ``tile_base[i]`` is the first page tile
    ``i`` may touch, and ``k_pages`` is the static per-tile page count.
    ``out_sorted[np.argsort(order)]`` restores request order (truncate the
    padding first)."""
    lids = np.asarray(list_ids, np.int64)
    xq = np.asarray(xs, np.int64)
    num_pages = host["num_pages"]

    # Lanes that settle at k == 0 never read a page; they park at the
    # LOWEST active anchor page so they cluster into spread-1 tiles
    # instead of widening a mixed tile's page window (parking at a fixed
    # page would reinflate k_pages toward num_pages).
    needs, act_lo, act_hi, pos0, s0 = _probe_windows(host, lids, xq)
    park = int(act_lo[needs].min()) if needs.any() else 0
    lo = np.where(needs, act_lo, park)
    hi = np.where(needs, act_hi, park)

    order = np.argsort(lo, kind="stable")
    q = order.size
    q_pad = max(TILE_Q, -(-q // TILE_Q) * TILE_Q)
    take = np.concatenate([order, np.repeat(order[-1:], q_pad - q)])

    lo_t = lo[take].reshape(-1, TILE_Q)
    hi_t = hi[take].reshape(-1, TILE_Q)
    base = lo_t.min(axis=1)
    spread = int((hi_t.max(axis=1) - base + 1).max(initial=1))
    k_pages = min(1 << (spread - 1).bit_length(), num_pages)
    base = np.minimum(base, num_pages - k_pages)

    return (order, base.astype(np.int32), k_pages,
            lids[take].astype(np.int32), xq[take].astype(np.int32),
            pos0[take].astype(np.int32), s0[take].astype(np.int32))


#: bytes of scalar-prefetched operands one launch may put in SMEM (1 MiB
#: on v5e, where a (tiles, k_pages) int32 table pads its rows to 128
#: lanes: 2,048 tiles at k_pages 64 already overflow it)
SMEM_BUDGET = 512 * 1024


def tiles_per_launch(k_pages: int) -> int:
    """Most tiles (a power of two) whose ``tile_slots`` rows fit
    ``SMEM_BUDGET``."""
    row = 4 * (-(-k_pages // 128) * 128)
    return 1 << ((SMEM_BUDGET // row).bit_length() - 1)


@partial(jax.jit, static_argnames=("max_scan", "max_depth", "T", "k_pages",
                                   "interpret"))
def _paged_call(tables: tuple[jax.Array, ...], tile_base: jax.Array,
                tile_slots: jax.Array, lids: jax.Array, xs: jax.Array,
                pos0: jax.Array, s0: jax.Array, *, max_scan: int,
                max_depth: int, T: int, k_pages: int,
                interpret: bool) -> jax.Array:
    starts, lasts, sleft, sright, ssum, csyms_pg, csums_pg = tables
    return paged_intersect_pallas(
        tile_base, tile_slots, lids, xs, pos0, s0, starts, lasts, sleft,
        sright, ssum, csyms_pg, csums_pg, max_scan=max_scan,
        max_depth=max_depth, T=T, k_pages=k_pages, interpret=interpret)


def _launch_routed(tables, host, list_ids, xs, *, max_scan, max_depth, T,
                   interpret, resident=None) -> np.ndarray:
    """Route, remap page ids to storage rows, launch, unsort.

    Fully-resident: the storage rows ARE the global page ids (identity
    ``tile_slots``).  Out-of-core: each tile's K consecutive page ids map
    through the resident slot table into the bounded pool — absent pages
    clamp to slot 0, which is provably never *selected* (a lane only
    commits values from pages inside its own routed window, and the
    working set was faulted in before the launch)."""
    q = np.asarray(list_ids).shape[0]
    if q == 0:
        return np.zeros(0, np.int32)
    with obs.span("kernel.route"):
        order, base, k_pages, lids_s, xs_s, pos0_s, s0_s = route_pages(
            host, list_ids, xs)
        tile_pages = base[:, None].astype(np.int64) + np.arange(k_pages)
        if resident is None:
            tile_slots = tile_pages.astype(np.int32)
        else:
            resident.ensure(probe_working_set(host, list_ids, xs))
            tile_slots = np.maximum(
                resident.slot_of_page[tile_pages], 0).astype(np.int32)
            tables = tables[:5] + resident.device_pool()
    # one launch per chunk of tiles, so the scalar-prefetched page table
    # fits the chip's SMEM; every chunk is enqueued before any is read
    step = tiles_per_launch(k_pages)
    outs = []
    with obs.span("kernel.launch"):
        for t in range(0, base.shape[0], step):
            lanes = slice(t * TILE_Q, (t + step) * TILE_Q)
            count_launch("list_intersect", interpret, mxu=True)
            outs.append(_paged_call(
                tables, jnp.asarray(base[t:t + step]),
                jnp.asarray(tile_slots[t:t + step]),
                jnp.asarray(lids_s[lanes]), jnp.asarray(xs_s[lanes]),
                jnp.asarray(pos0_s[lanes]), jnp.asarray(s0_s[lanes]),
                max_scan=max_scan, max_depth=max_depth, T=T,
                k_pages=k_pages, interpret=interpret))
    with obs.span("device.wait"):
        out = np.concatenate([np.asarray(o) for o in outs])
    unsort = np.empty(q, np.int64)
    unsort[order] = np.arange(q)
    return out[:q][unsort]


def next_geq_paged(tables: tuple[jax.Array, ...], host: dict,
                   list_ids: np.ndarray, xs: np.ndarray, *, max_scan: int,
                   max_depth: int, T: int, interpret: bool) -> np.ndarray:
    """Fused paged next_geq over a cached operand pack: (Q,) ids × (Q,)
    probes -> (Q,) int32 values, INT_INF where no element >= x exists.
    Routes pages on the host, launches the grid-blocked kernel, restores
    request order.  numpy in, numpy out: the router already lives on the
    host and the unsort forces a device sync anyway, so returning numpy
    avoids a pointless bounce back to device at the engine boundary."""
    return _launch_routed(tables, host, list_ids, xs, max_scan=max_scan,
                          max_depth=max_depth, T=T, interpret=interpret)


def next_geq_resident(tables: tuple[jax.Array, ...], host: dict, resident,
                      list_ids: np.ndarray, xs: np.ndarray, *,
                      max_scan: int, max_depth: int, T: int,
                      interpret: bool) -> np.ndarray:
    """Out-of-core variant of :func:`next_geq_paged`: ``tables`` is the
    5-entry fixed pack (``include_stream=False``); the paged stream comes
    from ``resident``'s pool with scalar-prefetch indices remapped through
    its slot table (DESIGN.md §11.2)."""
    return _launch_routed(tables, host, list_ids, xs, max_scan=max_scan,
                          max_depth=max_depth, T=T, interpret=interpret,
                          resident=resident)


def _as_paged(index: FlatIndex | PagedIndex) -> PagedIndex:
    return index if isinstance(index, PagedIndex) else \
        build_paged_index(index)


def next_geq(index: FlatIndex | PagedIndex, list_ids: jax.Array,
             xs: jax.Array, interpret: bool | None = None) -> jax.Array:
    """One-shot convenience: packs the paged operands on the fly."""
    if interpret is None:
        interpret = should_interpret()
    tables, statics, host = pad_paged_operands(_as_paged(index))
    return next_geq_paged(tables, host, np.asarray(list_ids),
                          np.asarray(xs), interpret=interpret, **statics)


def next_geq_probe(index: FlatIndex | PagedIndex, list_ids: jax.Array,
                   xs: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """Row-wise probe: (B,) list ids × (B, M) probes -> (B, M) next_geq
    values, by flattening into one fused kernel launch."""
    B, M = xs.shape
    flat_ids = jnp.repeat(jnp.asarray(list_ids, jnp.int32), M)
    vals = next_geq(index, flat_ids, jnp.asarray(xs).reshape(-1),
                    interpret=interpret)
    return vals.reshape(B, M)


def list_intersect(index: FlatIndex | PagedIndex, long_ids: jax.Array,
                   xs: jax.Array,
                   interpret: bool | None = None) -> jax.Array:
    """Membership-filter the probe matrix against the long lists: keeps
    xs[b, m] where it occurs in list long_ids[b], INT_INF elsewhere
    (INT_INF padding in xs never matches)."""
    vals = next_geq_probe(index, long_ids, xs, interpret=interpret)
    sent = jnp.int32(INT_INF)
    xs = jnp.asarray(xs, jnp.int32)
    return jnp.where((vals == xs) & (xs != sent), xs, sent)
