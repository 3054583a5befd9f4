"""Wrappers for the grid-blocked EF next_geq kernel.

Same two-tier shape as ``list_intersect.ops``:

* ``pad_ef_operands(store)`` — page the packed low-bits array once per
  index; engines cache the pack alongside the select samples.
* ``next_geq_ef(...)`` — the serving path: host probe state + low-window
  page routing (``route_low_pages``), one ``pallas_call``, unsort.

The router IS the numpy reference's first half (``ef_probe_state_np`` —
masks + the three high-bits selects over the page-sample directory), so
the kernel inherits its arithmetic bit for bit and only the low-bits
bucket search runs on device.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ... import obs
from .. import count_launch
from ...core.ef import EFStore, ef_probe_state_np
from .ef_next_geq import EF_PAGE, TILE_Q, ef_intersect_pallas


def pad_ef_operands(store: EFStore) -> tuple[jax.Array, dict]:
    """Page the packed low-bits words to (num_pages, 1, EF_PAGE) int32.
    Compute once per index (PallasEngine caches this in its EF pack)."""
    wl = int(store.lo_words.size)
    num_pages = max(1, -(-wl // EF_PAGE))
    pg = np.zeros(num_pages * EF_PAGE, dtype=np.uint32)
    pg[:wl] = store.lo_words
    tables = jnp.asarray(pg.view(np.int32).reshape(num_pages, 1, EF_PAGE))
    statics = dict(max_win=int(store.max_bucket) + 1, num_pages=num_pages)
    return tables, statics


def route_low_pages(store: EFStore, rank_pg: np.ndarray,
                    list_ids: np.ndarray, xs: np.ndarray,
                    num_pages: int):
    """Host half of the EF query path: probe state + page scheduling.

    Returns ``(order, tile_base, k_pages, lanes)`` where ``lanes`` is the
    dict of (Q_pad,) int32 kernel operands sorted by first low-bits page
    and padded to a TILE_Q multiple (repeating the final lane), and
    ``out_sorted[np.argsort(order)]`` restores request order.

    Lanes the selects already answered — plus ``l == 0`` lists, whose
    answer is pure high bits (``found = i1 > i0``; the bucket holds at
    most one element when l == 0, its low part is empty) — are finalized
    here: ``cnt = 0`` parks them at the lowest active page so they never
    widen a mixed tile's page window."""
    st = ef_probe_state_np(store, rank_pg, list_ids, xs)
    l = st["l"]
    done = st["done"].copy()
    val0 = st["val0"].copy()
    zl = (~done) & (l == 0)
    v_zl = np.where(st["i1"] > st["i0"], st["hx"], st["hi1"])
    val0 = np.where(zl, v_zl, val0)
    done |= zl

    gb0 = store.lo_word[st["lids"]].astype(np.int64) * 32
    e_max = np.maximum(st["i1"] - 1, st["i1m"])
    cnt = np.where(done, 0, e_max - st["i0"] + 1)
    # first element is processed at the step its HIGH word's page is
    # resident; its low word is then the previous page's last word (the
    # carry scratch) at worst — so the lane window starts at the LOW
    # word's page, guaranteeing the carry was written one step earlier.
    w_first = (gb0 + st["i0"] * l) >> 5
    w_last = (gb0 + e_max * l + np.maximum(l, 1) - 1) >> 5
    pg_lo = np.clip(w_first // EF_PAGE, 0, num_pages - 1)
    pg_hi = np.clip(w_last // EF_PAGE, 0, num_pages - 1)
    act = ~done
    park = int(pg_lo[act].min()) if act.any() else 0
    lo = np.where(act, pg_lo, park)
    hi = np.where(act, pg_hi, park)

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

    lanes = dict(done=done.astype(np.int32), val0=val0.astype(np.int32),
                 i0=st["i0"].astype(np.int32), cnt=cnt.astype(np.int32),
                 i1=st["i1"].astype(np.int32),
                 i1m=st["i1m"].astype(np.int32),
                 hx=st["hx"].astype(np.int32),
                 hi1=st["hi1"].astype(np.int32), l=l.astype(np.int32),
                 xlo=st["xlo"].astype(np.int32),
                 gb0=gb0.astype(np.int32))
    lanes = {k: v[take] for k, v in lanes.items()}
    return order, base.astype(np.int32), k_pages, lanes


_LANE_KEYS = ("done", "val0", "i0", "cnt", "i1", "i1m", "hx", "hi1", "l",
              "xlo", "gb0")


@partial(jax.jit, static_argnames=("max_win", "k_pages", "interpret"))
def _ef_call(tables, tile_base, *lane_arrays, max_win: int, k_pages: int,
             interpret: bool):
    return ef_intersect_pallas(tile_base, *lane_arrays, lo_pg=tables,
                               max_win=max_win, k_pages=k_pages,
                               interpret=interpret)


def next_geq_ef(tables: jax.Array, statics: dict, store: EFStore,
                rank_pg: np.ndarray, list_ids: np.ndarray, xs: np.ndarray,
                *, interpret: bool) -> np.ndarray:
    """Fused EF next_geq over a cached operand pack: (Q,) ids × (Q,)
    probes -> (Q,) int32 values, INT_INF where no element >= x exists.
    numpy in, numpy out, same convention (and reason) as
    ``list_intersect.ops.next_geq_paged``."""
    q = np.asarray(list_ids).shape[0]
    if q == 0:
        return np.zeros(0, np.int32)
    with obs.span("kernel.route"):
        order, base, k_pages, lanes = route_low_pages(
            store, rank_pg, list_ids, xs, statics["num_pages"])
    with obs.span("kernel.launch"):
        count_launch("ef_next_geq", interpret)
        out = _ef_call(tables, jnp.asarray(base),
                       *(jnp.asarray(lanes[k]) for k in _LANE_KEYS),
                       max_win=statics["max_win"], k_pages=k_pages,
                       interpret=interpret)
    with obs.span("device.wait"):
        out = np.asarray(out)
    unsort = np.empty(q, np.int64)
    unsort[order] = np.arange(q)
    return out[:q][unsort]
