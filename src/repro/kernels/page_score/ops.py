"""Wrappers for the grid-blocked page-entry decode kernel.

``pad_score_operands(pi)`` packs the device tables once per index — the
grammar tables packed ``(rows, 128)``, the paged symbol stream the probe
kernel already keeps, and two paged tables built on the host: each page's
inclusive prefix sums of the phrase sums (``sym_sum[c]``) and of the
per-symbol expansion lengths (``sym_len[c]``).  The kernel reads both
with the same one-page DMA discipline as values (gathering ``sym_len`` by
symbol id in-kernel would cost a (PAGE, S) one-hot per instance) and
rebases them at the entry's window instead of running a cumsum, which
Mosaic does not lower.

``page_decode(...)`` is the numpy-in/numpy-out launch the engine calls
per ScoreRound.  It splits a round into launches of at most
``ENTRIES_PER_LAUNCH`` entries, so the six scalar-prefetched per-entry
arrays fit the chip's SMEM.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ... import obs
from .. import count_launch, should_interpret
from ...core.jax_index import PagedIndex
from ..gather import pack_table
from ..list_intersect.ops import SMEM_BUDGET, paged_rows
from .page_score import TILE_B, page_decode_pallas

#: most entries (a power of two) whose six scalar-prefetched int32
#: metadata arrays fit ``SMEM_BUDGET``
ENTRIES_PER_LAUNCH = 1 << ((SMEM_BUDGET // (6 * 4)).bit_length() - 1)


def pad_score_operands(pi: PagedIndex) -> tuple[tuple[jax.Array, ...], dict]:
    """Kernel operand pack for one paged index: (tables, statics).
    Compute once per index; PallasEngine caches it lazily on the first
    ranked query."""
    fl = pi.flat
    c = np.asarray(fl.c, np.int64)
    page = pi.page_size
    pad = pi.num_pages * page - c.size

    def page_prefix(per_sym):
        # int64 cumsum wrapped to int32: the kernel only ever takes
        # differences inside one list's window, which fit int32
        v = np.pad(np.asarray(per_sym, np.int64)[c], (0, pad))
        return paged_rows(jnp.asarray(
            np.cumsum(v.reshape(-1, page), axis=1).astype(np.int32)))

    tables = (
        *(pack_table(a) for a in (fl.sym_left, fl.sym_right, fl.sym_sum,
                                  fl.sym_len)),
        paged_rows(pi.c_syms_pg), page_prefix(fl.sym_sum),
        page_prefix(fl.sym_len),
    )
    statics = dict(max_depth=fl.max_depth, T=fl.num_terminals)
    return tables, statics


@partial(jax.jit, static_argnames=("max_depth", "T", "b_pad", "interpret"))
def _call(tables: tuple[jax.Array, ...], pages: jax.Array, slo: jax.Array,
          nsym: jax.Array, base: jax.Array, head: jax.Array,
          cnt: jax.Array, *,
          max_depth: int, T: int, b_pad: int, interpret: bool) -> jax.Array:
    return page_decode_pallas(
        pages, slo, nsym, base, head, cnt, *tables, max_depth=max_depth,
        T=T, b_pad=b_pad, interpret=interpret)


def page_decode(tables: tuple[jax.Array, ...], statics: dict,
                pages: np.ndarray, slo: np.ndarray, nsym: np.ndarray,
                base: np.ndarray, head: np.ndarray, cnt: np.ndarray, *,
                b_pad: int, interpret: bool | None = None) -> np.ndarray:
    """Decode a batch of page entries: (Q,) metadata arrays -> (Q, b_pad)
    int32 doc ids, INT_INF padded.  ``b_pad`` must be a TILE_B multiple
    (the engine's ``page_elem_bucket`` guarantees it); ``cnt`` is the
    per-entry element count driving the output-tile guard."""
    if interpret is None:
        interpret = should_interpret()
    if b_pad % TILE_B:
        raise ValueError(f"b_pad {b_pad} not a multiple of {TILE_B}")
    meta = [np.asarray(a, np.int32) for a in (pages, slo, nsym, base, head,
                                               cnt)]
    # one launch per chunk of entries; every chunk is enqueued before any
    # is read
    outs = []
    with obs.span("kernel.launch"):
        for t in range(0, meta[0].shape[0], ENTRIES_PER_LAUNCH):
            count_launch("page_score", interpret, mxu=True)
            outs.append(_call(
                tables, *(jnp.asarray(a[t:t + ENTRIES_PER_LAUNCH])
                          for a in meta),
                b_pad=b_pad, interpret=bool(interpret), **statics))
    if not outs:
        return np.zeros((0, b_pad), np.int32)
    with obs.span("device.wait"):
        return np.concatenate([np.asarray(o) for o in outs])
