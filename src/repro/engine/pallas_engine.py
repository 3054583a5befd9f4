"""PallasEngine: the grid-blocked ``list_intersect`` kernel behind the
engine API.

The device hot path — phrase-sum skipping + fixed-depth grammar descent —
runs in ONE ``pallas_call`` per probe batch over the **paged** stream
layout (``kernels/list_intersect``, DESIGN.md §2.5): the host half of the
path (page routing: bucket lookup, anchor-page sort, per-tile base pages
for the scalar-prefetch BlockSpec) is numpy, the device half never holds
more than one stream page per kernel instance.  Expansion of the short
side reuses the jnp positional-descent program (it is outside the
per-probe critical path).  The paged index and lane-padded kernel operands
are computed once at construction and reused for every launch.

``interpret=None`` auto-selects: compiled on TPU, interpreter elsewhere —
the same convention as the other kernels' ops wrappers.

Merged probe rounds (the serving scheduler's cross-query dispatches,
DESIGN.md §8.2) arrive through the inherited
``DeviceEngine.dispatch_round`` pow2 padding; the kernel's own host-side
router then re-pads the sorted queries to a ``TILE_Q`` multiple, so a
merged round costs the same launch shape as a single-query round of the
same bucket.
"""

from __future__ import annotations

import numpy as np

from ..core.jax_index import (FlatIndex, PagedIndex, build_paged_index,
                              DEFAULT_PAGE)
from .. import obs
from ..core.repair import RePairResult
from ..kernels import should_interpret
from ..kernels.list_intersect import ops as K
from ..kernels.page_score import ops as PS
from .base import Engine
from .device import DeviceEngine


class PallasEngine(DeviceEngine):
    name = "pallas"

    def __init__(self, res: RePairResult, fi: FlatIndex | None = None,
                 max_short_len: int = 256, B: int = 8,
                 fallback: Engine | None = None,
                 interpret: bool | None = None,
                 page_size: int = DEFAULT_PAGE,
                 pi: PagedIndex | None = None, **kwargs):
        super().__init__(res, fi=fi, max_short_len=max_short_len, B=B,
                         fallback=fallback, **kwargs)
        self.interpret = (should_interpret() if interpret is None
                          else interpret)
        self.pi = pi if pi is not None else build_paged_index(self.fi,
                                                              page_size)
        if self._wants_store():
            # pack the RAM-tier operands only — the stream pages stay in
            # the admission cache's pool and enter each launch through the
            # scalar-prefetched slot table (DESIGN.md §11.2)
            self._tables, self._statics, self._host = K.pad_paged_operands(
                self.pi, include_stream=False)
            self.pi = self._attach_store(self.pi)
        else:
            self._tables, self._statics, self._host = K.pad_paged_operands(
                self.pi)
        self._score_pack = None   # page_score operands, first ranked query

    # -- ranked scoring (DESIGN.md §9) --------------------------------------

    def page_elem_bucket(self) -> int:
        """TILE_B-aligned row width for the grid-blocked decode kernel."""
        m = max(1, int(self.score_index.max_page_elems))
        return max(128, 1 << (m - 1).bit_length())

    def decode_page_batch(self, entries) -> np.ndarray:
        """Fused decode+score device path: page entries decode in one
        grid-blocked ``page_score`` pallas_call (one stream page DMA'd
        per entry — the block the pruning decision skipped never moves);
        the membership probes that score the fresh candidates then ride
        the fused ``list_intersect`` kernel, and the float32 reduction
        runs on device.  Out of core the full-stream operand pack does not
        exist, and the windowed jnp decode reads the resident pool.

        The kernel addresses an entry by the stream page that holds it,
        so the directory's pages (as built, after rounding) must tile this
        engine's pages — the launcher's fine 128-symbol directory does.
        Any other cut is refused rather than decoded by another path."""
        if self.resident is not None:
            return super().decode_page_batch(entries)
        si = self.score_index
        if int(self.pi.page_size) % int(si.page_size):
            raise ValueError(
                f"score directory page size {si.page_size} does not divide "
                f"the pallas engine's stream page size {self.pi.page_size}")
        if self._score_pack is None:
            self._score_pack = PS.pad_score_operands(self.pi)
        tables, statics = self._score_pack
        e = np.asarray(entries, np.int64).ravel()
        with obs.span("kernel.route"):
            lo = si.pg_sym_lo[e].astype(np.int64)
            pages = lo // int(self.pi.page_size)
            meta = (pages, lo - pages * int(self.pi.page_size),
                    si.pg_sym_hi[e] - si.pg_sym_lo[e], si.pg_base[e],
                    si.pg_head[e], si.pg_count[e])
        return PS.page_decode(tables, statics, *meta,
                              b_pad=self.page_elem_bucket(),
                              interpret=self.interpret)

    def _next_geq_dev(self, list_ids, xs) -> np.ndarray:
        return K.next_geq_paged(self._tables, self._host,
                                np.asarray(list_ids), np.asarray(xs),
                                interpret=self.interpret, **self._statics)

    def _next_geq_resident(self, lids, xs) -> np.ndarray:
        """Kernel launch against the admission cache: the router's page
        windows are remapped through the resident slot table into the
        scalar-prefetch index_map, so the DMA engine fetches pool rows
        while the kernel's offset math stays in stream coordinates."""
        return K.next_geq_resident(self._tables, self._host, self.resident,
                                   np.asarray(lids), np.asarray(xs),
                                   interpret=self.interpret,
                                   **self._statics)

    # -- codec-tier device paths (DESIGN.md §10.4) --------------------------

    def _build_ef_pack(self) -> dict:
        from ..kernels.ef_next_geq import ops as EFK
        rank = self.tier.ef.select_samples()
        tables, statics = EFK.pad_ef_operands(self.tier.ef)
        return {"samples": rank, "kern": (tables, statics)}

    def _ef_next_geq(self, lids, xq) -> np.ndarray:
        from ..kernels.ef_next_geq import ops as EFK
        pack = self._ef_pack()
        tables, statics = pack["kern"]
        return EFK.next_geq_ef(tables, statics, self.tier.ef,
                               pack["samples"], np.asarray(lids),
                               np.asarray(xq), interpret=self.interpret)

    def _probe_dev(self, long_ids, xs) -> np.ndarray:
        B, M = np.shape(xs)
        flat_ids = np.repeat(np.asarray(long_ids, np.int32), M)
        return self._next_geq_dev(
            flat_ids, np.asarray(xs).reshape(-1)).reshape(B, M)
