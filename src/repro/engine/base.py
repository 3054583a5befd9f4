"""The backend-pluggable query engine API (DESIGN.md §2.4).

One interface, three interchangeable backends:

* :class:`~repro.engine.host.HostEngine`   — the paper's host cursor
  structures (``CompressedList`` / ``SampledList`` / ``LookupList``);
* :class:`~repro.engine.JnpEngine`         — pure-jnp fixed-trip-count
  programs (the bit-exact reference for the kernel);
* :class:`~repro.engine.PallasEngine`      — the fused ``list_intersect``
  Pallas kernel (bucket lookup + phrase-sum skipping + grammar descent in
  one ``pallas_call``).

Every operation takes/returns **numpy** at the boundary so callers
(server, benchmarks, examples) are backend-agnostic; sentinel for "no
element" is ``INT_INF`` (int32 max).

The four operations:

* ``next_geq_batch(list_ids, xs)`` — smallest element >= x per query;
* ``member_batch(list_ids, xs)``   — boolean membership per query;
* ``intersect_pairs(pairs)``       — batched 2-term conjunctive queries;
* ``intersect_multi(idxs)``        — one k-term conjunctive query,
  pairwise svs from shortest to longest by *uncompressed* length (§3.3 —
  Re-Pair compressed lengths are non-monotonic).

``dispatch_round(list_ids, xs, algo)`` is the serving runtime's entry
point (DESIGN.md §8.2): one merged probe round — the concatenated
ProbeRound workloads of every in-flight query — routed to
``next_geq_batch``/``next_geq_bys_batch``, padded to a power-of-two
bucket on the device engines so merged sizes reuse O(log Q) jit entries.

**Codec tier** (DESIGN.md §10): constructed with ``codec`` (or under
``REPRO_CODEC``), the engine carries a per-list codec assignment
(Re-Pair / Elias-Fano / bitmap).  The public probe entry points split
each round's lanes by codec and dispatch every sub-round through that
codec's ``next_geq`` path; with no tier (the default) the classic
Re-Pair path runs with zero overhead.  The Re-Pair structures remain
the decode ground truth in every mode — the tier is a probe-path and
space overlay, so results are bit-identical across assignments.
"""

from __future__ import annotations

import abc
import os
from typing import Sequence

import numpy as np

from .. import obs
from ..core.cache import LRUCache
from ..core.jax_index import (DEFAULT_PAGE, INT_INF, ScoreIndex,
                              accumulate_scores, build_score_index)
from ..core.repair import RePairResult

#: entry bound of the per-engine decoded-list LRU (env override
#: ``REPRO_DECODE_CACHE``; 0 disables caching)
DECODE_CACHE_SIZE = int(os.environ.get("REPRO_DECODE_CACHE", "512"))

def _env_flag(name: str, default: bool = True) -> bool:
    raw = os.environ.get(name, "").strip().lower()
    if not raw:
        return default
    return raw not in ("0", "off", "false", "no")


#: cross-query lane dedup in merged rounds (DESIGN.md §13.1); env
#: override ``REPRO_DEDUP=0`` restores the PR 5 dispatch-every-lane path
DEDUP_ENABLED = _env_flag("REPRO_DEDUP", True)


class Engine(abc.ABC):
    """Backend-pluggable query engine over one Re-Pair compressed index."""

    name: str = "abstract"

    #: index-version token in every decode-cache key — the same keying the
    #: serving scheduler's caches use (DESIGN.md §8.3).  ``QueryServer``
    #: stamps it at each hot-swap; bumping it orphans the old entries, so
    #: the LRU evicts them as new decodes land.
    index_version: int = 0

    def __init__(self, res: RePairResult,
                 codec: "str | object | None" = None,
                 store: "str | object | None" = None,
                 resident_pages: int | None = None,
                 resident=None):
        self.res = res
        self.lengths = np.asarray(res.orig_lengths, dtype=np.int64)
        # out-of-core tier (DESIGN.md §11): ``store`` picks the page-store
        # backend (None defers to REPRO_STORE; ""/none disables), and
        # ``resident_pages`` bounds the admission cache (None defers to
        # REPRO_RESIDENT_PAGES).  A prebuilt ``resident`` shares another
        # engine's pool (the device engines hand theirs to the host
        # fallback so both tiers hit one cache).  Construction is deferred
        # to ``_init_store`` — concrete engines call it once their paged
        # geometry exists.
        from ..store import resolve_store_kind
        self.store = None
        self.resident = None
        self._resident_pages = resident_pages
        if resident is not None:
            self.resident = resident
            self.store = resident.store
            self._store_kind = None
        else:
            self._store_kind = resolve_store_kind(store)
        self._decoded = LRUCache(DECODE_CACHE_SIZE)
        self._score_index: ScoreIndex | None = None
        #: optional override of the score-directory page granularity —
        #: assign before the first ranked query to trade directory size
        #: against pruning resolution (tests/benchmarks pin 128 here)
        self.score_page_size: int | None = None
        # per-list codec tier (DESIGN.md §10): None in pure-repair mode;
        # a prebuilt CodecTier instance passes through so servers share
        # one tier across engine rebuilds
        from ..index.codec_tier import build_codec_tier
        self.tier = build_codec_tier(res, codec)
        #: bounded, version-keyed LRU for the EF select samples and the
        #: derived device packs — the same ``REPRO_DECODE_CACHE`` bound
        #: and ``index_version`` keying as the decode LRU, so a hot swap
        #: orphans stale packs and the LRU evicts them (DESIGN.md §10.2)
        self._ef_sel = LRUCache(DECODE_CACHE_SIZE)
        #: per-codec sub-dispatch telemetry, surfaced by the scheduler
        self.codec_dispatches = {"repair": 0, "ef": 0, "bitmap": 0}
        #: cross-query lane dedup toggle (DESIGN.md §13.1) — resolved
        #: from ``REPRO_DEDUP`` at construction; tests flip it per-engine
        self.dedup = DEDUP_ENABLED
        #: cumulative merged-round lane accounting (DESIGN.md §13.4);
        #: the scheduler snapshots deltas around each dispatch
        self.lane_stats = {"real_lanes": 0, "unique_lanes": 0,
                           "pad_lanes": 0, "dispatched_lanes": 0}
        #: True while inside a merged-round dispatch — scopes the device
        #: engines' pad-lane accounting to the round path (point APIs
        #: like ``member_batch`` pad too but aren't merged-round work)
        self._in_round = False

    # -- point operations ---------------------------------------------------

    @abc.abstractmethod
    def _next_geq_repair(self, list_ids: np.ndarray,
                         xs: np.ndarray) -> np.ndarray:
        """(Q,) int32 values over the Re-Pair structures; INT_INF where
        no element >= x exists.  The backend-specific probe primitive."""

    def next_geq_batch(self, list_ids: np.ndarray,
                       xs: np.ndarray) -> np.ndarray:
        """(Q,) int32 values; INT_INF where no element >= x exists.  With
        a codec tier, lanes split by their list's codec and each
        sub-batch runs that codec's probe path."""
        if self.tier is None:
            return np.asarray(self._next_geq_repair(list_ids, xs))
        return self._route_codecs(list_ids, xs, "svs")

    def member_batch(self, list_ids: np.ndarray, xs: np.ndarray) -> np.ndarray:
        """Boolean membership per lane.  Bitmap-coded lists answer with a
        single word test — no probe, no decode (DESIGN.md §10.3); all
        other lanes reduce to ``next_geq == x``."""
        lids = np.asarray(list_ids).ravel()
        xq = np.asarray(xs).ravel()
        if self.tier is None or self.tier.bm is None:
            return np.asarray(self.next_geq_batch(lids, xq)) == xq
        from ..index.codec_tier import CODEC_BITMAP, bitmap_member_np
        codes = self.tier.codec[lids.astype(np.int64)]
        out = np.zeros(lids.size, dtype=bool)
        bm = np.flatnonzero(codes == CODEC_BITMAP)
        rest = np.flatnonzero(codes != CODEC_BITMAP)
        if rest.size:
            out[rest] = (np.asarray(self.next_geq_batch(lids[rest],
                                                        xq[rest]))
                         == xq[rest])
        if bm.size:
            out[bm] = bitmap_member_np(self.tier.bm, lids[bm], xq[bm])
        return out

    def next_geq_bys_batch(self, list_ids: np.ndarray,
                           xs: np.ndarray) -> np.ndarray:
        """Batched Baeza-Yates-style binary-search next_geq [BY04]; same
        contract as ``next_geq_batch``.  Non-repair lanes route to their
        codec path — EF and bitmap probes ARE position-searches already,
        so "bys" only differentiates the repair lanes."""
        if self.tier is None:
            return np.asarray(self._next_geq_repair_bys(list_ids, xs))
        return self._route_codecs(list_ids, xs, "bys")

    def _next_geq_repair_bys(self, list_ids: np.ndarray,
                             xs: np.ndarray) -> np.ndarray:
        """Repair-lane [BY04] probe: the base implementation bisects the
        DECODED list (the classic uncompressed baseline); device engines
        override it with a positional bisection of the compressed
        stream's phrase-sum prefix table
        (``jnp_backend.next_geq_bys_batch``)."""
        lids = np.asarray(list_ids)
        xq = np.asarray(xs, np.int64)
        out = np.full(lids.shape, int(INT_INF), dtype=np.int64)
        for li in np.unique(lids):
            arr = self.decode_list(int(li))
            m = lids == li
            pos = np.searchsorted(arr, xq[m])
            hit = pos < arr.size
            out[m] = np.where(hit, arr[np.minimum(pos, arr.size - 1)],
                              int(INT_INF))
        return out.astype(np.int32)

    # -- out-of-core storage (DESIGN.md §11) ---------------------------------

    def _init_store(self, pi=None, page_size: int | None = None) -> None:
        """Materialize the requested page store + admission cache.  Called
        once by each concrete engine after its paged geometry exists;
        ``pi`` (a PagedIndex with real stream arrays) makes the store a
        zero-recompute snapshot of the exact pages the engine serves."""
        if self.resident is not None or self._store_kind is None:
            return
        from ..store import PageStore, ResidentSet, build_page_store
        kind = self._store_kind
        if isinstance(kind, PageStore):
            store = kind
        else:
            store = build_page_store(self.res, kind=kind,
                                     page_size=page_size, pi=pi)
        self.store = store
        self.resident = ResidentSet(store, budget=self._resident_pages)

    def prefault(self, probes=(), score_entries=None) -> None:
        """Fault the union page working set of one tick's merged rounds in
        a single batched gather (DESIGN.md §11.3).  ``probes`` is an
        iterable of ``(list_ids, xs)`` rounds; ``score_entries`` the
        tick's merged ScoreRound lanes.  No-op without a store — and
        purely an optimization with one: every dispatch path re-ensures
        its own working set, prefaulting just coalesces the tick's misses
        into one ``store.gather``."""
        if self.resident is None:
            return
        pages = self.working_set(probes, score_entries)
        if pages.size:
            self.resident.ensure(pages)

    def working_set(self, probes=(), score_entries=None) -> np.ndarray:
        """The union page working set of one tick's merged rounds —
        ``prefault``'s page computation, reused by the scheduler's
        overlapped-prefetch predictor (DESIGN.md §13.3)."""
        if self.resident is None:
            return np.empty(0, np.int64)
        groups = []
        for lids, xq in probes:
            lids = np.asarray(lids, np.int64).ravel()
            xq = np.asarray(xq, np.int64).ravel()
            if self.tier is not None and lids.size:
                m = self.tier.codec[lids] == 0   # only Re-Pair lanes
                lids, xq = lids[m], xq[m]        # touch the stream pool
            if lids.size:
                groups.append(self._probe_pages(lids, xq))
        if score_entries is not None:
            e = np.asarray(score_entries, np.int64).ravel()
            if e.size:
                groups.append(self._score_pages(e))
        groups = [g for g in groups if g.size]
        if not groups:
            return np.empty(0, np.int64)
        return np.unique(np.concatenate(groups))

    def span_pages(self, term_ids) -> np.ndarray:
        """Pages covering the FULL stream spans of ``term_ids`` — the
        prefetch predictor's superset for machines whose next probe
        values aren't known yet (queued first rounds, continuation
        re-probes of the same lists).  Non-repair lanes never touch the
        stream pool, so tiered engines keep only repair-coded lists."""
        if self.resident is None:
            return np.empty(0, np.int64)
        from ..store import pages_in_spans
        u = np.unique(np.asarray(list(term_ids), np.int64).ravel())
        u = u[(u >= 0) & (u < self.lengths.size)]
        if self.tier is not None and u.size:
            u = u[self.tier.codec[u] == 0]
        if u.size == 0:
            return np.empty(0, np.int64)
        starts = self.store.meta["starts"]
        return pages_in_spans(starts[u], starts[u + 1],
                              self.store.page_size)

    def _probe_pages(self, lids: np.ndarray, xq: np.ndarray) -> np.ndarray:
        """Pages one merged probe round can touch.  Host granularity is
        the full list span (the accessors materialize spans — the paper's
        contiguous-block unit); device engines override with the router's
        per-lane skip windows."""
        from ..store import pages_in_spans
        starts = self.store.meta["starts"]
        u = np.unique(lids)
        return pages_in_spans(starts[u], starts[u + 1],
                              self.store.page_size)

    def _score_pages(self, entries: np.ndarray) -> np.ndarray:
        """Pages one merged ScoreRound decode can touch."""
        from ..store import pages_in_spans
        si = self.score_index
        return pages_in_spans(si.pg_sym_lo[entries], si.pg_sym_hi[entries],
                              self.store.page_size)

    # -- merged probe rounds -------------------------------------------------

    def dispatch_round(self, list_ids: np.ndarray, xs: np.ndarray,
                       algo: str = "svs") -> np.ndarray:
        """One (possibly cross-query merged) probe round: route the flat
        ``(list_ids, xs)`` workload of a :class:`~repro.query.steps.ProbeRound`
        to the matching primitive — ``"svs"`` → ``next_geq_batch``,
        ``"bys"`` → ``next_geq_bys_batch``.  Both are elementwise in the
        (list, probe) pairs, so concatenating the rounds of many queries
        into one dispatch returns bit-identical values per lane.

        With a codec tier the merged round is **split by (codec, algo)
        into sub-rounds** (DESIGN.md §10.3): each sub-round dispatches
        through its codec's ``next_geq`` path, so a tick of mixed-codec
        queries costs one dispatch per (engine, codec, algo).  Device
        engines pad every sub-round to a power-of-two bucket
        (DESIGN.md §8.2) so arbitrary merged sizes reuse O(log Q) jit
        entries; the host tier dispatches unpadded — its loop would pay
        for the dead lanes.

        **Hot-path dedup** (DESIGN.md §13): duplicate ``(list_id, x)``
        lanes — different queries probing the same hot term at the same
        frontier — collapse to one representative via ``np.unique``'s
        inverse map before codec routing and padding; results scatter
        back to every requesting lane, bit-identical by construction.
        Nothing survives a round: every unique lane is dispatched."""
        with obs.span("engine.lanes"):
            lids = np.asarray(list_ids, np.int32).ravel()
            xq = np.asarray(xs, np.int32).ravel()
            n = lids.size
            if n == 0:
                return np.empty(0, dtype=np.int32)
            st = self.lane_stats
            st["real_lanes"] += n
            inv = None
            if self.dedup and n > 1:
                # (lid, x) -> one int64 key; bijective because list ids are
                # non-negative int32 and x's 32 bits are masked in whole
                key = ((lids.astype(np.int64) << 32)
                       | (xq.astype(np.int64) & 0xFFFFFFFF))
                _, uidx, inv = np.unique(key, return_index=True,
                                         return_inverse=True)
                if uidx.size == n:
                    inv = None       # nothing collapsed — skip the scatter
                else:
                    lids, xq = lids[uidx], xq[uidx]
            st["unique_lanes"] += lids.size
            out = self._dispatch_lanes(lids, xq, algo)
            return out if inv is None else out[inv]

    def _dispatch_lanes(self, lids: np.ndarray, xq: np.ndarray,
                        algo: str) -> np.ndarray:
        """The post-dedup slice of a merged round: codec
        routing + backend dispatch (the whole PR 5 round body)."""
        self.lane_stats["dispatched_lanes"] += lids.size
        self._in_round = True
        try:
            if self.tier is None:
                self.codec_dispatches["repair"] += 1
                return np.asarray(self._dispatch_codec(0, lids, xq, algo))
            return self._route_codecs(lids, xq, algo)
        finally:
            self._in_round = False

    def _route_codecs(self, list_ids, xs, algo: str) -> np.ndarray:
        """Split lanes by their list's codec; one sub-dispatch each."""
        from ..index.codec_tier import CODEC_NAMES
        lids = np.asarray(list_ids, np.int32).ravel()
        xq = np.asarray(xs, np.int32).ravel()
        codes = self.tier.codec[lids.astype(np.int64)]
        out = np.empty(lids.size, dtype=np.int32)
        for c in np.unique(codes):
            m = np.flatnonzero(codes == c)
            out[m] = np.asarray(
                self._dispatch_codec(int(c), lids[m], xq[m], algo))
            self.codec_dispatches[CODEC_NAMES[int(c)]] += 1
        return out

    def _dispatch_codec(self, codec: int, lids: np.ndarray, xq: np.ndarray,
                        algo: str) -> np.ndarray:
        """One single-codec sub-round (host tier: unpadded; the device
        override pads to the pow2 bucket before delegating here)."""
        if codec == 1:                       # CODEC_EF
            return self._ef_next_geq(lids, xq)
        if codec == 2:                       # CODEC_BITMAP
            return self._bitmap_next_geq(lids, xq)
        if algo == "bys":
            return np.asarray(self._next_geq_repair_bys(lids, xq))
        return np.asarray(self._next_geq_repair(lids, xq))

    # -- codec-tier probe paths (DESIGN.md §10) ------------------------------

    def _ef_pack(self) -> dict:
        """Select samples (+ backend packs) for the EF store, cached in
        the bounded version-keyed LRU (the PR 5 swap-eviction contract)."""
        key = (self.index_version, "ef")
        pack = self._ef_sel.get(key)
        if pack is None:
            pack = self._build_ef_pack()
            self._ef_sel.put(key, pack)
        return pack

    def _build_ef_pack(self) -> dict:
        return {"samples": self.tier.ef.select_samples()}

    def _ef_next_geq(self, lids, xq) -> np.ndarray:
        from ..core import ef as EF
        return EF.ef_next_geq_np(self.tier.ef, self._ef_pack()["samples"],
                                 lids, xq)

    def _bitmap_next_geq(self, lids, xq) -> np.ndarray:
        from ..index.codec_tier import bitmap_next_geq_np
        return bitmap_next_geq_np(self.tier.bm, lids, xq)

    # -- whole-list decode ---------------------------------------------------

    def decode_list(self, i: int) -> np.ndarray:
        """Full expansion of one list to sorted int64 doc ids (cached —
        the boolean executor's merge/union/complement operands).  The
        cache is a bounded LRU keyed on ``(index_version, i)``; the
        cached array is returned by reference and frozen: an accidental
        in-place mutation by a caller raises instead of silently
        corrupting every later query that touches the list."""
        i = int(i)
        key = (self.index_version, i)
        out = self._decoded.get(key)
        if out is None:
            out = self._decode_list(i)
            out.flags.writeable = False
            self._decoded.put(key, out)
        return out

    def _decode_list(self, i: int) -> np.ndarray:
        return self.res.decode_list(i)

    # -- ranked scoring (DESIGN.md §9) ---------------------------------------

    @property
    def score_index(self) -> ScoreIndex:
        """The engine's BM25 tables + block-max page directory, built
        lazily on the first ranked query.  Page entries are cut at THIS
        engine's stream-page boundaries (``_score_page_size``) so a page
        decode touches exactly the pages the probe kernels DMA by."""
        if self._score_index is None:
            self._score_index = build_score_index(
                self.res, page_size=self._score_page_size())
        return self._score_index

    def set_score_index(self, si: ScoreIndex) -> None:
        """Share one prebuilt scoring tier across engines over the same
        index (the differential gate and benchmarks build it once).  The
        page geometry must match — entries address this engine's pages."""
        if int(si.page_size) != int(self._score_page_size()):
            raise ValueError(
                f"score index page_size {si.page_size} != engine page "
                f"size {self._score_page_size()}")
        self._score_index = si

    def _score_page_size(self) -> int:
        if self.score_page_size is not None:
            return int(self.score_page_size)
        return DEFAULT_PAGE

    def page_elem_bucket(self) -> int:
        """Static width of a decoded page-entry row: the directory's max
        element count rounded to a power of two (one jit entry per index,
        not one per entry shape)."""
        m = max(1, int(self.score_index.max_page_elems))
        return max(8, 1 << (m - 1).bit_length())

    def decode_page_batch(self, entries: np.ndarray) -> np.ndarray:
        """Materialize block-max page entries: (Q,) entry ids ->
        (Q, page_elem_bucket) int32 doc ids, INT_INF past each entry's
        count.  Host reference: slice the cached whole-list decode (the
        per-entry ``elem_lo``/``count`` columns exist for exactly this)."""
        si = self.score_index
        e = np.asarray(entries, np.int64).ravel()
        out = np.full((e.size, self.page_elem_bucket()), int(INT_INF),
                      np.int32)
        for q, ei in enumerate(e.tolist()):
            cnt = int(si.pg_count[ei])
            lo = int(si.pg_elem_lo[ei])
            docs = self.decode_list(int(si.pg_list[ei]))
            out[q, :cnt] = docs[lo:lo + cnt]
        return out

    def dispatch_score_round(self, entries: np.ndarray) -> np.ndarray:
        """One (possibly cross-query merged) ScoreRound: decode the flat
        page-entry lanes of every in-flight ranked query.  Elementwise in
        the entry lanes, so merged dispatches return bit-identical rows;
        device engines pad to the same power-of-two buckets as
        ``dispatch_round``.

        Duplicate entry lanes — several ranked queries scoring the same
        hot page in one tick — dedup exactly like probe lanes: decode
        the unique set, scatter rows back via the inverse map
        (DESIGN.md §13.1)."""
        with obs.span("engine.lanes"):
            e = np.asarray(entries, np.int32).ravel()
            n = e.size
            if n == 0:
                return np.empty((0, self.page_elem_bucket()), np.int32)
            st = self.lane_stats
            st["real_lanes"] += n
            inv = None
            if self.dedup and n > 1:
                ue, inv = np.unique(e, return_inverse=True)
                if ue.size == n:
                    inv = None
                else:
                    e = ue.astype(np.int32)
            st["unique_lanes"] += e.size
            st["dispatched_lanes"] += e.size
            self._in_round = True
            try:
                rows = self._dispatch_score_unique(e)
            finally:
                self._in_round = False
            return rows if inv is None else rows[inv]

    def _dispatch_score_unique(self, entries: np.ndarray) -> np.ndarray:
        """The post-dedup slice of a merged ScoreRound (host tier:
        unpadded; the device override pads to the pow2 bucket)."""
        return self.decode_page_batch(entries)

    def score_batch(self, doc_ids: np.ndarray, terms) -> np.ndarray:
        """Exact BM25 scores of ``doc_ids`` for the term bag ``terms``:
        one merged membership round (all K terms × all D docs in a single
        ``next_geq_batch``) feeding the shared fixed-order float32
        reduction — bit-identical on every backend and to the oracle."""
        si = self.score_index
        docs = np.asarray(doc_ids, np.int64).ravel()
        ts = np.asarray(sorted({int(t) for t in terms
                                if 0 <= int(t) < self.lengths.size}),
                        np.int64)
        if docs.size == 0 or ts.size == 0:
            return np.zeros(docs.size, np.float32)
        lids = np.repeat(ts, docs.size).astype(np.int32)
        xs = np.tile(docs, ts.size).astype(np.int32)
        member = (np.asarray(self.next_geq_batch(lids, xs), np.int64)
                  .reshape(ts.size, docs.size) == docs)
        return accumulate_scores(si, ts, member, docs)

    # -- conjunctive queries ------------------------------------------------

    @abc.abstractmethod
    def intersect_pairs(self, pairs: Sequence[tuple[int, int]]
                        ) -> list[np.ndarray]:
        """Batched (term AND term); each result is a sorted int64 id array."""

    @abc.abstractmethod
    def intersect_multi(self, idxs: Sequence[int]) -> np.ndarray:
        """One k-term AND query; sorted int64 id array."""

    def intersect_multi_meld(self, idxs: Sequence[int]) -> np.ndarray:
        """One k-term AND by **adaptive melding** (Barbay–Kenyon style):
        all k cursors chase a common frontier — one batched ``next_geq``
        round advances every list to the current candidate, the maximum
        answer becomes the next candidate, agreement emits an element.
        O(k · alternation) probe rounds, each a single engine batch, so
        the same driver melds on host, device, and the sharded dispatch
        path.  Backend-generic: implemented purely over
        ``next_geq_batch``."""
        idxs = [int(i) for i in idxs]
        if not idxs:
            return np.empty(0, dtype=np.int64)
        if len(idxs) == 1:
            return self.decode_list(idxs[0]).copy()  # never alias the cache
        lids = np.asarray(idxs, dtype=np.int32)
        inf = int(INT_INF)
        out: list[int] = []
        x = 0
        while True:
            vals = np.asarray(self.next_geq_batch(
                lids, np.full(lids.size, x, dtype=np.int32)), np.int64)
            m = int(vals.max())
            if m >= inf:        # some list is exhausted — no more matches
                break
            if int(vals.min()) == m:
                out.append(m)
                x = m + 1
            else:
                x = m
        return np.asarray(out, dtype=np.int64)

    # -- helpers shared by the backends -------------------------------------

    def order_by_length(self, idxs: Sequence[int]) -> list[int]:
        """Shortest-first by UNCOMPRESSED length, the [BLOL06] svs order the
        paper adopts in §3.3."""
        return sorted(idxs, key=lambda i: int(self.lengths[i]))

    @staticmethod
    def compact(row: np.ndarray) -> np.ndarray:
        """Strip INT_INF sentinels from a padded device row."""
        row = np.asarray(row)
        return row[row != int(INT_INF)].astype(np.int64)
