"""Device engines: the shared batching/routing logic plus the jnp backend.

``DeviceEngine`` owns everything backend-independent — expansion of the
short side, (short, long) normalization, candidate thinning for k-term
queries, host fallback for degenerate pairs — and delegates exactly one
primitive to the concrete backend: the batched next_geq probe.  JnpEngine
implements it with the vmapped fixed-trip-count program
(``engine/jnp_backend.py``, flat or paged addressing); PallasEngine with
the grid-blocked ``list_intersect`` kernel.  Both are therefore
interchangeable anywhere, and must agree bit-exactly.

Pair routing is vectorized: (short, long) normalization and the
device/host outlier split are numpy index arithmetic over the whole batch,
not a per-pair Python loop.

**Sharded dispatch** (DESIGN.md §2.5): construct a device engine with a
``mesh`` carrying a ``data`` axis and ``next_geq_batch`` runs under
``shard_map`` — the grammar tables are replicated to every device, the
compressed stream + spans + (b)-sampling are list-partitioned into
contiguous shards balanced by stream length (``shard_flat_index``), each
device answers the queries whose list it owns, and a ``pmax`` across the
axis assembles the batch (every list has exactly one owner; non-owners
emit -1).
"""

from __future__ import annotations

import abc
import dataclasses
import functools
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import obs
from ..core.jax_index import (FlatIndex, PagedIndex, as_store_backed,
                              build_flat_index, build_paged_index,
                              DEFAULT_PAGE)
from ..core.repair import RePairResult
from ..distributed.sharding import index_partition_spec
from ..kernels.list_intersect import ops as K
from .base import Engine
from .host import HostEngine
from . import jnp_backend as J


def _pull(x) -> np.ndarray:
    """``x`` as numpy; pulling a device array is the span ``device.wait``
    (the host blocks there until the device has produced it)."""
    if isinstance(x, jax.Array):
        with obs.span("device.wait"):
            return np.asarray(x)
    return np.asarray(x)


def shard_flat_index(fi: FlatIndex, num_shards: int
                     ) -> tuple[dict, np.ndarray, np.ndarray]:
    """List-partition a flat index into ``num_shards`` contiguous shards
    balanced by compressed-stream length.

    Returns ``(stacked, shard_of_list, local_lid)``: ``stacked`` maps field
    name -> (num_shards, ...) array (per-shard spans rebased to the shard's
    local stream, everything padded to the widest shard so the stack is
    rectangular), and the two (L,) routing tables give each global list its
    owning shard and its index within it.  Grammar tables are NOT here —
    they replicate (DESIGN.md §2.5)."""
    starts = np.asarray(fi.starts, np.int64)
    L = starts.size - 1
    N = int(starts[-1])
    c = np.asarray(fi.c, np.int64)
    boffs = np.asarray(fi.bucket_offsets, np.int64)
    bpos = np.asarray(fi.bck_c_pos, np.int64)
    babs = np.asarray(fi.bck_abs, np.int64)
    per_list = {k: np.asarray(getattr(fi, k), np.int64)
                for k in ("firsts", "lasts", "lengths", "kbits")}

    # contiguous list boundaries closest to equal stream slices
    targets = (np.arange(num_shards + 1) * N) // max(num_shards, 1)
    lb = np.searchsorted(starts, targets, side="left")
    lb[0], lb[-1] = 0, L
    lb = np.maximum.accumulate(lb)

    shard_of_list = np.repeat(np.arange(num_shards), np.diff(lb))
    local_lid = np.arange(L) - lb[shard_of_list]

    l_max = max(1, int(np.diff(lb).max(initial=0)))
    n_max = max(1, int((starts[lb[1:]] - starts[lb[:-1]]).max(initial=0)))
    nb_max = max(1, int((boffs[lb[1:]] - boffs[lb[:-1]]).max(initial=0)))

    def blank(fill, *shape):
        return np.full((num_shards, *shape), fill, dtype=np.int64)

    out = {"c": blank(0, n_max), "starts": blank(0, l_max + 1),
           "bucket_offsets": blank(0, l_max + 1),
           "bck_c_pos": blank(0, nb_max), "bck_abs": blank(0, nb_max),
           "firsts": blank(0, l_max), "lasts": blank(-1, l_max),
           "lengths": blank(0, l_max), "kbits": blank(1, l_max)}
    for d in range(num_shards):
        a, b = lb[d], lb[d + 1]
        c0, c1 = starts[a], starts[b]
        out["c"][d, : c1 - c0] = c[c0:c1]
        loc = starts[a : b + 1] - c0
        out["starts"][d, : b - a + 1] = loc
        out["starts"][d, b - a + 1 :] = loc[-1]
        o0, o1 = boffs[a], boffs[b]
        ob = boffs[a : b + 1] - o0
        out["bucket_offsets"][d, : b - a + 1] = ob
        out["bucket_offsets"][d, b - a + 1 :] = ob[-1]
        out["bck_c_pos"][d, : o1 - o0] = bpos[o0:o1]
        out["bck_abs"][d, : o1 - o0] = babs[o0:o1]
        for k, v in per_list.items():
            out[k][d, : b - a] = v[a:b]
    stacked = {k: v.astype(np.int32) for k, v in out.items()}
    return stacked, shard_of_list.astype(np.int32), local_lid.astype(np.int32)


_STACKED_FIELDS = ("c", "starts", "bucket_offsets", "bck_c_pos", "bck_abs",
                   "firsts", "lasts", "lengths", "kbits")


def _stacked_specs(mesh: Mesh) -> dict:
    return {k: index_partition_spec(k, (1, 1), mesh)
            for k in _STACKED_FIELDS}


@functools.lru_cache(maxsize=None)
def _sharded_dispatch(mesh: Mesh, axis: str, statics: tuple):
    """One jitted shard_map program per (mesh, static bounds): the index
    arrays are traced ARGUMENTS, not closure captures, so rebuilding the
    index (same bounds, same shapes) hits the same executable — the
    §2.3 no-retrace-on-rebuild rule extends to the sharded path."""
    bounds = dict(statics)
    rep = P(None)
    specs = _stacked_specs(mesh)

    def local_next_geq(stk, gram, sof, llid, gids, xs):
        stk = {k: v[0] for k, v in stk.items()}  # this shard's block
        local_fi = FlatIndex(**gram, **stk, **bounds)
        mine = sof[gids] == jax.lax.axis_index(axis)
        vals = J.next_geq_batch(local_fi, jnp.where(mine, llid[gids], 0), xs)
        # every list has exactly one owner; losers emit -1 and pmax
        # assembles the replicated answer
        return jax.lax.pmax(jnp.where(mine, vals, -1), axis)

    return jax.jit(jax.shard_map(
        local_next_geq, mesh=mesh,
        in_specs=(specs, rep, rep, rep, rep, rep),
        out_specs=rep, check_vma=False))


def make_sharded_next_geq(fi: FlatIndex, mesh: Mesh, axis: str = "data"):
    """Bind one flat index to the shard_map dispatch for
    ``next_geq_batch`` over a ``data`` mesh axis: replicated grammar,
    list-partitioned stream/spans (specs from
    ``distributed.sharding.index_partition_spec``)."""
    num_shards = mesh.shape[axis]
    stacked, shard_of_list, local_lid = shard_flat_index(fi, num_shards)
    # each device holds only its own shard; everything else replicates
    specs = _stacked_specs(mesh)
    stacked = {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
               for k, v in stacked.items()}
    rep = NamedSharding(mesh, P())
    grammar = {k: jax.device_put(getattr(fi, k), rep)
               for k in ("sym_left", "sym_right", "sym_sum", "sym_len")}
    shard_of_list = jax.device_put(shard_of_list, rep)
    local_lid = jax.device_put(local_lid, rep)
    statics = (("num_terminals", fi.num_terminals),
               ("max_depth", fi.max_depth), ("max_scan", fi.max_scan),
               ("universe", fi.universe))
    dispatch = _sharded_dispatch(mesh, axis, statics)

    def call(gids, xs):
        return dispatch(stacked, grammar, shard_of_list, local_lid,
                        gids, xs)

    return call


class DeviceEngine(Engine):
    """Backend-independent device-engine scaffolding.

    ``max_short_len`` is the static expansion cap of the device program:
    pairs (or k-term queries) whose *shortest* list exceeds it route to the
    host fallback engine, exactly like a real serving tier routes outliers.
    ``mesh`` (with a ``data`` axis) switches ``next_geq_batch`` to the
    shard_map dispatch path.
    """

    def __init__(self, res: RePairResult, fi: FlatIndex | None = None,
                 max_short_len: int = 256, B: int = 8,
                 fallback: Engine | None = None,
                 mesh: Mesh | None = None, mesh_axis: str = "data",
                 codec=None, store=None, resident_pages=None,
                 resident=None):
        super().__init__(res, codec=codec, store=store,
                         resident_pages=resident_pages, resident=resident)
        self.fi = fi if fi is not None else build_flat_index(res, B=B)
        self.max_short_len = max_short_len
        self._B = B
        self._fallback = fallback
        self.mesh = mesh
        self._sharded_next_geq = None
        self._bys_incl = None   # [BY04] prefix table, built on first bys
        self._route_host = None  # routing snapshot, set by _attach_store
        self._starts_np = None
        #: work sent to the host fallback by design (pairs and k-term
        #: queries whose shortest list passes ``max_short_len``, whole-list
        #: decodes past ``_DECODE_CAP``), surfaced by the scheduler
        self.host_routes = {"pairs": 0, "multi": 0, "decodes": 0}
        if mesh is not None and mesh_axis in mesh.axis_names:
            self._sharded_next_geq = make_sharded_next_geq(
                self.fi, mesh, mesh_axis)

    # -- out-of-core store attach (DESIGN.md §11) ---------------------------

    def _wants_store(self) -> bool:
        return self.resident is not None or self._store_kind is not None

    def _attach_store(self, pi: PagedIndex) -> PagedIndex:
        """Swap a just-built paged index onto the admission cache: build
        (or adopt) the PageStore from the index's own paged arrays, replace
        the stream leaves with placeholders (``as_store_backed``) so the
        device never holds the full stream, and snapshot the host routing
        tables — the directories/buckets/grammar the paper keeps in RAM.
        Returns ``pi`` unchanged when no store was requested."""
        if not self._wants_store():
            return pi
        if self.resident is None:
            from ..store import (PageStore, ResidentSet, build_page_store)
            if isinstance(self._store_kind, PageStore):
                self.store = self._store_kind
            else:
                self.store = build_page_store(self.res,
                                              kind=self._store_kind, pi=pi)
            self.resident = ResidentSet(self.store,
                                        budget=self._resident_pages)
        else:
            self.store = self.resident.store
        if int(self.store.page_size) != int(pi.page_size):
            raise ValueError(
                "page store geometry mismatch: store page_size "
                f"{self.store.page_size} != index {pi.page_size}")
        # drop the O(N) flat stream as well: paged placeholders via
        # as_store_backed, and the flat mirror's ``c`` shrinks to one
        # element — every resident dispatch path reads the pool, and the
        # store gate poisons these arrays to prove nothing else does
        slim = dataclasses.replace(self.fi, c=jnp.zeros(1, jnp.int32))
        self.fi = slim
        pi = as_store_backed(dataclasses.replace(pi, flat=slim), self.store)
        self._route_host = K.routing_snapshot(pi)
        self._starts_np = np.asarray(self.store.meta["starts"], np.int64)
        return pi

    def _pool(self):
        """The resident pool's device mirror (syms, sums, slot table)."""
        return self.resident.device_tables()

    def _probe_pages(self, lids: np.ndarray, xq: np.ndarray) -> np.ndarray:
        """Working set of a probe round = exactly the pages the router
        would window (shared ``_probe_windows`` math), so a prefault batch
        faults nothing a dispatch wouldn't."""
        if self._sharded_next_geq is not None or self._route_host is None:
            return np.zeros(0, np.int64)   # sharding is its own residency
        return K.probe_working_set(self._route_host, lids, xq)

    @property
    def fallback(self) -> Engine:
        """Host fallback, built lazily on the first outlier route — its
        (b)-sampling duplicates the one inside build_flat_index, so paying
        for it only when a query actually needs it keeps engine
        construction to one sampling pass.  Under a store it shares this
        engine's ResidentSet, so outlier routes hit the same bounded pool
        (one admission cache per index version)."""
        if self._fallback is None:
            self._fallback = HostEngine(self.res, method="lookup",
                                        B=self._B, resident=self.resident)
        return self._fallback

    # -- the one backend-specific primitive --------------------------------

    @abc.abstractmethod
    def _next_geq_dev(self, list_ids, xs):
        """(Q,) ids × (Q,) probes -> (Q,) int32 array.  Takes numpy or
        device arrays; the backend owns any transfer (the pallas backend
        routes pages on the host first, so handing it numpy avoids a
        device round-trip)."""

    @abc.abstractmethod
    def _probe_dev(self, long_ids, xs):
        """(B,) ids × (B, M) probes -> (B, M) int32 array."""

    # -- engine API ---------------------------------------------------------

    #: merged probe rounds are padded up to power-of-two buckets of at
    #: least this many lanes (DESIGN.md §8.2)
    ROUND_BUCKET_MIN = 16

    def _dispatch_codec(self, codec: int, lids: np.ndarray, xq: np.ndarray,
                        algo: str) -> np.ndarray:
        """Merged-round padding convention for the device tier: the
        scheduler concatenates the pending rounds of every in-flight
        query, so each (codec, algo) sub-round's flat size varies tick to
        tick.  Pad up to the next power of two (min ``ROUND_BUCKET_MIN``)
        by repeating the sub-round's first lane — a real (list, probe) of
        THIS codec, so the pad lanes stay inside the codec's own tables —
        and slice the answers back: every jitted probe program (flat,
        paged, shard_map, pallas, ef, bitmap) sees O(log Q) distinct
        shapes instead of one per merged size."""
        n = lids.size
        bucket = max(self.ROUND_BUCKET_MIN, 1 << (n - 1).bit_length())
        if bucket != n:
            lids = np.pad(lids, (0, bucket - n), mode="edge")
            xq = np.pad(xq, (0, bucket - n), mode="edge")
        if self._in_round:
            self.lane_stats["pad_lanes"] += bucket - n
        return np.asarray(super()._dispatch_codec(codec, lids, xq,
                                                  algo))[:n]

    def _next_geq_repair(self, list_ids: np.ndarray,
                         xs: np.ndarray) -> np.ndarray:
        lids = np.asarray(list_ids, np.int32)
        xq = np.asarray(xs, np.int32)
        if self._sharded_next_geq is not None:
            return _pull(self._sharded_next_geq(lids, xq))
        if self.resident is not None:
            return _pull(self._next_geq_resident(lids, xq))
        return _pull(self._next_geq_dev(lids, xq))

    def _next_geq_resident(self, lids: np.ndarray,
                           xq: np.ndarray) -> np.ndarray:
        """Resident-pool probe: fault the round's working set (a no-op
        when the scheduler already prefaulted it), then run the
        slot-indexed paged mirror against the bounded pool."""
        self.resident.ensure(K.probe_working_set(self._route_host,
                                                 lids, xq))
        ps, pu, st = self._pool()
        return J.next_geq_batch_resident(
            self.pi, ps, pu, st, jnp.asarray(lids, jnp.int32),
            jnp.asarray(xq, jnp.int32))

    def _next_geq_repair_bys(self, list_ids: np.ndarray,
                             xs: np.ndarray) -> np.ndarray:
        """Device binary-search path: bisect the span's phrase-sum prefix
        table, then one grammar descent (``jnp_backend.next_geq_bys_batch``).
        Replicated (never shard_map-dispatched): the prefix table is an
        index-global auxiliary array — the EF and bitmap stores follow
        the same replication rule (DESIGN.md §10.3).  Out of core it
        delegates to the resident probe path: the [BY04] prefix table is
        another O(N) full-stream array, which is exactly what the bounded
        pool exists to avoid, and the next_geq contract is identical."""
        if self.resident is not None:
            return self._next_geq_repair(list_ids, xs)
        if self._bys_incl is None:
            self._bys_incl = J.build_bys_table(self.fi)
        return _pull(J.next_geq_bys_batch(
            self.fi, self._bys_incl, jnp.asarray(list_ids, jnp.int32),
            jnp.asarray(xs, jnp.int32)))

    # -- codec-tier device paths (DESIGN.md §10.3) ---------------------------

    def _build_ef_pack(self) -> dict:
        from ..core import ef as EF
        rank = self.tier.ef.select_samples()
        return {"samples": rank,
                "dev": EF.ef_device_pack(self.tier.ef, rank)}

    def _ef_next_geq(self, lids, xq) -> np.ndarray:
        from ..core import ef as EF
        return _pull(EF.ef_next_geq_jnp(self._ef_pack()["dev"], lids, xq))

    def _bm_pack(self):
        key = (self.index_version, "bm")
        pack = self._ef_sel.get(key)
        if pack is None:
            from ..index import codec_tier as CT
            pack = CT.bitmap_device_pack(self.tier.bm)
            self._ef_sel.put(key, pack)
        return pack

    def _bitmap_next_geq(self, lids, xq) -> np.ndarray:
        from ..index import codec_tier as CT
        return _pull(CT.bitmap_next_geq_jnp(self._bm_pack(), lids, xq))

    def _probe_tiered(self, long_ids, mat):
        """(B,) ids × (B, M) probes with per-list codec routing: repair
        batches keep the backend's 2-D ``_probe_dev`` fast path; with a
        tier the lanes flatten through ``next_geq_batch`` so EF/bitmap
        lists probe their own stores (results are identical either way —
        the repair structures stay ground truth).  The resident path
        flattens too: the probe rounds reuse the one slot-indexed
        program instead of growing a second 2-D resident mirror."""
        if self.tier is None and self.resident is None:
            return self._probe_dev(long_ids, mat)
        B, M = np.shape(mat)
        flat_ids = np.repeat(np.asarray(long_ids, np.int32), M)
        vals = self.next_geq_batch(flat_ids,
                                   np.asarray(mat, np.int32).reshape(-1))
        return np.asarray(vals).reshape(B, M)

    #: device expansion cap for whole-list decode; beyond it the host
    #: reference decodes (one-off outliers, same routing idea as
    #: ``max_short_len``)
    _DECODE_CAP = 8192

    def _expand(self, ids, max_len: int) -> jax.Array:
        """Batched list expansion, routed through the resident pool when a
        store is attached.  ``max_len`` bounds the symbol window read per
        list, so only pages covering ``[starts[i], starts[i] + max_len)``
        (clipped to the span) are faulted."""
        if self.resident is None:
            return J.expand_batch(self.fi, jnp.asarray(ids, jnp.int32),
                                  max_len)
        from ..store import pages_in_spans
        idx = np.asarray(ids, np.int64).ravel()
        lo = self._starts_np[idx]
        hi = np.minimum(self._starts_np[idx + 1], lo + max_len)
        self.resident.ensure(pages_in_spans(lo, hi,
                                            int(self.pi.page_size)))
        ps, pu, st = self._pool()
        return J.expand_batch_resident(self.pi, ps, pu, st,
                                       jnp.asarray(idx, jnp.int32), max_len)

    def _decode_list(self, i: int) -> np.ndarray:
        """Whole-list decode via the device positional-descent expansion.
        The static ``max_len`` is the length rounded up to a power of two,
        so jit entries stay O(log max-length) rather than one per length."""
        n = int(self.lengths[i])
        if n > self._DECODE_CAP:
            self.host_routes["decodes"] += 1
            return super()._decode_list(i)
        bucket = max(16, 1 << (max(1, n - 1)).bit_length())
        row = self._expand([i], bucket)
        return self.compact(_pull(row[0]))

    def intersect_pairs(self, pairs: Sequence[tuple[int, int]]
                        ) -> list[np.ndarray]:
        if not len(pairs):
            return []
        arr = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
        plen = self.lengths[arr]
        swap = plen[:, 0] > plen[:, 1]  # strict: ties keep request order
        shorts = np.where(swap, arr[:, 1], arr[:, 0])
        longs = np.where(swap, arr[:, 0], arr[:, 1])
        to_host = self.lengths[shorts] > self.max_short_len
        out: list[np.ndarray | None] = [None] * arr.shape[0]
        dev = np.flatnonzero(~to_host)
        if dev.size:
            mat = self._expand(shorts[dev], self.max_short_len)
            vals = self._probe_tiered(jnp.asarray(longs[dev], jnp.int32),
                                      mat)
            kept = _pull(J.match_mask(vals, mat))
            for qi, row in zip(dev, kept):
                out[qi] = self.compact(row)
        host = np.flatnonzero(to_host)
        if host.size:                   # outlier route: host svs, one batch
            self.host_routes["pairs"] += int(host.size)
            host_outs = self.fallback.intersect_pairs(
                list(zip(shorts[host].tolist(), longs[host].tolist())))
            for qi, o in zip(host, host_outs):
                out[qi] = o
        return out  # type: ignore[return-value]

    def intersect_multi(self, idxs: Sequence[int]) -> np.ndarray:
        """Device-side pairwise svs, shortest-first by uncompressed length
        (§3.3): expand the shortest list once, then thin the candidate row
        through every longer list with batched next_geq probes.  The row
        keeps its (1, max_short_len) shape throughout, so all k-1 probe
        rounds hit one jit cache entry."""
        order = self.order_by_length(idxs)
        if not order:
            return np.empty(0, dtype=np.int64)
        if self.lengths[order[0]] > self.max_short_len:
            self.host_routes["multi"] += 1
            return self.fallback.intersect_multi(idxs)
        cand = self._expand(order[:1], self.max_short_len)  # (1, M)
        for i in order[1:]:
            vals = self._probe_tiered(jnp.asarray([i], jnp.int32), cand)
            cand = J.match_mask(vals, cand)
        return self.compact(_pull(cand[0]))

    # -- ranked scoring (DESIGN.md §9) --------------------------------------

    def _score_page_size(self) -> int:
        """Cut the score directory at THIS engine's page boundaries by
        default: a paged engine scores by the pages its probe kernels DMA
        by.  (The windowed decode itself is geometry-agnostic — an
        explicit ``score_page_size`` override wins; the fused Pallas
        page-score kernel needs directory pages that divide its stream
        pages and refuses any other cut.)"""
        if self.score_page_size is not None:
            return int(self.score_page_size)
        pi = getattr(self, "pi", None)
        return int(pi.page_size) if pi is not None else DEFAULT_PAGE

    #: ScoreRound rows carry whole decoded pages, so their bucket floor is
    #: lower than the probe lanes' — a serial query's chunk fits in one
    SCORE_BUCKET_MIN = 8

    def _dispatch_score_unique(self, entries: np.ndarray) -> np.ndarray:
        """Merged ScoreRound (post-dedup) with the same power-of-two
        bucket convention as ``dispatch_round``: pad the entry lanes with
        the directory's cheapest entry (fewest elements — its decode is
        real but its guarded tiles all no-op), slice the rows back."""
        e = np.asarray(entries, np.int32).ravel()
        n = e.size
        bucket = max(self.SCORE_BUCKET_MIN, 1 << (n - 1).bit_length())
        if bucket != n:
            pad_id = int(np.argmin(self.score_index.pg_count))
            e = np.pad(e, (0, bucket - n), constant_values=pad_id)
            if self._in_round:
                self.lane_stats["pad_lanes"] += bucket - n
        return self.decode_page_batch(e)[:n]

    def decode_page_batch(self, entries: np.ndarray) -> np.ndarray:
        """Device page-entry decode: gather each entry's (symbol range,
        base, head) row from the directory and run the windowed positional
        descent (``jnp_backend.decode_pages_batch``) — O(page) work per
        lane regardless of list length, the block-max pruning payoff."""
        si = self.score_index
        e = np.asarray(entries, np.int64).ravel()
        if self.resident is not None:
            from ..store import pages_in_spans
            self.resident.ensure(pages_in_spans(
                np.asarray(si.pg_sym_lo[e], np.int64),
                np.asarray(si.pg_sym_hi[e], np.int64),
                int(self.pi.page_size)))
            ps, pu, st = self._pool()
            out = J.decode_pages_resident(
                self.pi, ps, pu, st,
                jnp.asarray(si.pg_sym_lo[e], jnp.int32),
                jnp.asarray(si.pg_sym_hi[e], jnp.int32),
                jnp.asarray(si.pg_base[e], jnp.int32),
                jnp.asarray(si.pg_head[e], jnp.int32),
                win=int(si.page_size), max_elems=self.page_elem_bucket())
            return _pull(out)
        out = J.decode_pages_batch(
            self.fi,
            jnp.asarray(si.pg_sym_lo[e], jnp.int32),
            jnp.asarray(si.pg_sym_hi[e], jnp.int32),
            jnp.asarray(si.pg_base[e], jnp.int32),
            jnp.asarray(si.pg_head[e], jnp.int32),
            win=int(si.page_size), max_elems=self.page_elem_bucket())
        return _pull(out)

    def score_batch(self, doc_ids: np.ndarray, terms) -> np.ndarray:
        """Device-side score accumulation: the membership probes ride the
        batched next_geq path (sharded dispatch included), the float32
        reduction runs on device (``accumulate_scores_device`` — a
        sequential segment-sum over the decoded membership matrix in the
        same fixed term order as the host reference, so the scores are
        bit-identical)."""
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
        out = J.accumulate_scores_device(
            jnp.asarray(si.idf[ts], jnp.float32),
            jnp.asarray(si.doc_w[docs], jnp.float32),
            jnp.asarray(member))
        return _pull(out)


class JnpEngine(DeviceEngine):
    """Fixed-trip-count vmapped jnp programs (the kernel's bit-exact
    reference).  ``paged=True`` routes probes through the paged-addressing
    mirror over a :class:`PagedIndex` — same values, page-local reads."""

    name = "jnp"

    def __init__(self, res: RePairResult, fi: FlatIndex | None = None,
                 max_short_len: int = 256, B: int = 8,
                 fallback: Engine | None = None, paged: bool = False,
                 page_size: int = DEFAULT_PAGE,
                 pi: PagedIndex | None = None, **kwargs):
        super().__init__(res, fi=fi, max_short_len=max_short_len, B=B,
                         fallback=fallback, **kwargs)
        # a store implies paged addressing: the admission cache's unit IS
        # the stream page, so the flat mirror has no out-of-core form
        self.pi = pi if pi is not None else (
            build_paged_index(self.fi, page_size)
            if (paged or self._wants_store()) else None)
        if self.pi is not None:
            self.pi = self._attach_store(self.pi)

    def _next_geq_dev(self, list_ids: jax.Array, xs: jax.Array) -> jax.Array:
        if self.pi is not None:
            return J.next_geq_batch_paged(self.pi, list_ids, xs)
        return J.next_geq_batch(self.fi, list_ids, xs)

    def _probe_dev(self, long_ids: jax.Array, xs: jax.Array) -> jax.Array:
        if self.pi is not None:
            return J.probe_batch_paged(self.pi, long_ids, xs)
        return J.probe_batch(self.fi, long_ids, xs)
