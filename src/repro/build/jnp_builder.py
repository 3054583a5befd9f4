"""JnpBuilder: fixed-shape, jit-able Re-Pair rounds on device (DESIGN.md §3).

The host loop's data-dependent steps become fixed-shape device programs
over a padded buffer of static length ``Np`` and rule tables of static
budget ``Rb`` (doubled + re-jitted when a build outgrows them — the
"static symbol budget" trick, §3.2).  Three design decisions carry the
throughput:

* **hole semantics, no per-round compaction** — a replaced right symbol
  is not sliced out (data-dependent shape) nor shuffled out (a sort per
  round); its slot just goes dead in a ``live`` mask.  Logical adjacency
  is the *next-live chain* (a reversed ``cummin`` of live positions), so
  pair slots, greedy-overlap runs, and partner invalidation are all
  gathers and scans — O(Np) with small constants, no sort, no scatter.
  Separators stay live-but-not-real forever: they occupy a chain slot
  (breaking adjacency across lists, §3.1) but can never match a pair.
* **packed single-key sort histogram** — pair ``(a, b)`` packs into one
  int32 key ``a * S + b`` (``S = T + Rb``; the builder refuses symbol
  spaces past ``sqrt(2^31)`` rather than overflow).  One 1-operand sort
  groups identical pairs into runs; run lengths (a reversed ``cummin``
  over run starts) are exact counts.  Multi-operand comparator sorts —
  an order of magnitude slower on every backend — appear nowhere on the
  fast path.
* **top-K ranked table** — ranking only ever feeds the greedy
  disjoint-pair scan, which examines a few multiples of
  ``pairs_per_round`` entries, so the full-length rank sort is replaced
  by a gather-compaction of the good runs into a static ``RANK_K`` table
  and a tiny lexicographic sort by (count desc, left asc, right asc) —
  the exact ``np.unique`` + stable-argsort tie-break of the host.  The
  rare round where more than RANK_K distinct pairs survive the filters
  AND the table runs dry before ``take`` pairs are chosen is re-run on
  the full-length exact variant (same arithmetic, full-size sort), so
  parity is unconditional.

``build_grammar`` runs the fused jitted round in a host loop that reads
back four control scalars per round — no per-list or per-array host
roundtrips; the grammar and compacted stream cross the boundary exactly
once, at finalize.  Everything is int32 (the same value domain as
:class:`FlatIndex`).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..core.repair import Grammar, RePairResult, lists_to_gap_stream
from .base import Builder

I32 = jnp.int32
BIG = 2**31 - 1      # sentinel key: sorts past every real packed pair
MAX_PACK = 46340     # floor(sqrt(2^31)): largest symbol space that packs
RANK_K = 4096        # static ranked-table size of the fast path


class DeviceBuildState(NamedTuple):
    """The whole working set of a device build — a pytree of int32/bool
    arrays with static shapes (Np,) / (Rb,) plus one live scalar."""

    seq: jax.Array        # (Np,) symbol per slot (garbage where dead)
    live: jax.Array       # (Np,) slot occupies a position in the logical
    #                       sequence (real symbols AND separators)
    real: jax.Array       # (Np,) live and not a separator
    rule_l: jax.Array     # (Rb,) left child of rule i
    rule_r: jax.Array     # (Rb,)
    rule_sum: jax.Array   # (Rb,) phrase sums
    rule_len: jax.Array   # (Rb,) expanded lengths
    rule_depth: jax.Array  # (Rb,) parse-tree depths
    num_rules: jax.Array  # ()


# -- chain + pair-stream helpers ---------------------------------------------

def _next_live(live: jax.Array) -> jax.Array:
    """nl[i] = smallest live j > i (Np when none): reversed cummin."""
    Np = live.shape[0]
    idx = jnp.arange(Np, dtype=I32)
    at = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(live, idx, Np))))
    return jnp.concatenate([at[1:], jnp.full((1,), Np, I32)])


def _prev_live(live: jax.Array) -> jax.Array:
    """pl[i] = largest live j < i (-1 when none): cummax."""
    idx = jnp.arange(live.shape[0], dtype=I32)
    at = jax.lax.cummax(jnp.where(live, idx, -1))
    return jnp.concatenate([jnp.full((1,), -1, I32), at[:-1]])


def _pair_streams(seq, live, real, *, S):
    """Per-slot adjacent pair of the LOGICAL sequence: left symbol, right
    symbol (through the next-live chain), validity, and the packed key
    ``a * S + b`` (BIG where invalid)."""
    Np = seq.shape[0]
    nl = _next_live(live)
    nlc = jnp.minimum(nl, Np - 1)
    pb = seq[nlc]
    vp = real & (nl < Np) & real[nlc]
    packed = jnp.where(vp, seq * S + pb, BIG)
    return pb, vp, packed


# -- counting + ranking ------------------------------------------------------

def _runs_of_sorted(ks):
    """Distinct-pair runs of the sorted key array: (run-start mask, exact
    occurrence count at each run start, total valid pairs)."""
    Np = ks.shape[0]
    idx = jnp.arange(Np, dtype=I32)
    valid = ks != BIG
    prev = jnp.concatenate([jnp.full((1,), -1, I32), ks[:-1]])
    rs = valid & (ks != prev)
    nxt = jnp.flip(jax.lax.cummin(jnp.flip(jnp.where(rs, idx, Np))))
    nxt_after = jnp.concatenate([nxt[1:], jnp.full((1,), Np, I32)])
    total = valid.sum().astype(I32)
    count = jnp.minimum(nxt_after, total) - idx
    return rs, count, total


def _cap_kept(ks, packed, rs, *, cap):
    """[CN07] early-pairs filter: keep the ``cap`` distinct pairs whose
    first occurrence in the sequence comes earliest.  First occurrences
    are a scatter-min into each run's start slot."""
    Np = ks.shape[0]
    idx = jnp.arange(Np, dtype=I32)
    slot = jnp.searchsorted(ks, packed).astype(I32)
    slot = jnp.where(packed != BIG, slot, Np)
    fo = jnp.full(Np, BIG, I32).at[slot].min(idx, mode="drop")
    thresh = jnp.sort(jnp.where(rs, fo, BIG))[min(cap - 1, Np - 1)]
    return rs & (fo <= thresh)


def _rank_good(ks, count, good, *, S, K):
    """Gather the good runs into a K-slot table and rank it by
    (count desc, left asc, right asc) — the host's exact tie-break.
    ``K=None`` ranks at full length (the exact fallback).  Returns
    (neg_key, left, right, count) ranked arrays + n_good.

    When more than K runs are good, the table holds EXACTLY the top K of
    the host order: every run above the K-th-largest count, plus ties at
    the threshold broken by smallest packed key (ks order IS packed
    ascending) — so the ranked table is a true prefix of the host's
    ranking, and the caller only needs the exact fallback when the
    greedy scan runs the whole table dry."""
    Np = ks.shape[0]
    n_good = good.sum().astype(I32)
    if K is None:
        neg = jnp.where(good, -count, BIG)
        a = jnp.where(good, ks // S, BIG)
        b = jnp.where(good, ks % S, BIG)
        return (*jax.lax.sort((neg, a, b, count), num_keys=3), n_good)
    thresh = jnp.sort(jnp.where(good, count, -1))[max(Np - K, 0)]
    strict = good & (count > thresh)
    ties = good & (count == thresh)
    room = K - strict.sum().astype(I32)
    keep = strict | (ties & (jnp.cumsum(ties.astype(I32)) <= room))
    csum = jnp.cumsum(keep.astype(I32))
    src = jnp.searchsorted(csum, jnp.arange(1, K + 1, dtype=I32)).astype(I32)
    on = jnp.arange(K, dtype=I32) < csum[Np - 1]
    srcc = jnp.minimum(src, Np - 1)
    kk = jnp.where(on, ks[srcc], BIG)
    cc = jnp.where(on, count[srcc], 0)
    neg = jnp.where(on, -cc, BIG)
    a = jnp.where(on, kk // S, BIG)
    b = jnp.where(on, kk % S, BIG)
    return (*jax.lax.sort((neg, a, b, cc), num_keys=3), n_good)


def _count_ranked(packed, pa, pb, vp, *, S, cap, min_count, K):
    """Ranked pair histogram via the packed single-key sort.  Returns
    (neg, left, right, count, n_good, n_runs)."""
    ks = jnp.sort(packed)
    rs, count, _ = _runs_of_sorted(ks)
    n_runs = rs.sum().astype(I32)
    kept = _cap_kept(ks, packed, rs, cap=cap) if cap > 0 else rs
    good = kept & (count >= min_count)
    neg, a, b, c, n_good = _rank_good(ks, count, good, S=S, K=K)
    return neg, a, b, c, n_good, n_runs


# -- selection + replacement -------------------------------------------------

def _select_disjoint(neg, ra, rb, take, *, S, P):
    """Host-greedy disjoint top-k: walk the ranked pairs, skip any pair
    sharing a symbol with an earlier choice, stop at ``take`` chosen.
    ``S`` sizes the used-symbol bitmap."""
    K = ra.shape[0]

    def cond(st):
        j, cnt, _, _, _ = st
        return (j < K) & (cnt < take) & (neg[jnp.minimum(j, K - 1)] != BIG)

    def body(st):
        j, cnt, used, ch_l, ch_r = st
        l, r = ra[j], rb[j]
        ok = ~used[l] & ~used[r]
        used = jnp.where(ok, used.at[l].set(True).at[r].set(True), used)
        ch_l = jnp.where(ok, ch_l.at[cnt].set(l), ch_l)
        ch_r = jnp.where(ok, ch_r.at[cnt].set(r), ch_r)
        return j + 1, cnt + ok.astype(I32), used, ch_l, ch_r

    init = (jnp.int32(0), jnp.int32(0), jnp.zeros((S,), bool),
            jnp.full((P,), -1, I32), jnp.full((P,), -1, I32))
    _, n_chosen, _, ch_l, ch_r = jax.lax.while_loop(cond, body, init)
    return ch_l, ch_r, n_chosen


def _match_chosen(packed, ch_l, ch_r, n_chosen, *, S):
    """cand[i] = slot i's pair is one of the chosen; kidx[i] = which one.
    A searchsorted against the tiny sorted chosen-key table — pairs are
    symbol-disjoint, so each slot matches at most one."""
    P = ch_l.shape[0]
    kmask = jnp.arange(P, dtype=I32) < n_chosen
    ckey = jnp.where(kmask, ch_l * S + ch_r, BIG)
    sp, sk = jax.lax.sort((ckey, jnp.arange(P, dtype=I32)), num_keys=1)
    pos = jnp.minimum(jnp.searchsorted(sp, packed).astype(I32), P - 1)
    cand = (packed != BIG) & (sp[pos] == packed)
    return cand, sk[pos]


def _take_parity(cand, live):
    """Greedy left-to-right == take even offsets within each run of
    chain-consecutive candidates; offsets counted in LIVE positions, so
    dead holes never split a run the host would see as contiguous."""
    Np = cand.shape[0]
    idx = jnp.arange(Np, dtype=I32)
    pl = _prev_live(live)
    cand_prev = cand[jnp.maximum(pl, 0)] & (pl >= 0)
    chain_start = cand & ~cand_prev
    start_pos = jnp.maximum(jax.lax.cummax(
        jnp.where(chain_start, idx, -1)), 0)
    livec = jnp.cumsum(live.astype(I32))
    offset = livec - livec[start_pos]
    return cand & (offset % 2 == 0), pl


def _apply_replace(state: DeviceBuildState, packed, ch_l, ch_r, n_chosen,
                   *, S, T):
    """Rewrite every taken slot to its new symbol and deaden its partner
    (the next-live slot) — pure elementwise ops and gathers."""
    cand, kidx = _match_chosen(packed, ch_l, ch_r, n_chosen, S=S)
    taken, pl = _take_parity(cand, state.live)
    new_id = T + state.num_rules + kidx
    seq = jnp.where(taken, new_id, state.seq)
    dead = taken[jnp.maximum(pl, 0)] & (pl >= 0)
    return state._replace(seq=seq, live=state.live & ~dead,
                          real=state.real & ~dead), taken, kidx


def _register_rules(state: DeviceBuildState, ch_l, ch_r, n_chosen, *, T):
    """Scatter the chosen pairs into the rule tables at slots
    ``num_rules + k`` with their phrase sums / lengths / depths."""
    Rb = state.rule_l.shape[0]
    P = ch_l.shape[0]
    k = jnp.arange(P, dtype=I32)
    on = k < n_chosen
    slot = jnp.where(on, state.num_rules + k, Rb)   # Rb -> dropped

    def look(tab, term_val, s):
        ridx = jnp.clip(s - T, 0, Rb - 1)
        return jnp.where(s < T, term_val, tab[ridx])

    s_l = look(state.rule_sum, ch_l, ch_l)
    s_r = look(state.rule_sum, ch_r, ch_r)
    n_l = look(state.rule_len, jnp.ones_like(ch_l), ch_l)
    n_r = look(state.rule_len, jnp.ones_like(ch_r), ch_r)
    d_l = look(state.rule_depth, jnp.zeros_like(ch_l), ch_l)
    d_r = look(state.rule_depth, jnp.zeros_like(ch_r), ch_r)

    def put(tab, vals):
        return tab.at[slot].set(vals, mode="drop")

    return state._replace(
        rule_l=put(state.rule_l, ch_l),
        rule_r=put(state.rule_r, ch_r),
        rule_sum=put(state.rule_sum, s_l + s_r),
        rule_len=put(state.rule_len, n_l + n_r),
        rule_depth=put(state.rule_depth, 1 + jnp.maximum(d_l, d_r)),
        num_rules=state.num_rules + n_chosen,
    )


@partial(jax.jit,
         static_argnames=("T", "cap", "min_count", "P", "K", "counts_fn"))
def _device_round(state: DeviceBuildState, take, *, T, cap, min_count, P,
                  K, counts_fn=_count_ranked):
    """One fused Re-Pair round: histogram -> greedy top-k -> replacement
    -> rule registration.  Control scalars leave the device as ONE
    stacked array (n_chosen, kept_any, n_good, n_runs, n_live) — a
    single host sync per round.  ``K=None`` is the exact
    full-length-rank variant (the fallback for rounds whose good-pair
    table overflows RANK_K mid-greedy)."""
    Rb = state.rule_l.shape[0]
    S = T + Rb
    pb, vp, packed = _pair_streams(state.seq, state.live, state.real, S=S)
    neg, ra, rb, rc, n_good, n_runs = counts_fn(
        packed, state.seq, pb, vp, S=S, cap=cap, min_count=min_count, K=K)
    take = jnp.minimum(take, n_good)
    ch_l, ch_r, n_chosen = _select_disjoint(neg, ra, rb, take, S=S, P=P)
    state, taken, _ = _apply_replace(state, packed, ch_l, ch_r, n_chosen,
                                     S=S, T=T)
    state = _register_rules(state, ch_l, ch_r, n_chosen, T=T)
    scalars = jnp.stack([n_chosen, taken.any().astype(I32), n_good,
                         n_runs, state.live.sum().astype(I32)])
    return state, scalars


@partial(jax.jit, static_argnames=("new_np",))
def _compact_to(state: DeviceBuildState, *, new_np: int
                ) -> DeviceBuildState:
    """Shrink the working buffer: gather the live slots (symbols AND
    separators, order preserved) into a fresh ``new_np``-slot buffer.
    Holes accumulate as rounds replace pairs; once fewer than half the
    slots are live, re-bucketing keeps every subsequent round's cost
    proportional to the CURRENT stream, not the original one (the same
    effect the host loop gets from physically compacting each round,
    paid O(log) times instead of every round)."""
    Np = state.seq.shape[0]
    csum = jnp.cumsum(state.live.astype(I32))
    n_live = csum[Np - 1]
    src = jnp.searchsorted(csum, jnp.arange(1, new_np + 1, dtype=I32)
                           ).astype(I32)
    srcc = jnp.minimum(src, Np - 1)
    on = jnp.arange(new_np, dtype=I32) < n_live
    return state._replace(seq=jnp.where(on, state.seq[srcc], 0),
                          live=on, real=on & state.real[srcc])


@partial(jax.jit, static_argnames=("L",))
def _finalize(seq, live, real, *, L):
    """Strip separators and dead holes on device: per-list span ends +
    the compacted symbol stream (sliced on the host after the single
    transfer)."""
    Np = seq.shape[0]
    idx = jnp.arange(Np, dtype=I32)
    acum = jnp.cumsum(real.astype(I32))
    sep = live & ~real
    srank = jnp.cumsum(sep.astype(I32))            # 1-based at separators
    ends = jnp.zeros((L + 1,), I32).at[
        jnp.where(sep, srank - 1, L)].set(acum, mode="drop")[:L]
    perm = jnp.argsort(jnp.where(real, idx, Np + idx))
    return seq[perm], ends, acum[Np - 1]


class JnpBuilder(Builder):
    """Device Re-Pair construction with pure-jnp rounds (the bit-exact
    reference the pair_count kernel is checked against)."""

    name = "jnp"
    _counts_fn = staticmethod(_count_ranked)

    # -- state construction --------------------------------------------------

    def init_state(self, lists: Sequence[np.ndarray]
                   ) -> tuple[DeviceBuildState, dict]:
        stream, firsts, lens, universe = lists_to_gap_stream(lists)
        sep = stream == -1
        max_gap = int(stream[~sep].max(initial=0))
        T = max_gap + 1
        n0 = stream.size
        Np = max(128, -(-n0 // 128) * 128)
        Rb = max(1, self.config.budget)
        self._check_pack(T, Rb)
        state = DeviceBuildState(
            seq=jnp.zeros(Np, I32).at[:n0].set(
                jnp.asarray(np.where(sep, 0, stream), I32)),
            live=jnp.zeros(Np, bool).at[:n0].set(True),
            real=jnp.zeros(Np, bool).at[:n0].set(jnp.asarray(~sep)),
            rule_l=jnp.zeros(Rb, I32), rule_r=jnp.zeros(Rb, I32),
            rule_sum=jnp.zeros(Rb, I32), rule_len=jnp.zeros(Rb, I32),
            rule_depth=jnp.zeros(Rb, I32), num_rules=jnp.int32(0))
        meta = dict(T=T, firsts=firsts, lens=lens, universe=universe,
                    L=len(lists))
        return state, meta

    @staticmethod
    def _check_pack(T: int, Rb: int) -> None:
        if T + Rb > MAX_PACK:
            raise ValueError(
                f"symbol space T+Rb = {T + Rb} exceeds {MAX_PACK} "
                f"(int32 pair packing); lower rule_budget or use the "
                f"host builder for this corpus")

    def _grow(self, state: DeviceBuildState, T: int) -> DeviceBuildState:
        """Double the static rule budget (re-jits the round once)."""
        Rb = state.rule_l.shape[0]
        self._check_pack(T, 2 * Rb)
        pad = lambda a: jnp.zeros(2 * Rb, I32).at[:Rb].set(a)
        return state._replace(
            rule_l=pad(state.rule_l), rule_r=pad(state.rule_r),
            rule_sum=pad(state.rule_sum), rule_len=pad(state.rule_len),
            rule_depth=pad(state.rule_depth))

    # -- round-level API (numpy boundary, for cross-backend diffing) ---------

    @staticmethod
    def _pack_space(state: DeviceBuildState, T: int,
                    top_id: int = 0) -> int:
        """Packing base for the round-level API: wide enough for the
        budget, every symbol already in the sequence, and any explicit
        id block the caller hands replace_round — callers are free to
        use ids beyond the current static budget."""
        s_max = int(jnp.max(jnp.where(state.real, state.seq, 0)))
        S = max(T + state.rule_l.shape[0], s_max + 1, top_id + 1)
        if S > MAX_PACK:
            raise ValueError(f"symbol space {S} exceeds {MAX_PACK}")
        return S

    def count_pairs(self, state_meta) -> tuple[np.ndarray, np.ndarray]:
        state, meta = state_meta
        cfg = self.config
        S = self._pack_space(state, meta["T"])
        _, _, packed = _pair_streams(state.seq, state.live, state.real,
                                     S=S)
        neg, ra, rb, rc, n_good, _ = _count_ranked(
            packed, None, None, None, S=S, cap=cfg.table_cap,
            min_count=cfg.min_count, K=None)
        g = int(n_good)
        pairs = np.stack([np.asarray(ra[:g]), np.asarray(rb[:g])],
                         axis=1).astype(np.int64)
        return pairs, np.asarray(rc[:g]).astype(np.int64)

    def replace_round(self, state_meta, pairs, new_ids):
        state, meta = state_meta
        pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
        new_ids = np.asarray(new_ids, dtype=np.int64)
        if pairs.shape[0] > 1 and not (np.diff(new_ids) == 1).all():
            raise ValueError("device replace_round needs contiguous ids")
        P = max(1, self.config.pairs_per_round, pairs.shape[0])
        ch = np.full((2, P), -1, np.int64)
        ch[0, :pairs.shape[0]] = pairs[:, 0]
        ch[1, :pairs.shape[0]] = pairs[:, 1]
        T = meta["T"]
        first = int(new_ids[0]) if new_ids.size else T
        S = self._pack_space(state, T, top_id=first + pairs.shape[0])
        _, vp, packed = _pair_streams(state.seq, state.live, state.real,
                                      S=S)
        # align the new-id arithmetic of _apply_replace (T + num_rules
        # + kidx) with the caller's explicit id block
        tmp = state._replace(num_rules=jnp.int32(first - T))
        new_state, taken, kidx = _apply_replace(
            tmp, packed, jnp.asarray(ch[0], I32), jnp.asarray(ch[1], I32),
            jnp.int32(pairs.shape[0]), S=S, T=T)
        new_state = new_state._replace(num_rules=state.num_rules)
        tk = np.asarray(taken)
        ki = np.asarray(kidx)
        counts = np.bincount(ki[tk], minlength=P)[:pairs.shape[0]]
        return (new_state, meta), counts.astype(np.int64)

    # -- fused build ---------------------------------------------------------

    def _check_round(self, n_runs: int) -> None:
        """Hook for backends whose candidate table is budget-bounded."""

    def _round(self, state: DeviceBuildState, take: int, *, T: int, P: int,
               K: int | None):
        """Launch one fused device round; returns ``(state, scalars)``."""
        cfg = self.config
        return _device_round(state, jnp.int32(take), T=T,
                             cap=cfg.table_cap, min_count=cfg.min_count,
                             P=P, K=K, counts_fn=self._counts_fn)

    def build_grammar(self, lists: Sequence[np.ndarray]) -> RePairResult:
        cfg = self.config
        state, meta = self.init_state(lists)
        T, L = meta["T"], meta["L"]
        P = max(1, cfg.pairs_per_round)
        num_rules = 0
        while True:
            if cfg.max_rules is not None and num_rules >= cfg.max_rules:
                break
            take = P
            if cfg.max_rules is not None:
                take = min(take, cfg.max_rules - num_rules)
            while num_rules + take > state.rule_l.shape[0]:
                state = self._grow(state, T)
            new_state, scalars = self._round(state, take, T=T, P=P,
                                             K=self._rank_k())
            n_chosen, kept_any, n_good, n_runs, n_live = map(
                int, np.asarray(scalars))
            if (self._rank_k() is not None and n_good > self._rank_k()
                    and n_chosen < min(take, n_good)):
                # ranked table ran dry mid-greedy: redo this round on the
                # exact full-length variant (rare; parity-critical)
                new_state, scalars = self._round(state, take, T=T, P=P,
                                                 K=None)
                n_chosen, kept_any, n_good, n_runs, n_live = map(
                    int, np.asarray(scalars))
            state = new_state
            num_rules += n_chosen
            self._check_round(n_runs)
            if not n_good:
                break
            if not kept_any:
                break
            # re-bucket once fewer than half the slots are live, so the
            # long tail of small rounds runs on small buffers
            Np = state.seq.shape[0]
            if Np > 128 and n_live <= Np // 2:
                state = _compact_to(
                    state, new_np=max(128, -(-n_live // 128) * 128))

        out_seq, ends, n_active = _finalize(state.seq, state.live,
                                            state.real, L=L)
        R = num_rules
        rules = np.stack([np.asarray(state.rule_l[:R]),
                          np.asarray(state.rule_r[:R])],
                         axis=1).astype(np.int64)
        grammar = Grammar(
            num_terminals=T,
            rules=rules.reshape(-1, 2),
            sums=np.asarray(state.rule_sum[:R]).astype(np.int64),
            lengths=np.asarray(state.rule_len[:R]).astype(np.int64),
            depths=np.asarray(state.rule_depth[:R]).astype(np.int32),
        )
        starts = np.concatenate([[0], np.asarray(ends)]).astype(np.int64)
        return RePairResult(
            grammar=grammar,
            seq=np.asarray(out_seq)[:int(n_active)].astype(np.int64),
            starts=starts,
            first_values=meta["firsts"],
            orig_lengths=meta["lens"],
            universe=meta["universe"],
        )

    def _rank_k(self) -> int | None:
        """Static ranked-table size; None = always exact full length."""
        return RANK_K
