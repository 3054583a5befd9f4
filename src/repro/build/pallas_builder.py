"""PallasBuilder: the jnp round structure with the histogram on the
``kernels/pair_count`` grid kernel (DESIGN.md §3.3).

Only the counting stage differs from :class:`JnpBuilder`:

1. one single-key sort of the packed pair stream still identifies the
   DISTINCT pairs (that is what defines the candidate set — there is no
   way around grouping the stream once per round), but their occurrence
   counts are not taken from run lengths;
2. the candidates are compacted into a **static table** of ``Kp`` slots —
   the first ``table_cap`` distinct pairs by first occurrence (the
   host's [CN07] early-pairs policy verbatim), or all of them when
   uncapped;
3. the kernel does the counting work — a tiled ``(TILE_K, TILE_N)``
   compare-and-accumulate sweep of the pair stream, VMEM-resident per
   instance, the construction twin of ``list_intersect``'s paging
   discipline;
4. ranking/selection/replacement are shared with JnpBuilder, so the
   grammar is bit-identical to both other backends.

The static table is the one approximation surface: with
``table_cap == 0`` the build is exact only while the number of distinct
pairs fits ``config.pair_table``; the per-round ``n_runs`` scalar guards
this and the builder raises (asking for a cap or a bigger table) instead
of silently diverging from the host grammar.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from ..kernels import count_launch, should_interpret
from ..kernels.pair_count.ops import tile_stream
from ..kernels.pair_count.pair_count import pair_count_pallas
from .base import BuildConfig
from .jnp_builder import (BIG, I32, JnpBuilder, _cap_kept, _runs_of_sorted)


def _count_ranked_pallas(packed, pa, pb, vp, *, S, cap, min_count, K,
                         Kp, interpret):
    """Drop-in for ``jnp_builder._count_ranked``: same return contract,
    ranked arrays of length ``Kp``, occurrence counts from the kernel.
    ``K`` (the jnp fast-path table size) is unused — ``Kp`` already
    bounds the ranked table, and ``n_good <= Kp`` by construction, so
    the exact-fallback redo never triggers for this backend."""
    Np = packed.shape[0]
    ks = jnp.sort(packed)
    rs, _, _ = _runs_of_sorted(ks)
    n_runs = rs.sum().astype(I32)
    kept = _cap_kept(ks, packed, rs, cap=cap) if cap > 0 else rs

    # candidate table: the kept distinct pairs, gather-compacted into Kp
    # static slots (table order is irrelevant — ranking re-sorts)
    csum = jnp.cumsum(kept.astype(I32))
    n_cand = csum[Np - 1]
    src = jnp.searchsorted(csum, jnp.arange(1, Kp + 1, dtype=I32)
                           ).astype(I32)
    on = jnp.arange(Kp, dtype=I32) < n_cand
    kk = jnp.where(on, ks[jnp.minimum(src, Np - 1)], BIG)
    ca = jnp.where(on, kk // S, -1)
    cb = jnp.where(on, kk % S, -1)

    counts = pair_count_pallas(ca, cb, tile_stream(pa), tile_stream(pb),
                               tile_stream(vp), interpret=interpret)

    good = on & (counts >= min_count)
    neg = jnp.where(good, -counts, BIG)
    a = jnp.where(good, ca, BIG)
    b = jnp.where(good, cb, BIG)
    neg_r, ra, rb, rc = jax.lax.sort((neg, a, b, counts), num_keys=3)
    return neg_r, ra, rb, rc, good.sum().astype(I32), n_runs


class PallasBuilder(JnpBuilder):
    name = "pallas"

    def __init__(self, config: BuildConfig | None = None, *,
                 interpret: bool | None = None, **overrides):
        super().__init__(config, **overrides)
        cfg = self.config
        k_req = cfg.table_cap if cfg.table_cap > 0 else cfg.pair_table
        self._Kp = max(128, -(-k_req // 128) * 128)
        self.interpret = (should_interpret() if interpret is None
                          else interpret)
        # one partial per builder: a stable hashable object, so the fused
        # round jits once and is reused every round
        self._counts_fn = partial(_count_ranked_pallas, Kp=self._Kp,
                                  interpret=self.interpret)

    def _rank_k(self) -> int | None:
        return self._Kp

    def _round(self, state, take, *, T, P, K):
        count_launch("pair_count", self.interpret)
        return super()._round(state, take, T=T, P=P, K=K)

    def _check_round(self, n_runs: int) -> None:
        if self.config.table_cap == 0 and n_runs > self._Kp:
            raise RuntimeError(
                f"pallas builder candidate table ({self._Kp}) is smaller "
                f"than the {n_runs} distinct pairs this round; set "
                f"table_cap (capped counting) or raise pair_table to keep "
                f"host parity")
