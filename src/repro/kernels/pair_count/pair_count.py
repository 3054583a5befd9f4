"""Pallas TPU kernel: tiled pair histogram over the active sequence.

The construction-time hot loop of Re-Pair (DESIGN.md §3.3): count, for a
static table of K candidate pairs, every adjacent occurrence
``(seq[i], seq[i+1])`` across the working sequence.  The sequence lives in
HBM as fixed-size tiles ``(num_tiles, 1, TILE_N)`` — the same paging
discipline as ``list_intersect``: each kernel instance sees exactly ONE
sequence tile and one candidate tile, so per-instance VMEM is a function
of ``TILE_K`` and ``TILE_N``, never of the stream length N.

The grid is ``(K_tiles, num_tiles)`` with the sequence axis innermost;
the output block for candidate tile ``kt`` is revisited across every
sequence step and accumulates in place (zeroed at step 0) — the standard
reduction idiom, so no scratch is needed.  Per instance the work is one
``(TILE_K, TILE_N)`` compare-and-popcount: pure VPU, no gathers.

Invalid sequence slots (separators, the dropped-tail padding, position
``n-1``'s wraparound pair) arrive pre-masked in ``vm``; sentinel
candidates use id ``-1``, which no valid slot can match (symbol ids are
non-negative).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TILE_N = 512   # sequence slots per instance (lane multiple)
TILE_K = 512   # candidate pairs per instance


def pair_count(a_ref, b_ref, pa_ref, pb_ref, vm_ref, out_ref):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ca = a_ref[0, :]                     # (TILE_K,) candidate lefts
    cb = b_ref[0, :]
    pa = pa_ref[0, :]                    # (TILE_N,) sequence tile
    pb = pb_ref[0, :]
    vm = vm_ref[0, :]
    m = ((ca[:, None] == pa[None, :]) & (cb[:, None] == pb[None, :])
         & (vm[None, :] != 0))
    out_ref[0, :] += jnp.sum(m.astype(jnp.int32), axis=1)


def pair_count_pallas(cand_a: jax.Array, cand_b: jax.Array,
                      pa_t: jax.Array, pb_t: jax.Array, vm_t: jax.Array,
                      *, interpret: bool = False) -> jax.Array:
    """Histogram of K candidate pairs over a tiled pair stream.

    ``cand_a``/``cand_b`` (K,) int32 with -1 sentinels; ``pa_t``/``pb_t``/
    ``vm_t`` (num_tiles, 1, TILE_N) int32 — left symbol, right symbol and
    validity of every adjacent pair slot, one sequence tile per block.
    Returns (K,) int32 exact counts, bit-identical to the jnp sort
    histogram (``ref.py``)."""
    K = cand_a.shape[0]
    nt, _, tn = pa_t.shape
    tk = min(TILE_K, K)
    # the grid must cover every candidate: pad the table to a tile
    # multiple with -1 sentinels (a partial tail tile would otherwise be
    # skipped by the floor division and return garbage counts)
    pad = -K % tk
    if pad:
        cand_a = jnp.pad(cand_a, (0, pad), constant_values=-1)
        cand_b = jnp.pad(cand_b, (0, pad), constant_values=-1)
    kp = K + pad
    cspec = pl.BlockSpec((1, tk), lambda kt, t: (0, kt))
    sspec = pl.BlockSpec((None, 1, tn), lambda kt, t: (t, 0, 0))
    return pl.pallas_call(
        pair_count,                      # Mosaic's name for the kernel
        grid=(kp // tk, nt),
        in_specs=[cspec, cspec, sspec, sspec, sspec],
        out_specs=pl.BlockSpec((1, tk), lambda kt, t: (0, kt)),
        out_shape=jax.ShapeDtypeStruct((1, kp), jnp.int32),
        interpret=interpret,
    )(cand_a[None, :], cand_b[None, :], pa_t, pb_t, vm_t)[0, :K]
