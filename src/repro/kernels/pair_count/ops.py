"""Wrappers for the pair_count histogram kernel.

``pair_count(seq, active, n, cand_a, cand_b)`` derives the adjacent-pair
stream (left symbol, right symbol, validity) from the working sequence on
device, tiles it to ``(num_tiles, TILE_N)``, and launches the kernel.
All shapes are static, so the device builders call this inside their
jitted round; ``interpret`` auto-selects like every other kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import count_launch, should_interpret
from .pair_count import TILE_N, pair_count_pallas


def tile_stream(x: jax.Array) -> jax.Array:
    """(Np,) -> the kernel's (num_tiles, 1, tn) int32 layout, zero-padded
    to a tile multiple."""
    Np = x.shape[0]
    tn = min(TILE_N, Np)
    pad = -(-Np // tn) * tn - Np
    return jnp.pad(x.astype(jnp.int32), (0, pad)).reshape(-1, 1, tn)


def _pair_stream(seq: jax.Array, active: jax.Array, n: jax.Array):
    """(a, b, valid) for every adjacent pair slot, tiled.  Mirrors the
    device builders' pair semantics: a slot is valid iff both positions
    are active and inside the live length."""
    Np = seq.shape[0]
    idx = jnp.arange(Np, dtype=jnp.int32)
    b = jnp.concatenate([seq[1:], jnp.zeros((1,), seq.dtype)])
    b_act = jnp.concatenate([active[1:], jnp.zeros((1,), bool)])
    vm = active & b_act & (idx + 1 < n)
    return tile_stream(seq), tile_stream(b), tile_stream(vm)


@partial(jax.jit, static_argnames=("interpret",))
def _pair_count_jit(seq, active, n, cand_a, cand_b, *, interpret):
    pa_t, pb_t, vm_t = _pair_stream(seq, active, n)
    return pair_count_pallas(cand_a.astype(jnp.int32),
                             cand_b.astype(jnp.int32), pa_t, pb_t, vm_t,
                             interpret=interpret)


def pair_count(seq: jax.Array, active: jax.Array, n,
               cand_a: jax.Array, cand_b: jax.Array,
               interpret: bool | None = None) -> jax.Array:
    """(K,) int32 exact occurrence counts of the candidate pairs across
    the active sequence.  ``cand_a/cand_b`` must be 128-multiple length
    (use -1 sentinels for unused slots)."""
    if interpret is None:
        interpret = should_interpret()
    count_launch("pair_count", interpret)
    return _pair_count_jit(jnp.asarray(seq), jnp.asarray(active),
                           jnp.asarray(n, jnp.int32), jnp.asarray(cand_a),
                           jnp.asarray(cand_b), interpret=interpret)
