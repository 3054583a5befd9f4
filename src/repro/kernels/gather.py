"""Exact int32 table lookups inside the Pallas kernels.

Arbitrary dynamic gathers from VMEM do not vectorize on the TPU, so every
kernel looks tables up with one-hot masked sums (exact in int32; an index
outside the table reads 0).  Kernels keep per-lane values as ``(1, Q)``
rows; a lookup turns its index row into a column, one-hots it against the
table's lanes and turns the sum back into a row, so every value a kernel
carries through a loop has the same row layout (Mosaic aborts on a loop
carry whose layout changes between iterations).  Two shapes of lookup:

* ``row_gather`` — a small table held as one resident row (a stream page,
  a low-bits page): one ``(Q, width)`` one-hot.
* ``table_gather`` — a table that grows with the index (grammar rules,
  list directories), stored dense as ``(rows, 128)`` by ``pack_table``.
  The one-hot runs one 128-lane row at a time, so the live intermediate
  stays ``(Q, 128)`` whatever the rule count — a single ``(Q, width)``
  one-hot over 65,536 rules would be 32 MiB, above the chip's scoped VMEM.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

LANES = 128


def pack_table(a) -> jax.Array:
    """1-D int table -> dense ``(rows, 128)`` int32, zero-padded (at least
    one row).  The kernels take it as one whole-array VMEM block."""
    a = np.asarray(a).astype(np.int32).ravel()
    rows = max(1, -(-a.size // LANES))
    return jnp.asarray(np.pad(a, (0, rows * LANES - a.size)).reshape(
        rows, LANES))


def row_gather(table: jax.Array, idx: jax.Array) -> jax.Array:
    """table (1, width), idx (1, Q) -> (1, Q) = table[idx]."""
    col = idx.T
    iota = lax.broadcasted_iota(jnp.int32, (col.shape[0], table.shape[1]), 1)
    return jnp.sum(jnp.where(col == iota, table, 0), axis=1,
                   keepdims=True).T


def table_gather(tbl_ref, idx: jax.Array) -> jax.Array:
    """tbl_ref (rows, 128) VMEM ref of a ``pack_table`` table, idx (1, Q)
    -> (1, Q) = table[idx]."""
    col = idx.T
    lane = lax.broadcasted_iota(jnp.int32, (col.shape[0], LANES), 1)

    def body(r, acc):
        hit = lane == col - r * LANES
        return acc + jnp.where(hit, tbl_ref[pl.ds(r, 1), :], 0)

    acc = lax.fori_loop(0, tbl_ref.shape[0], body,
                        jnp.zeros((col.shape[0], LANES), jnp.int32))
    return jnp.sum(acc, axis=1, keepdims=True).T
