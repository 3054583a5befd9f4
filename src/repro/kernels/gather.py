"""Exact int32 table lookups inside the Pallas kernels.

Arbitrary dynamic gathers from VMEM do not vectorize on the TPU.  Kernels
keep per-lane values as ``(1, Q)`` rows; a lookup turns its index row into
a column and its answer back into a row, so every value a kernel carries
through a loop has the same row layout (Mosaic aborts on a loop carry
whose layout changes between iterations).  An index outside the table
reads 0.  Two shapes of lookup:

* ``row_gather`` — a small table held as one resident row (a stream page,
  a low-bits page): one ``(Q, width)`` one-hot masked sum on the VPU.
* ``table_gather`` — a table that grows with the index (grammar rules,
  list directories), stored dense as ``(rows, 128)`` int32 by
  ``pack_table``.  The MXU picks the row: the index's row ``idx >> 7`` is
  one-hot ``(Q, rows)`` in bfloat16 and multiplied by the table split
  into its four byte planes, ``(rows, 4·128)`` bfloat16.  A byte is exact
  in bfloat16 and each output sums a single product in float32, so the
  dot returns the row's bytes exactly; they recombine in int32 (wrapping
  back to negatives), and one ``(Q, 128)`` compare picks the lane
  ``idx & 127``.  A negative index has a negative row and matches none.
  The one-hot is taken over chunks of at most ``CHUNK_ROWS`` rows, so it
  stays ``(Q, ≤512)`` whatever the rule count (a single ``(Q, width)``
  one-hot over 65,536 rules would be 32 MiB, above the chip's scoped
  VMEM).

A kernel step that looks the same table up several times (the grammar
descent) builds its planes once with ``table_planes`` and looks up with
``plane_gather``; tables of one row count that are read at the same
index share the one-hot and one dot.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

LANES = 128
BYTES = 4           # byte planes of an int32 entry
CHUNK_ROWS = 512    # most table rows one row one-hot spans


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


def table_planes(*tbl_refs) -> jax.Array:
    """``pack_table`` tables of one row count, as VMEM refs -> their byte
    planes side by side: (rows, 4·128·len(tbl_refs)) bfloat16."""
    planes = []
    for ref in tbl_refs:
        t = ref[...]
        planes += [((t >> 8 * b) & 255).astype(jnp.float32)
                   .astype(jnp.bfloat16) for b in range(BYTES)]
    return jnp.concatenate(planes, axis=1)


def plane_gather(planes: jax.Array, idx: jax.Array) -> list[jax.Array]:
    """planes from ``table_planes``, idx (1, Q) -> one (1, Q) row per
    table: its entries at idx."""
    col = idx.T
    q, rows = col.shape[0], planes.shape[0]
    row = col >> 7
    got = None
    for r0 in range(0, rows, CHUNK_ROWS):
        n = min(CHUNK_ROWS, rows - r0)
        hot = (row - r0 == lax.broadcasted_iota(jnp.int32, (q, n), 1))
        part = jnp.dot(hot.astype(jnp.bfloat16), planes[r0:r0 + n],
                       preferred_element_type=jnp.float32)
        got = part if got is None else got + part
    got = got.astype(jnp.int32)
    lane = (col & (LANES - 1)) == lax.broadcasted_iota(jnp.int32,
                                                       (q, LANES), 1)
    outs = []
    for t in range(0, planes.shape[1], BYTES * LANES):
        word = got[:, t:t + LANES]
        for b in range(1, BYTES):
            word = word | (got[:, t + b * LANES:t + (b + 1) * LANES]
                           << 8 * b)
        outs.append(jnp.sum(jnp.where(lane, word, 0), axis=1,
                            keepdims=True).T)
    return outs


def table_gather(tbl_ref, idx: jax.Array) -> jax.Array:
    """tbl_ref (rows, 128) VMEM ref of a ``pack_table`` table, idx (1, Q)
    -> (1, Q) = table[idx]."""
    return plane_gather(table_planes(tbl_ref), idx)[0]
