"""Shared tiling for the one-hot scatter kernels (hll_fused, bank_scatter,
cm_scatter, sparse_scatter) and the row-block ring folds.

TPU has no random read-modify-write port, so every scatter kernel folds its
(cell, value) stream into a VMEM-resident accumulator with a chunked one-hot
compare-reduce: a (chunk, cells) equality mask against the cell iota selects
each item's value into its cell column, and a reduce over the chunk axis
merges the chunk.  A chunk is one 128-item row of a (rows, 128) stream tile;
the tile is transposed once so each chunk becomes a column that broadcasts
along the cell lanes, which keeps every load a whole 2-D tile (Mosaic has no
in-kernel 1-D dynamic slice).

Banks enter the row-block kernels as (row_blocks, cell_rows, 128) arrays
(``to_tiles``): the block's last two dimensions then equal the array's, as
Mosaic requires, whatever ``row_block * m`` is.  Cells past ``row_block * m``
are zero padding that no stream element addresses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LANES = 128


def cell_rows(cells: int) -> int:
    """Rows of 128 lanes that hold ``cells`` cells."""
    return -(-cells // LANES)


def to_tiles(x: jnp.ndarray) -> jnp.ndarray:
    """(..., cells) -> (..., cell_rows, 128), zero-padding the cell axis."""
    cells = x.shape[-1]
    padded = cell_rows(cells) * LANES
    if padded != cells:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, padded - cells)])
    return x.reshape(*x.shape[:-1], padded // LANES, LANES)


def from_tiles(x: jnp.ndarray, cells: int) -> jnp.ndarray:
    """Inverse of ``to_tiles``: (..., cell_rows, 128) -> (..., cells)."""
    return x.reshape(*x.shape[:-2], x.shape[-2] * LANES)[..., :cells]


def onehot_fold(acc, col, val, reduce, combine):
    """Fold a (rows, 128) tile of (cell, value) pairs into (1, cells) ``acc``.

    ``reduce`` merges one chunk's one-hot rows (``jnp.max`` / ``jnp.sum``);
    ``combine`` merges the chunk into the accumulator (``jnp.maximum`` /
    ``jnp.add``).  A value of 0 is the identity of both, which is how callers
    neutralize padding and foreign keys.
    """
    cell_ids = jax.lax.broadcasted_iota(jnp.int32, (LANES, acc.shape[1]), 1)
    col_t = col.astype(jnp.int32).T  # (128, rows): column j = chunk j
    val_t = val.astype(jnp.int32).T
    for j in range(col.shape[0]):
        onehot = jnp.where(col_t[:, j : j + 1] == cell_ids, val_t[:, j : j + 1], 0)
        acc = combine(acc, reduce(onehot, axis=0, keepdims=True))
    return acc
