"""Pallas TPU kernel: dedup-scatter of sparse-destined items into COO row blocks.

Fourth sibling of ``bank_scatter``/``window_fold``/``cm_scatter``: the
HybridBank (DESIGN.md §12) defers sparse-row dedup into an append buffer and
compacts under pressure; this kernel is the compaction's scatter phase.  The
(row, bucket, rank) triple stream — existing COO pairs re-emitted as triples
plus the newly hashed append buffer — sweeps a grid tiled over *bank row
blocks*, exactly like ``bank_scatter`` tiles ingest, but the VMEM-resident
tile here is the row block's bucket -> max-rank pair map (dense-addressed so
the TPU's chunked one-hot compare-reduce, ``kernels.onehot``, can stand in
for the random read-modify-write port it does not have), initialized to zero
instead of carrying registers in.

At the final item tile the kernel flushes the deduped pair tile
(``row_block * m`` int32 cells; the host-side COO compaction reads the
surviving ``(bucket, max rank)`` pairs back out of it in bucket order); the
per-row distinct-bucket counts promotion detection needs are one popcount
over that map in the same jitted call — no second pass over the stream.
Cost is O(items * row_block * m) VPU compares per row block: the small-m
trade again, so the cap mirrors ``MAX_BLOCK_CELLS``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.onehot import LANES, cell_rows, from_tiles, onehot_fold

DEFAULT_BLOCK_ROWS = 8
# row_block * m VMEM-resident pair cells per grid step (same budget as the
# bank_scatter accumulator).
MAX_BLOCK_CELLS = 1 << 12


def _sparse_kernel(
    keys_ref,
    idx_ref,
    rank_ref,
    pairs_ref,
    scratch_ref,
    *,
    m: int,
    row_block: int,
):
    jb = pl.program_id(0)  # bank row block
    step = pl.program_id(1)  # item tile

    @pl.when(step == 0)
    def _init():
        # unlike bank_scatter there are no incoming registers: the pair
        # tile starts empty and the stream alone decides the survivors
        scratch_ref[...] = jnp.zeros_like(scratch_ref)

    keys = keys_ref[...]  # (block_rows, LANES)
    local = keys - jb * row_block
    owned = (local >= 0) & (local < row_block)
    # rank 0 is the identity of the bucket max, so items owned by other row
    # blocks (and padding, pre-masked to rank 0 by the wrapper) are no-ops
    # aimed at cell 0.
    rank = jnp.where(owned, rank_ref[...], 0)
    col = jnp.where(owned, local * m + idx_ref[...], 0)
    scratch_ref[...] = onehot_fold(scratch_ref[...], col, rank, jnp.max, jnp.maximum)

    @pl.when(step == pl.num_programs(1) - 1)
    def _flush():
        pairs_ref[...] = scratch_ref[...].reshape(pairs_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("rows", "m", "row_block", "block_rows", "interpret"),
)
def sparse_scatter_coo(
    keys: jnp.ndarray,
    idx: jnp.ndarray,
    rank: jnp.ndarray,
    *,
    rows: int,
    m: int,
    row_block: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> tuple:
    """Dedup a routed (key, bucket, rank) stream into per-row pair maps.

    ``keys``/``idx``/``rank`` are (tile_rows, LANES) int32 tiles of the
    triple stream (tile_rows divisible by ``block_rows``); ``rows`` is the
    bank's row count, divisible by ``row_block``.  Padding and foreign keys
    must arrive pre-masked to rank 0 — see ``sketch.backends.sparse_merge``
    for the wrapper that owns tiling and masking.  Returns the (rows, m)
    int32 max-rank cells and the (rows,) int32 distinct-bucket counts.
    """
    if rows % row_block != 0:
        raise ValueError(f"row_block ({row_block}) must divide rows ({rows})")
    if row_block * m > MAX_BLOCK_CELLS:
        raise ValueError(
            f"row_block*m = {row_block * m} exceeds the VMEM cell cap "
            f"{MAX_BLOCK_CELLS}; use the jnp dedup path for large banks"
        )
    if keys.shape != idx.shape or keys.shape != rank.shape:
        raise ValueError("keys/idx/rank tile shapes must match")
    if keys.ndim != 2 or keys.shape[1] != LANES:
        raise ValueError(
            f"stream tiles must be (rows, {LANES}), got {keys.shape}"
        )
    tile_rows = keys.shape[0]
    if tile_rows % block_rows != 0:
        raise ValueError(
            f"block_rows ({block_rows}) must divide tile rows ({tile_rows})"
        )

    row_blocks = rows // row_block
    cells = row_block * m
    crows = cell_rows(cells)
    grid = (row_blocks, tile_rows // block_rows)
    stream_spec = pl.BlockSpec((block_rows, LANES), lambda j, i: (i, 0))
    pairs = pl.pallas_call(
        functools.partial(_sparse_kernel, m=m, row_block=row_block),
        grid=grid,
        in_specs=[stream_spec, stream_spec, stream_spec],
        out_specs=pl.BlockSpec((None, crows, LANES), lambda j, i: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((row_blocks, crows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, crows * LANES), jnp.int32)],
        interpret=interpret,
    )(
        keys.astype(jnp.int32),
        idx.astype(jnp.int32),
        rank.astype(jnp.int32),
    )
    pairs = from_tiles(pairs, cells).reshape(rows, m)
    # one popcount over the deduped map (m lanes per row do not tile the
    # kernel's 128-lane cell rows, so it runs as one XLA reduce)
    return pairs, jnp.sum(pairs > 0, axis=1, dtype=jnp.int32)
