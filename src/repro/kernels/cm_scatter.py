"""Pallas TPU kernels: keyed scatter-ADD into a (B, d, w) count-min bank.

The bank_scatter kernel folds a keyed HLL stream into a register bank with
a chunked one-hot compare-reduce over the block's flattened cell space
(``kernels.onehot``); this module is its additive mirror for the count-min
family (DESIGN.md §13).  A count-min ingest lands d increments per item —
one per depth row, at column ``r*w + idx_r`` of the row's flattened (d, w)
counter slab — so the wrapper repeats each stream element d times and this
kernel sums the resulting (key, cell, hit) stream into ``row_block`` whole
counter slabs held VMEM-resident for the entire sweep.

Where the max-lattice neutralizes padding with rank 0, the sum-lattice
neutralizes it with hit 0 (the additive identity): padding and foreign
keys arrive pre-masked to ``val = 0`` and aim at cell 0 as a no-op.
Counter arithmetic is int32 two's-complement, bit-identical to the uint32
wraparound of the jnp reference (the wrapper bitcasts in and out).

``cm_window_fold_sum`` is the fourth sibling of ``window_fold``: the same
masked ring fold over a (W, B, d*w) counter ring, with + replacing max
(an expired bucket contributes 0, the additive identity).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.onehot import LANES, cell_rows, from_tiles, onehot_fold, to_tiles

DEFAULT_BLOCK_ROWS = 8
# row_block * d * w VMEM-resident cells per grid step (the bank_scatter
# cap applied to count-min slabs: d=4, w=1024 fits exactly one row).
MAX_BLOCK_CELLS = 1 << 12


def _cm_kernel(
    keys_ref,
    col_ref,
    val_ref,
    counters_in_ref,
    out_ref,
    scratch_ref,
    *,
    cells_per_row: int,
    row_block: int,
):
    jb = pl.program_id(0)  # bank row block
    step = pl.program_id(1)  # item tile

    @pl.when(step == 0)
    def _init():
        scratch_ref[...] = counters_in_ref[...].reshape(scratch_ref.shape)

    keys = keys_ref[...]  # (block_rows, LANES)
    local = keys - jb * row_block
    owned = (local >= 0) & (local < row_block)
    # hit 0 is the identity of the cell sum, so entries owned by other row
    # blocks (and padding, pre-masked to val 0 by the wrapper) are no-ops
    # aimed at cell 0.
    val = jnp.where(owned, val_ref[...], 0)
    col = jnp.where(owned, local * cells_per_row + col_ref[...], 0)
    scratch_ref[...] = onehot_fold(scratch_ref[...], col, val, jnp.sum, jnp.add)

    @pl.when(step == pl.num_programs(1) - 1)
    def _flush():
        out_ref[...] = scratch_ref[...].reshape(out_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("cells_per_row", "row_block", "block_rows", "interpret"),
)
def cm_scatter_add(
    counters: jnp.ndarray,
    keys: jnp.ndarray,
    col: jnp.ndarray,
    val: jnp.ndarray,
    *,
    cells_per_row: int,
    row_block: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Sum a precomputed (key, cell, hit) stream into a (B, d*w) bank.

    ``counters`` is (B, cells_per_row) int32 with B divisible by
    ``row_block``; ``keys``/``col``/``val`` are (rows, LANES) int32 tiles
    of the d-expanded stream (rows divisible by ``block_rows``).  Padding
    and foreign keys must arrive pre-masked to val 0 — see
    ``sketch.backends.cm_update`` for the wrapper that owns hashing,
    d-expansion, tiling, and masking.
    """
    bank_rows, got_cells = counters.shape
    if got_cells != cells_per_row:
        raise ValueError(
            f"counters are (B, {got_cells}), expected d*w={cells_per_row}"
        )
    if bank_rows % row_block != 0:
        raise ValueError(f"row_block ({row_block}) must divide B ({bank_rows})")
    if row_block * cells_per_row > MAX_BLOCK_CELLS:
        raise ValueError(
            f"row_block*d*w = {row_block * cells_per_row} exceeds the VMEM "
            f"cell cap {MAX_BLOCK_CELLS}; use the jnp scatter path instead"
        )
    if keys.shape != col.shape or keys.shape != val.shape:
        raise ValueError("keys/col/val tile shapes must match")
    rows = keys.shape[0]
    if keys.ndim != 2 or keys.shape[1] != LANES:
        raise ValueError(f"stream tiles must be (rows, {LANES}), got {keys.shape}")
    if rows % block_rows != 0:
        raise ValueError(f"block_rows ({block_rows}) must divide rows ({rows})")

    row_blocks = bank_rows // row_block
    cells = row_block * cells_per_row
    crows = cell_rows(cells)
    # the (row_blocks, cell_rows, 128) layout keeps every reshape of the
    # stored bank outside the kernel
    cnt3d = to_tiles(counters.reshape(row_blocks, cells))
    grid = (row_blocks, rows // block_rows)
    stream_spec = pl.BlockSpec((block_rows, LANES), lambda j, i: (i, 0))
    bank_spec = pl.BlockSpec((None, crows, LANES), lambda j, i: (j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_cm_kernel, cells_per_row=cells_per_row, row_block=row_block),
        grid=grid,
        in_specs=[stream_spec, stream_spec, stream_spec, bank_spec],
        out_specs=bank_spec,
        out_shape=jax.ShapeDtypeStruct(cnt3d.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, crows * LANES), jnp.int32)],
        interpret=interpret,
    )(
        keys.astype(jnp.int32),
        col.astype(jnp.int32),
        val.astype(jnp.int32),
        cnt3d,
    )
    return from_tiles(out, cells).reshape(bank_rows, cells_per_row)


def _cm_fold_kernel(mask_ref, ring_ref, out_ref, scratch_ref):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        scratch_ref[...] = jnp.zeros_like(scratch_ref)

    # masked slices fold as 0, the identity of the cell sum
    contrib = jnp.where(mask_ref[w] > 0, ring_ref[...], 0)
    scratch_ref[...] = scratch_ref[...] + contrib

    @pl.when(w == pl.num_programs(1) - 1)
    def _flush():
        out_ref[...] = scratch_ref[...]


@functools.partial(
    jax.jit, static_argnames=("cells_per_row", "row_block", "interpret")
)
def cm_window_fold_sum(
    ring: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    cells_per_row: int,
    row_block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fold a (W, B, d*w) int32 counter ring into (B, d*w) by masked sum.

    ``ring`` is (W, B, d*w) int32 with B divisible by ``row_block``;
    ``mask`` is (W,) int32 where nonzero marks a live bucket.  See
    ``sketch.backends.cm_window_fold`` for the wrapper that owns padding,
    bitcasts, and block sizing.
    """
    if ring.ndim != 3:
        raise ValueError(f"ring must be (W, B, d*w), got {ring.shape}")
    window, bank_rows, got_cells = ring.shape
    if got_cells != cells_per_row:
        raise ValueError(
            f"ring is (W, B, {got_cells}), expected d*w={cells_per_row}"
        )
    if bank_rows % row_block != 0:
        raise ValueError(f"row_block ({row_block}) must divide B ({bank_rows})")
    if row_block * cells_per_row > MAX_BLOCK_CELLS:
        raise ValueError(
            f"row_block*d*w = {row_block * cells_per_row} exceeds the VMEM "
            f"cell cap {MAX_BLOCK_CELLS}; use the jnp fold instead"
        )
    if mask.shape != (window,):
        raise ValueError(f"mask must be ({window},), got {mask.shape}")

    row_blocks = bank_rows // row_block
    cells = row_block * cells_per_row
    crows = cell_rows(cells)
    ring4d = to_tiles(ring.reshape(window, row_blocks, cells))
    grid = (row_blocks, window)
    out = pl.pallas_call(
        _cm_fold_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),  # (W,) mask, whole
            pl.BlockSpec((None, None, crows, LANES), lambda j, w: (w, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, crows, LANES), lambda j, w: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(ring4d.shape[1:], jnp.int32),
        scratch_shapes=[pltpu.VMEM((crows, LANES), jnp.int32)],
        interpret=interpret,
    )(mask.astype(jnp.int32), ring4d)
    return from_tiles(out, cells).reshape(bank_rows, cells_per_row)
