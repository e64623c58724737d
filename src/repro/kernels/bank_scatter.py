"""Pallas TPU kernel: keyed scatter-max into a stacked (B, m) register bank.

The FPGA engine time-multiplexes one aggregation datapath over many flows:
each arriving word carries a flow key, and the bucket update lands in that
flow's BRAM slice (arXiv:2504.16896 applies the same trick to sketch banks).
The TPU analogue for a multi-tenant bank: the (key, bucket, rank) stream is
precomputed once (the hash_rank kernel), and this kernel folds it into the
bank with the grid tiled over *bank rows* — exactly how ``bucket_fold``
tiles the m axis of a single sketch, except the tile here is a block of
``row_block`` whole sketches whose ``row_block * m`` registers stay resident
in a VMEM scratch accumulator for the entire item sweep.

TPU has no random read-modify-write port, so the update is the same chunked
one-hot compare-reduce as ``hll_fused`` (``kernels.onehot``), widened to the
block's flattened (row, bucket) cell space: an item owned by the current row
block selects
cell ``(key - block_start) * m + bucket``; items owned by other blocks (and
padding) are neutralized by forcing their rank to 0, the identity of the
bucket max.  Cost is O(items * row_block * m) VPU compares per row block —
the small-m trade again, which is why the bank cap mirrors ``MAX_FUSED_P``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.onehot import LANES, cell_rows, from_tiles, onehot_fold, to_tiles

DEFAULT_BLOCK_ROWS = 8
# row_block * m VMEM-resident cells per grid step (the hll_fused m <= 4096
# trade, applied to a block of sketches instead of one).
MAX_BLOCK_CELLS = 1 << 12


def _bank_kernel(
    keys_ref,
    idx_ref,
    rank_ref,
    regs_in_ref,
    out_ref,
    scratch_ref,
    *,
    m: int,
    row_block: int,
):
    jb = pl.program_id(0)  # bank row block
    step = pl.program_id(1)  # item tile

    @pl.when(step == 0)
    def _init():
        scratch_ref[...] = regs_in_ref[...].reshape(scratch_ref.shape)

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
        out_ref[...] = scratch_ref[...].reshape(out_ref.shape)


@functools.partial(
    jax.jit,
    static_argnames=("m", "row_block", "block_rows", "interpret"),
)
def bank_scatter_max(
    registers: jnp.ndarray,
    keys: jnp.ndarray,
    idx: jnp.ndarray,
    rank: jnp.ndarray,
    *,
    m: int,
    row_block: int,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fold a precomputed (key, bucket, rank) stream into a (B, m) bank.

    ``registers`` is (B, m) int32 with B divisible by ``row_block``;
    ``keys``/``idx``/``rank`` are (rows, LANES) int32 tiles of the routed
    stream (rows divisible by ``block_rows``).  Padding and foreign keys
    must arrive pre-masked to rank 0 — see ``sketch.backends.bank_update``
    for the wrapper that owns tiling and masking.
    """
    bank_rows, got_m = registers.shape
    if got_m != m:
        raise ValueError(f"registers are (B, {got_m}), expected m={m}")
    if bank_rows % row_block != 0:
        raise ValueError(f"row_block ({row_block}) must divide B ({bank_rows})")
    if row_block * m > MAX_BLOCK_CELLS:
        raise ValueError(
            f"row_block*m = {row_block * m} exceeds the VMEM cell cap "
            f"{MAX_BLOCK_CELLS}; use the jnp scatter path for large banks"
        )
    if keys.shape != idx.shape or keys.shape != rank.shape:
        raise ValueError("keys/idx/rank tile shapes must match")
    rows = keys.shape[0]
    if keys.ndim != 2 or keys.shape[1] != LANES:
        raise ValueError(f"stream tiles must be (rows, {LANES}), got {keys.shape}")
    if rows % block_rows != 0:
        raise ValueError(f"block_rows ({block_rows}) must divide rows ({rows})")

    row_blocks = bank_rows // row_block
    cells = row_block * m
    crows = cell_rows(cells)
    # the (row_blocks, cell_rows, 128) layout keeps every reshape of the
    # stored bank outside the kernel
    regs3d = to_tiles(registers.reshape(row_blocks, cells))
    grid = (row_blocks, rows // block_rows)
    stream_spec = pl.BlockSpec((block_rows, LANES), lambda j, i: (i, 0))
    bank_spec = pl.BlockSpec((None, crows, LANES), lambda j, i: (j, 0, 0))
    out = pl.pallas_call(
        functools.partial(_bank_kernel, m=m, row_block=row_block),
        grid=grid,
        in_specs=[stream_spec, stream_spec, stream_spec, bank_spec],
        out_specs=bank_spec,
        out_shape=jax.ShapeDtypeStruct(regs3d.shape, jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, crows * LANES), jnp.int32)],
        interpret=interpret,
    )(
        keys.astype(jnp.int32),
        idx.astype(jnp.int32),
        rank.astype(jnp.int32),
        regs3d,
    )
    return from_tiles(out, cells).reshape(bank_rows, m)
