"""Pallas TPU kernel: fully-fused HLL aggregation pipeline (small-p sketches).

The FPGA keeps the *entire* aggregation phase on-chip: hash units feed bucket
BRAM with an II=1 read-max-write loop.  The TPU analogue for sketches whose
register file fits VMEM comfortably (p <= 12, m <= 4096): a grid over input
tiles with the registers held in a VMEM scratch accumulator for the whole
sweep — input words stream HBM->VMEM once, hashes/ranks/updates never touch
HBM, and the registers are written back exactly once at the end.

TPU has no random read-modify-write port, so the bucket update is expressed
as a chunked one-hot compare-reduce (``kernels.onehot``): a (128, m)
equality mask against the bucket iota selects each item's rank into its
bucket column and a max over the chunk axis merges the chunk — "updates to
the same counter arriving during the read-modify-write cycle are merged"
(paper §V-A.4), except here the merge window is the whole chunk.  Cost is
O(items * m) VPU compares, which is the right trade only for small m; for
p=16 the scatter-based path in sketch/hll.py is used instead (see DESIGN.md
§2).

Padding items are neutralized by forcing their rank to 0: registers are
non-negative and max(r, 0) is the identity, so a rank-0 update is a no-op
by construction.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.onehot import LANES, onehot_fold
from repro.sketch import hll
from repro.sketch.hll import HLLConfig

DEFAULT_BLOCK_ROWS = 8
MAX_FUSED_P = 12


def _fused_kernel(
    n_valid_ref,
    items_ref,
    regs_in_ref,
    out_ref,
    scratch_ref,
    *,
    cfg: HLLConfig,
    block_rows: int,
):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        scratch_ref[...] = regs_in_ref[...]

    items = items_ref[...]  # (block_rows, LANES)
    idx, rank = hll.hash_index_rank(items, cfg)

    # neutralize padding: global row-major position >= n_valid -> rank 0
    tile = block_rows * LANES
    pos = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0) * LANES
    pos = pos + jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
    pos = pos + step * tile
    rank = jnp.where(pos < n_valid_ref[0, 0], rank, 0)

    scratch_ref[...] = onehot_fold(scratch_ref[...], idx, rank, jnp.max, jnp.maximum)

    @pl.when(step == pl.num_programs(0) - 1)
    def _flush():
        out_ref[...] = scratch_ref[...]


@functools.partial(jax.jit, static_argnames=("cfg", "block_rows", "interpret"))
def hll_update_fused(
    registers: jnp.ndarray,
    items: jnp.ndarray,
    n_valid: jnp.ndarray,
    cfg: HLLConfig,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    interpret: bool = False,
) -> jnp.ndarray:
    """Aggregate (rows, 128) items into (1, m) int32 registers, fully fused.

    ``n_valid`` is a (1, 1) int32 array: items at flat positions >= n_valid
    are padding and are ignored.  Use kernels.ops.hll_update for the
    flat-stream convenience wrapper.
    """
    if cfg.p > MAX_FUSED_P:
        raise ValueError(
            f"fused pipeline supports p <= {MAX_FUSED_P} (m <= "
            f"{1 << MAX_FUSED_P}); use the scatter path for p={cfg.p}"
        )
    if items.ndim != 2 or items.shape[1] != LANES:
        raise ValueError(f"items must be (rows, {LANES}), got {items.shape}")
    rows = items.shape[0]
    if rows % block_rows != 0:
        raise ValueError(f"rows ({rows}) must divide block_rows ({block_rows})")
    if registers.shape != (1, cfg.m):
        raise ValueError(f"registers must be (1, {cfg.m}), got {registers.shape}")

    grid = (rows // block_rows,)
    full_regs = pl.BlockSpec((1, cfg.m), lambda i: (0, 0))
    return pl.pallas_call(
        functools.partial(_fused_kernel, cfg=cfg, block_rows=block_rows),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),  # n_valid
            pl.BlockSpec((block_rows, LANES), lambda i: (i, 0)),  # items
            full_regs,  # current registers
        ],
        out_specs=full_regs,
        out_shape=jax.ShapeDtypeStruct((1, cfg.m), jnp.int32),
        scratch_shapes=[pltpu.VMEM((1, cfg.m), jnp.int32)],
        interpret=interpret,
    )(n_valid.astype(jnp.int32), items.astype(jnp.uint32), registers)
