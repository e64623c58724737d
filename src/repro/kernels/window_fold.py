"""Pallas TPU kernel: masked ring fold for a (W, B, m) windowed bank.

A sliding-window estimate over a ``WindowedBank`` is one reduction: fold
the live time buckets of the (W, B, m) ring into a scratch (B, m) bank by
bucket-wise max, then finalize with the batched estimator (DESIGN.md §11).
The FPGA sliding-window sketches this mirrors (arXiv:2504.16896) keep one
BRAM bank per time slice and OR/merge the live slices on query; the TPU
analogue folds the ring axis with the VPU.

The grid tiles the BANK over row blocks exactly the way ``bank_scatter``
does — each grid step owns ``row_block`` whole sketches whose
``row_block * m`` registers stay resident in a VMEM scratch accumulator —
and sweeps the W ring slices in the inner grid dimension.  Expired buckets
(and suffix windows shorter than W) are neutralized by a (W,) mask: a
masked slice contributes rank 0, the identity of the bucket max, so every
suffix window is bit-identical to merging its buckets one by one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.onehot import LANES, cell_rows, from_tiles, to_tiles

# row_block * m VMEM-resident cells per grid step (the bank_scatter cap,
# applied to the fold side of the window).
MAX_BLOCK_CELLS = 1 << 12


def _window_kernel(mask_ref, ring_ref, out_ref, scratch_ref):
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        scratch_ref[...] = jnp.zeros_like(scratch_ref)

    # masked slices fold as 0, the identity of the bucket max
    contrib = jnp.where(mask_ref[w] > 0, ring_ref[...], 0)
    scratch_ref[...] = jnp.maximum(scratch_ref[...], contrib)

    @pl.when(w == pl.num_programs(1) - 1)
    def _flush():
        out_ref[...] = scratch_ref[...]


@functools.partial(jax.jit, static_argnames=("m", "row_block", "interpret"))
def window_fold_max(
    ring: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    m: int,
    row_block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fold a (W, B, m) int32 ring into (B, m) by masked bucket-wise max.

    ``ring`` is (W, B, m) int32 with B divisible by ``row_block``; ``mask``
    is (W,) int32 where nonzero marks a live bucket.  See
    ``sketch.backends.window_fold`` for the wrapper that owns padding,
    dtype casts, and block sizing.
    """
    if ring.ndim != 3:
        raise ValueError(f"ring must be (W, B, m), got {ring.shape}")
    window, bank_rows, got_m = ring.shape
    if got_m != m:
        raise ValueError(f"ring is (W, B, {got_m}), expected m={m}")
    if bank_rows % row_block != 0:
        raise ValueError(f"row_block ({row_block}) must divide B ({bank_rows})")
    if row_block * m > MAX_BLOCK_CELLS:
        raise ValueError(
            f"row_block*m = {row_block * m} exceeds the VMEM cell cap "
            f"{MAX_BLOCK_CELLS}; use the jnp fold for large banks"
        )
    if mask.shape != (window,):
        raise ValueError(f"mask must be ({window},), got {mask.shape}")

    row_blocks = bank_rows // row_block
    cells = row_block * m
    crows = cell_rows(cells)
    # the (W, row_blocks, cell_rows, 128) layout keeps every reshape
    # outside the kernel
    ring4d = to_tiles(ring.reshape(window, row_blocks, cells))
    grid = (row_blocks, window)
    out = pl.pallas_call(
        _window_kernel,
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
    return from_tiles(out, cells).reshape(bank_rows, m)


@functools.partial(jax.jit, static_argnames=("m", "row_block", "interpret"))
def window_merge_max(
    parts: jnp.ndarray,
    *,
    m: int,
    row_block: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fold a (K, B, m) int32 stack of fold fragments into (B, m) by max.

    The incremental-merge entry point of the prefix/suffix window
    decomposition (DESIGN.md §14): where ``window_fold_max`` sweeps W ring
    slices per query, the decomposed read path merges K fragments with K
    tiny and independent of W — the prefix-stack top, the running suffix
    accumulator, and the dirty head bucket.  A merge IS a W=K fold with
    every slice live, so this reuses the masked ring sweep with an
    all-ones mask and inherits its bit-identity to the bucket-by-bucket
    reference for free.
    """
    if parts.ndim != 3:
        raise ValueError(f"parts must be (K, B, m), got {parts.shape}")
    return window_fold_max(
        parts,
        jnp.ones((parts.shape[0],), jnp.int32),
        m=m,
        row_block=row_block,
        interpret=interpret,
    )
