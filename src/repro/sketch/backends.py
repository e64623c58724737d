"""Aggregation backends behind the ExecutionPlan registry.

Three backends ship by default, all bit-identical on the same stream (the
max-lattice makes slicing/padding invisible — DESIGN.md §6):

  jnp              XLA scatter-max; ``pipelines`` k slices the stream into k
                   sub-sketches folded by one fused segment-max (Fig. 3)
  pallas           fully-fused Pallas kernel, registers VMEM-resident for the
                   whole sweep (small-p sketches, p <= 12 — DESIGN.md §2)
  pallas_pipelined k fused Pallas pipelines + the bucket-fold kernel

This module also owns the tiling/padding wrappers that used to live in
``repro.kernels.ops`` (now a deprecated shim).  Non-divisible streams are
always padded, never rejected: padded positions get rank 0, and a rank-0
update is the identity of the bucket max.

``interpret`` defaults to True off-TPU and False on TPU, where the
Mosaic-compiled kernel runs; ``PALLAS_MODES`` tallies how every wrapper call
resolved it, so an on-chip check can prove no kernel fell back to the
interpreter.
"""

from __future__ import annotations

import collections
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.sketch import hll
from repro.sketch.hll import HLLConfig
from repro.sketch.plan import (
    DEFAULT_PIPELINES,
    ExecutionPlan,
    SparseDedup,
    register_backend,
    register_bank_backend,
    register_cm_backend,
    register_cm_window_backend,
    register_sparse_backend,
    register_window_backend,
    register_window_merge_backend,
)

# The kernel modules themselves import repro.sketch.hll, so they are loaded
# lazily (first wrapper call) rather than at module import — this keeps
# `import repro.kernels.hash_rank` (a documented, non-deprecated entry)
# working as a process's very first import instead of dying in the cycle
# repro.kernels.* -> repro.sketch -> backends -> repro.kernels.*.
LANES = 128  # pltpu lane width; asserted against the kernel modules on load


def _kernels():
    from repro.kernels import bucket_fold as _fold
    from repro.kernels import hash_rank as _hash
    from repro.kernels import hll_fused as _fused

    assert _hash.LANES == _fold.LANES == _fused.LANES == LANES
    return _hash, _fold, _fused


def _bank_kernel_module():
    from repro.kernels import bank_scatter as _bank

    assert _bank.LANES == LANES
    return _bank


def _window_kernel_module():
    from repro.kernels import window_fold as _window

    assert _window.LANES == LANES
    return _window


# "compiled" / "interpret" -> how many Pallas wrapper calls resolved to it
PALLAS_MODES: collections.Counter = collections.Counter()


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """A plan's ``interpret`` flag: None = compiled on TPU, interpreted
    elsewhere.  Every resolution is tallied in ``PALLAS_MODES``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    PALLAS_MODES["interpret" if interpret else "compiled"] += 1
    return interpret


def _pad_to_tiles(flat: jnp.ndarray, tile_items: int) -> Tuple[jnp.ndarray, int]:
    """Pad a flat stream up to a whole number of (block_rows, 128) tiles.

    Always at least one tile, so empty streams/slices (e.g. a short last
    pipeline when n < k) lower cleanly; the kernels' n_valid masking turns
    the all-padding tile into a no-op.
    """
    n = flat.shape[0]
    padded = max(1, -(-n // tile_items)) * tile_items
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(padded // LANES, LANES), n


# ----------------------------------------------------------------------------
# jnp backend (reference scatter path + lane-pipelined variant)
# ----------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg", "pipelines"))
def update_pipelined(
    registers: jnp.ndarray,
    items: jnp.ndarray,
    cfg: HLLConfig,
    pipelines: int = DEFAULT_PIPELINES,
) -> jnp.ndarray:
    """Fig. 3 on one device: slice the stream over k pipelines, fold with max.

    Streams that do not divide ``pipelines`` are zero-padded and the padded
    positions' ranks masked to 0 (the bucket-max identity), so any length is
    accepted and the result stays bit-identical to the single-pipeline path.
    """
    flat = items.reshape(-1)
    n = flat.shape[0]
    if pipelines <= 1 or n == 0:
        return hll.update(registers, flat, cfg)
    padded = -(-n // pipelines) * pipelines
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    slices = flat.reshape(pipelines, padded // pipelines)
    idx, rank = hll.hash_index_rank(slices, cfg)
    if padded != n:
        pos = jnp.arange(padded, dtype=jnp.int32).reshape(slices.shape)
        rank = jnp.where(pos < n, rank, 0)
    # per-pipeline partial sketches: offset bucket ids per pipeline then one
    # segment_max over k*m segments (single fused scatter).
    offsets = (jnp.arange(pipelines, dtype=jnp.int32) * cfg.m)[:, None]
    seg = (idx + offsets).reshape(-1)
    partial_regs = jax.ops.segment_max(
        rank.reshape(-1), seg, num_segments=pipelines * cfg.m
    )
    partial_regs = jnp.maximum(partial_regs, 0).astype(hll.REGISTER_DTYPE)
    folded = jnp.max(partial_regs.reshape(pipelines, cfg.m), axis=0)
    return jnp.maximum(registers, folded)


# ----------------------------------------------------------------------------
# Pallas kernel wrappers (absorb tiling, dtype casts, block clamping)
# ----------------------------------------------------------------------------


def hash_rank(
    items: jnp.ndarray,
    cfg: HLLConfig,
    *,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fused murmur3+rank of a flat item stream -> (idx, rank) int32 arrays."""
    _hash, _, _ = _kernels()
    block_rows = _hash.DEFAULT_BLOCK_ROWS if block_rows is None else block_rows
    interpret = _resolve_interpret(interpret)
    flat = items.reshape(-1)
    tiled, n = _pad_to_tiles(flat, block_rows * LANES)
    idx, rank = _hash.hash_rank(
        tiled, cfg, block_rows=block_rows, interpret=interpret
    )
    return idx.reshape(-1)[:n], rank.reshape(-1)[:n]


def bucket_fold(
    partials: jnp.ndarray,
    *,
    block_m: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fold (k, m) partial registers (any int dtype) -> (m,) by max."""
    _, _fold, _ = _kernels()
    block_m = _fold.DEFAULT_BLOCK_M if block_m is None else block_m
    interpret = _resolve_interpret(interpret)
    out = _fold.bucket_fold(
        partials.astype(jnp.int32), block_m=block_m, interpret=interpret
    )
    return out.astype(partials.dtype)


def hll_update(
    registers: jnp.ndarray,
    items: jnp.ndarray,
    cfg: HLLConfig,
    *,
    block_rows: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fully-fused aggregation of a flat stream into (m,) uint8 registers.

    Small-p sketches only (p <= 12); the p=16 production sketch uses the
    scatter path in sketch/hll.py — see the kernel docstring for why.
    """
    _, _, _fused = _kernels()
    block_rows = _fused.DEFAULT_BLOCK_ROWS if block_rows is None else block_rows
    interpret = _resolve_interpret(interpret)
    flat = items.reshape(-1)
    tiled, n = _pad_to_tiles(flat, block_rows * LANES)
    n_valid = jnp.full((1, 1), n, jnp.int32)
    regs2d = registers.astype(jnp.int32).reshape(1, cfg.m)
    out = _fused.hll_update_fused(
        regs2d, tiled, n_valid, cfg, block_rows=block_rows, interpret=interpret
    )
    return out.reshape(cfg.m).astype(hll.REGISTER_DTYPE)


def pipelined_update(
    registers: jnp.ndarray,
    items: jnp.ndarray,
    cfg: HLLConfig,
    pipelines: int = DEFAULT_PIPELINES,
    *,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Paper Fig. 3 built from the kernels: k fused pipelines + fold kernel.

    Slices the stream across ``pipelines`` sub-sketches, aggregates each with
    the fused kernel, folds partials with the bucket_fold kernel, and merges
    into the running registers.
    """
    interpret = _resolve_interpret(interpret)
    flat = items.reshape(-1)
    n = flat.shape[0]
    per = -(-n // pipelines)
    partials = []
    for k in range(pipelines):
        part = flat[k * per : (k + 1) * per]  # static slice; last may be short
        partials.append(
            hll_update(
                jnp.zeros((cfg.m,), hll.REGISTER_DTYPE), part, cfg,
                interpret=interpret,
            )
        )
    folded = bucket_fold(jnp.stack(partials), interpret=interpret)
    return jnp.maximum(registers, folded)


# ----------------------------------------------------------------------------
# registry entries: fn(registers, items, cfg, plan) -> registers
# ----------------------------------------------------------------------------


@register_backend("jnp")
def _jnp_backend(registers, items, cfg: HLLConfig, plan: ExecutionPlan):
    return update_pipelined(registers, items, cfg, plan.pipelines)


@register_backend("pallas")
def _pallas_backend(registers, items, cfg: HLLConfig, plan: ExecutionPlan):
    # the fused kernel is one hardware pipeline; k>1 belongs to
    # "pallas_pipelined", so `pipelines` is intentionally not consulted here.
    return hll_update(registers, items, cfg, interpret=plan.interpret)


@register_backend("pallas_pipelined")
def _pallas_pipelined_backend(registers, items, cfg: HLLConfig, plan: ExecutionPlan):
    return pipelined_update(
        registers, items, cfg, plan.pipelines, interpret=plan.interpret
    )


# ----------------------------------------------------------------------------
# SketchBank ingest paths (keyed scatter-max; DESIGN.md §9)
# ----------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def bank_update_jnp(
    registers: jnp.ndarray,
    keys: jnp.ndarray,
    items: jnp.ndarray,
    cfg: HLLConfig,
) -> jnp.ndarray:
    """Reference bank ingest: ONE segment-max over (key, bucket) cells.

    Row b's bucket idx lands in flattened segment ``b*m + idx`` — the same
    offset trick the batched register histogram uses (DESIGN.md §8), so the
    whole (B, m) bank aggregates a keyed stream with a single fused scatter.
    Out-of-range keys route to a discarded trailing segment (never clamped
    into a neighboring row); ``pipelines`` is ignored because the scatter is
    already one fused op — there is no fold to parallelize.

    The flattened cell space must fit int32 (TPU has no 64-bit datapath):
    banks with B*m >= 2^31 would silently wrap the segment ids, so they are
    rejected loudly — shard such fleets across banks (or devices) instead.
    """
    bank_rows, m = registers.shape
    if bank_rows * m >= 1 << 31:
        raise ValueError(
            f"bank cell space B*m = {bank_rows}*{m} overflows int32 segment "
            f"ids; split the fleet across multiple banks or mesh shards"
        )
    idx, rank = hll.hash_index_rank(items, cfg)
    valid = (keys >= 0) & (keys < bank_rows)
    seg = jnp.where(valid, keys * m + idx, bank_rows * m)
    new = jax.ops.segment_max(
        jnp.where(valid, rank, 0).astype(hll.REGISTER_DTYPE),
        seg,
        num_segments=bank_rows * m + 1,
    )
    folded = new[: bank_rows * m].reshape(bank_rows, m)
    return jnp.maximum(registers, folded)


def bank_update(
    registers: jnp.ndarray,
    keys: jnp.ndarray,
    items: jnp.ndarray,
    cfg: HLLConfig,
    *,
    row_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas bank ingest: hash_rank kernel + the bank_scatter kernel.

    The (key, bucket, rank) stream is computed once by the fused hash
    kernel; the scatter kernel then tiles the BANK over row blocks the way
    ``bucket_fold`` tiles m, keeping ``row_block * m`` registers VMEM-
    resident per sweep.  Small-m banks only (the hll_fused trade); the
    default row_block picks the largest block under the VMEM cell cap.
    """
    _bank = _bank_kernel_module()
    _hash, _, _ = _kernels()
    interpret = _resolve_interpret(interpret)
    bank_rows, m = registers.shape
    if m > _bank.MAX_BLOCK_CELLS:
        raise ValueError(
            f"pallas bank ingest supports m <= {_bank.MAX_BLOCK_CELLS} "
            f"(p <= 12); use the jnp scatter path for m={m}"
        )
    flat_keys = keys.reshape(-1).astype(jnp.int32)
    flat_items = items.reshape(-1)
    valid = (flat_keys >= 0) & (flat_keys < bank_rows)
    # one padding serves both kernels: the hash tile (64 rows) is a
    # multiple of the scatter tile (8 rows), so the hashed stream feeds the
    # scatter kernel with no slice/re-pad round-trip in between
    assert (_hash.DEFAULT_BLOCK_ROWS * LANES) % (
        _bank.DEFAULT_BLOCK_ROWS * LANES
    ) == 0
    tile_items = _hash.DEFAULT_BLOCK_ROWS * LANES
    items_t, _ = _pad_to_tiles(flat_items, tile_items)
    keys_t, _ = _pad_to_tiles(jnp.where(valid, flat_keys, 0), tile_items)
    valid_t, _ = _pad_to_tiles(valid.astype(jnp.int32), tile_items)
    idx_t, rank_t = _hash.hash_rank(
        items_t, cfg, block_rows=_hash.DEFAULT_BLOCK_ROWS, interpret=interpret
    )
    # same drop rule as the jnp path: padding and foreign keys are masked
    # to rank 0 (the bucket-max identity), never clamped into a neighbor
    rank_t = jnp.where(valid_t > 0, rank_t, 0)

    if row_block is None:
        row_block = max(1, _bank.MAX_BLOCK_CELLS // m)
    row_block = min(row_block, bank_rows)
    padded_rows = -(-bank_rows // row_block) * row_block
    regs32 = registers.astype(jnp.int32)
    if padded_rows != bank_rows:
        # phantom rows receive nothing (keys < bank_rows) and are sliced off
        regs32 = jnp.pad(regs32, ((0, padded_rows - bank_rows), (0, 0)))
    out = _bank.bank_scatter_max(
        regs32,
        keys_t,
        idx_t,
        rank_t,
        m=m,
        row_block=row_block,
        interpret=interpret,
    )
    return out[:bank_rows].astype(hll.REGISTER_DTYPE)


@register_bank_backend("jnp")
def _jnp_bank_backend(registers, keys, items, cfg: HLLConfig, plan: ExecutionPlan):
    return bank_update_jnp(registers, keys, items, cfg)


@register_bank_backend("pallas")
def _pallas_bank_backend(registers, keys, items, cfg: HLLConfig, plan: ExecutionPlan):
    # one datapath, widest row block under the VMEM cap
    return bank_update(registers, keys, items, cfg, interpret=plan.interpret)


@register_bank_backend("pallas_pipelined")
def _pallas_pipelined_bank_backend(
    registers, keys, items, cfg: HLLConfig, plan: ExecutionPlan
):
    # tile the bank over k pipelines (paper Fig. 3 applied to rows): each
    # grid block owns ceil(B/k) sketches, still under the VMEM cell cap
    rows = registers.shape[0]
    row_block = max(1, -(-rows // plan.pipelines))
    _bank = _bank_kernel_module()
    row_block = min(row_block, max(1, _bank.MAX_BLOCK_CELLS // cfg.m))
    return bank_update(
        registers,
        keys,
        items,
        cfg,
        row_block=row_block,
        interpret=plan.interpret,
    )


# ----------------------------------------------------------------------------
# WindowedBank ring folds (masked max over the W axis; DESIGN.md §11)
# ----------------------------------------------------------------------------


@jax.jit
def window_fold_jnp(ring: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Reference ring fold: ONE masked max-reduce over the W axis.

    Expired/unselected buckets fold as all-zero registers (rank 0 is the
    identity of the bucket max), so any suffix window is bit-identical to
    merging its live buckets one by one.
    """
    masked = jnp.where(mask[:, None, None], ring, jnp.zeros_like(ring))
    return jnp.max(masked, axis=0)


def window_fold(
    ring: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    row_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas ring fold: the window_fold kernel over row-block tiles.

    Tiles the (W, B, m) ring over bank-row blocks exactly like
    ``bank_update`` tiles ingest — ``row_block * m`` registers VMEM-
    resident per grid step — and sweeps the ring axis in the inner grid
    dimension with a scratch accumulator.  Small-m banks only (the
    hll_fused trade); the default row_block picks the largest block under
    the VMEM cell cap.
    """
    _window = _window_kernel_module()
    interpret = _resolve_interpret(interpret)
    window, bank_rows, m = ring.shape
    if m > _window.MAX_BLOCK_CELLS:
        raise ValueError(
            f"pallas window fold supports m <= {_window.MAX_BLOCK_CELLS} "
            f"(p <= 12); use the jnp fold for m={m}"
        )
    if row_block is None:
        row_block = max(1, _window.MAX_BLOCK_CELLS // m)
    row_block = min(row_block, bank_rows)
    padded_rows = -(-bank_rows // row_block) * row_block
    ring32 = ring.astype(jnp.int32)
    if padded_rows != bank_rows:
        # phantom rows fold all-zero registers and are sliced off
        ring32 = jnp.pad(ring32, ((0, 0), (0, padded_rows - bank_rows), (0, 0)))
    out = _window.window_fold_max(
        ring32,
        mask.astype(jnp.int32),
        m=m,
        row_block=row_block,
        interpret=interpret,
    )
    return out[:bank_rows].astype(ring.dtype)


@register_window_backend("jnp")
def _jnp_window_backend(ring, mask, cfg: HLLConfig, plan: ExecutionPlan):
    return window_fold_jnp(ring, mask)


@register_window_backend("pallas")
def _pallas_window_backend(ring, mask, cfg: HLLConfig, plan: ExecutionPlan):
    # one datapath, widest row block under the VMEM cap
    return window_fold(ring, mask, interpret=plan.interpret)


@register_window_backend("pallas_pipelined")
def _pallas_pipelined_window_backend(
    ring, mask, cfg: HLLConfig, plan: ExecutionPlan
):
    # tile the fold over k pipelines: each grid block owns ceil(B/k)
    # sketches, still under the VMEM cell cap
    rows = ring.shape[1]
    row_block = max(1, -(-rows // plan.pipelines))
    _window = _window_kernel_module()
    row_block = min(row_block, max(1, _window.MAX_BLOCK_CELLS // cfg.m))
    return window_fold(ring, mask, row_block=row_block, interpret=plan.interpret)


# ----------------------------------------------------------------------------
# incremental window merges (K fold fragments -> one bank; DESIGN.md §14)
# ----------------------------------------------------------------------------


@jax.jit
def window_merge_jnp(parts: jnp.ndarray) -> jnp.ndarray:
    """Reference incremental merge: ONE max-reduce over the K fragments."""
    return jnp.max(parts, axis=0)


def window_merge(
    parts: jnp.ndarray,
    *,
    row_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas incremental merge: the window_merge_max kernel entry point.

    Same padding/dtype contract as ``window_fold`` — the (K, B, m) stack
    of fold fragments is row-block tiled under the VMEM cell cap, and the
    kernel sweeps the K axis (tiny, W-independent) with the ring fold's
    scratch accumulator.
    """
    _window = _window_kernel_module()
    interpret = _resolve_interpret(interpret)
    _, bank_rows, m = parts.shape
    if m > _window.MAX_BLOCK_CELLS:
        raise ValueError(
            f"pallas window merge supports m <= {_window.MAX_BLOCK_CELLS} "
            f"(p <= 12); use the jnp merge for m={m}"
        )
    if row_block is None:
        row_block = max(1, _window.MAX_BLOCK_CELLS // m)
    row_block = min(row_block, bank_rows)
    padded_rows = -(-bank_rows // row_block) * row_block
    parts32 = parts.astype(jnp.int32)
    if padded_rows != bank_rows:
        # phantom rows merge all-zero registers and are sliced off
        parts32 = jnp.pad(parts32, ((0, 0), (0, padded_rows - bank_rows), (0, 0)))
    out = _window.window_merge_max(
        parts32, m=m, row_block=row_block, interpret=interpret
    )
    return out[:bank_rows].astype(parts.dtype)


@register_window_merge_backend("jnp")
def _jnp_window_merge_backend(parts, cfg: HLLConfig, plan: ExecutionPlan):
    return window_merge_jnp(parts)


@register_window_merge_backend("pallas")
def _pallas_window_merge_backend(parts, cfg: HLLConfig, plan: ExecutionPlan):
    return window_merge(parts, interpret=plan.interpret)


@register_window_merge_backend("pallas_pipelined")
def _pallas_pipelined_window_merge_backend(
    parts, cfg: HLLConfig, plan: ExecutionPlan
):
    rows = parts.shape[1]
    row_block = max(1, -(-rows // plan.pipelines))
    _window = _window_kernel_module()
    row_block = min(row_block, max(1, _window.MAX_BLOCK_CELLS // cfg.m))
    return window_merge(parts, row_block=row_block, interpret=plan.interpret)


# ----------------------------------------------------------------------------
# HybridBank sparse dedup (append-buffer compaction; DESIGN.md §12)
# ----------------------------------------------------------------------------


def _sparse_kernel_module():
    from repro.kernels import sparse_scatter as _sparse

    assert _sparse.LANES == LANES
    return _sparse


# the jnp dedup picks its layout by stream-vs-bank size: below this fraction
# of the bank's rows*m cell count the O(n log n) sort wins, above it the
# O(n + rows*m) scatter does (measured crossover on CPU is ~cells/45; /32
# keeps a safety margin on the scatter side, whose cost is flat in n)
_SPARSE_CELLS_CROSSOVER = 32


def cell_space_fits(rows: int, m: int) -> bool:
    """True when flattened ``row * m + bucket`` cell ids fit int32.

    The TPU has no 64-bit datapath, so the layouts that form such ids (the
    dense-cells map and the Pallas ``sparse_scatter`` kernel) serve only
    banks below 2^31 cells; the (row, bucket) sort serves every size.
    """
    return rows * m < 1 << 31


def _check_cell_space(rows: int, m: int) -> None:
    """The guard of every layout that flattens (row, bucket) into one id:
    past 2^31 cells the int32 ids would silently wrap."""
    if not cell_space_fits(rows, m):
        raise ValueError(
            f"bank cell space B*m = {rows}*{m} overflows the int32 cell ids "
            f"of the flattened dedup layouts (dense cells, Pallas "
            f"sparse_scatter); the (row, bucket) sorted dedup serves banks "
            f"of this size"
        )


@partial(jax.jit, static_argnames=("rows",))
def sparse_merge_sorted(row, bucket, rank, *, rows):
    """Sorted-stream dedup: two stable argsorts, by (bucket, rank) then row.

    The first sort orders by ``bucket << 8 | rank`` (a row-local key, below
    2^24 at p <= 16), the second, stably, by row, so the stream ends up in
    (row, bucket, rank) order: within each equal (row, bucket) run ranks
    ascend and the LAST element carries the max.  Invalid entries
    (padding, out-of-range rows) take the sentinel row ``rows`` and sort
    to the end, where they never survive.  No ``row * m + bucket`` id is
    formed, so a bank's ``rows * m`` may pass 2^31.  Two single-key sorts,
    not one multi-key ``lax.sort``: the latter runs faster on a v5e but
    compiles about four times longer (PERF.md, section 6), and compaction
    meets new stream lengths inside a serving window.  Cost tracks the
    stream, not the bank — the right trade for small compactions.
    """
    valid = (row >= 0) & (row < rows)
    key = (bucket << 8) | rank
    order1 = jnp.argsort(key, stable=True)
    row1 = jnp.where(valid, row, rows)[order1]
    key1 = key[order1]
    order2 = jnp.argsort(row1, stable=True)
    row_s, key_s = row1[order2], key1[order2]
    bucket_s, rank_s = key_s >> 8, key_s & 0xFF
    is_last = jnp.concatenate(
        [
            (row_s[1:] != row_s[:-1]) | (bucket_s[1:] != bucket_s[:-1]),
            jnp.ones((1,), bool),
        ]
    )
    survivor = is_last & (row_s < rows)
    distinct = jnp.bincount(jnp.where(survivor, row_s, rows), length=rows + 1)[
        :rows
    ]
    return row_s, bucket_s, rank_s, survivor, distinct.astype(jnp.int32)


@partial(jax.jit, static_argnames=("rows", "m"))
def sparse_merge_cells(row, bucket, rank, *, rows, m):
    """Dense-cells dedup: ONE segment-max over ``row * m + bucket`` cells.

    The same fused scatter as ``bank_update_jnp``, landing in a zeroed
    (rows, m) max-rank map instead of live registers; per-row distinct
    counts fall out of one popcount over the map.  Cost is O(n + rows*m)
    flat in the stream — the right trade once the stream rivals the bank.
    Banks below 2^31 cells only (``_check_cell_space``).
    """
    _check_cell_space(rows, m)
    valid = (row >= 0) & (row < rows)
    seg = jnp.where(valid, row * m + bucket, rows * m)
    cells = jax.ops.segment_max(
        jnp.where(valid, rank, 0).astype(jnp.int32),
        seg,
        num_segments=rows * m + 1,
    )[: rows * m].reshape(rows, m)
    # segment_max fills untouched segments with INT32_MIN; the cells
    # contract is "0 = empty" (what the pallas kernel's zeroed scratch
    # produces), so clamp before anything scans for nonzero cells
    cells = jnp.maximum(cells, 0)
    distinct = jnp.sum(cells > 0, axis=1, dtype=jnp.int32)
    return cells, distinct


def sparse_merge(
    row,
    bucket,
    rank,
    rows: int,
    cfg: HLLConfig,
    *,
    row_block: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Pallas sparse dedup: the sparse_scatter kernel over COO row blocks.

    The triple stream tiles like every other kernel stream; padding and
    out-of-range rows are masked to rank 0 (the bucket-max identity), never
    clamped into a neighbor.  The kernel keeps each row block's
    ``row_block * m`` pair cells VMEM-resident for the whole sweep and
    flushes per-row distinct counts alongside the deduped map, so promotion
    detection costs no second pass.  Small-m banks only (the hll_fused
    trade); the default row_block is the widest under the VMEM cell cap.
    """
    _sparse = _sparse_kernel_module()
    interpret = _resolve_interpret(interpret)
    m = cfg.m
    _check_cell_space(rows, m)
    if m > _sparse.MAX_BLOCK_CELLS:
        raise ValueError(
            f"pallas sparse dedup supports m <= {_sparse.MAX_BLOCK_CELLS} "
            f"(p <= 12); use the jnp dedup path for m={m}"
        )
    flat_row = jnp.asarray(row).reshape(-1).astype(jnp.int32)
    valid = (flat_row >= 0) & (flat_row < rows)
    tile_items = _sparse.DEFAULT_BLOCK_ROWS * LANES
    keys_t, _ = _pad_to_tiles(jnp.where(valid, flat_row, 0), tile_items)
    idx_t, _ = _pad_to_tiles(
        jnp.where(valid, jnp.asarray(bucket).reshape(-1), 0).astype(jnp.int32),
        tile_items,
    )
    rank_t, _ = _pad_to_tiles(
        jnp.where(valid, jnp.asarray(rank).reshape(-1), 0).astype(jnp.int32),
        tile_items,
    )
    if row_block is None:
        row_block = max(1, _sparse.MAX_BLOCK_CELLS // m)
    row_block = min(row_block, rows)
    padded_rows = -(-rows // row_block) * row_block
    cells, distinct = _sparse.sparse_scatter_coo(
        keys_t,
        idx_t,
        rank_t,
        rows=padded_rows,
        m=m,
        row_block=row_block,
        interpret=interpret,
    )
    # phantom padding rows receive nothing (keys < rows) and are sliced off
    return cells[:rows], distinct[:rows]


@register_sparse_backend("jnp")
def _jnp_sparse_backend(row, bucket, rank, rows, cfg: HLLConfig, plan: ExecutionPlan):
    m = cfg.m
    n = row.shape[0]
    if cell_space_fits(rows, m) and n * _SPARSE_CELLS_CROSSOVER >= rows * m:
        cells, distinct = sparse_merge_cells(row, bucket, rank, rows=rows, m=m)
        return SparseDedup(distinct=distinct, cells=cells)
    row_s, bucket_s, rank_s, survivor, distinct = sparse_merge_sorted(
        row, bucket, rank, rows=rows
    )
    return SparseDedup(
        distinct=distinct,
        row_s=row_s,
        bucket_s=bucket_s,
        rank_s=rank_s,
        survivor=survivor,
    )


@register_sparse_backend("pallas")
def _pallas_sparse_backend(
    row, bucket, rank, rows, cfg: HLLConfig, plan: ExecutionPlan
):
    # one datapath, widest row block under the VMEM cap
    cells, distinct = sparse_merge(
        row, bucket, rank, rows, cfg, interpret=plan.interpret
    )
    return SparseDedup(distinct=distinct, cells=cells)


@register_sparse_backend("pallas_pipelined")
def _pallas_pipelined_sparse_backend(
    row, bucket, rank, rows, cfg: HLLConfig, plan: ExecutionPlan
):
    # tile the dedup over k pipelines: each grid block owns ceil(B/k) rows,
    # still under the VMEM cell cap
    row_block = max(1, -(-rows // plan.pipelines))
    _sparse = _sparse_kernel_module()
    row_block = min(row_block, max(1, _sparse.MAX_BLOCK_CELLS // cfg.m))
    cells, distinct = sparse_merge(
        row, bucket, rank, rows, cfg, row_block=row_block, interpret=plan.interpret
    )
    return SparseDedup(distinct=distinct, cells=cells)


# ----------------------------------------------------------------------------
# CountMinBank paths (keyed scatter-add + gather-min; DESIGN.md §13)
# ----------------------------------------------------------------------------


def _cm_kernel_module():
    from repro.kernels import cm_scatter as _cms

    assert _cms.LANES == LANES
    return _cms


def _cm_module():
    # lazy for the same reason as the kernel modules: countmin pulls in the
    # bank/window carriers, which must not load mid-way through this module
    from repro.sketch import countmin as _cm

    return _cm


@partial(jax.jit, static_argnames=("cfg",))
def cm_update_jnp(
    counters: jnp.ndarray,
    keys: jnp.ndarray,
    items: jnp.ndarray,
    cfg,
) -> jnp.ndarray:
    """Reference cm ingest: ONE segment-sum over (key, depth, column) cells.

    Item i with key b lands d increments, at flattened cells
    ``b*d*w + r*w + idx_r(i)`` — the bank_update_jnp offset trick with the
    depth lane folded into the cell id, so the whole (B, d, w) bank
    ingests a keyed stream with a single fused scatter-add.  Out-of-range
    keys route to a discarded trailing segment (the §9 drop rule; never
    clamped into a neighboring row).  Counters wrap mod 2^32 by uint32
    arithmetic.  Like the HLL bank, the flattened cell space must fit
    int32 segment ids: B*d*w >= 2^31 is rejected loudly.
    """
    _cm = _cm_module()
    rows, depth, width = counters.shape
    cells = depth * width
    if rows * cells >= 1 << 31:
        raise ValueError(
            f"cm cell space B*d*w = {rows}*{depth}*{width} overflows int32 "
            f"segment ids; split the fleet across multiple banks or shards"
        )
    idx = _cm.cm_hash_index(items, cfg)  # (d, n)
    valid = (keys >= 0) & (keys < rows)
    lane = jnp.arange(depth, dtype=jnp.int32)[:, None] * width
    seg = jnp.where(
        valid[None, :], keys[None, :] * cells + lane + idx, rows * cells
    ).reshape(-1)
    hits = jnp.broadcast_to(
        valid.astype(counters.dtype)[None, :], idx.shape
    ).reshape(-1)
    delta = jax.ops.segment_sum(hits, seg, num_segments=rows * cells + 1)
    return counters + delta[: rows * cells].reshape(rows, depth, width)


@partial(jax.jit, static_argnames=("cfg",))
def cm_query_jnp(
    counters: jnp.ndarray, items: jnp.ndarray, cfg
) -> jnp.ndarray:
    """Reference cm point query: gather d cells per (row, item), min-reduce.

    Returns (B, n) estimated counts — the classical count-min upper
    bound.  One fused gather + reduce; there is no Pallas flavor because
    a gather-min has no scatter hazard to fuse away, so every backend
    pair shares this query.
    """
    _cm = _cm_module()
    rows, depth, width = counters.shape
    idx = _cm.cm_hash_index(items, cfg)  # (d, n)
    r = jnp.broadcast_to(jnp.arange(depth, dtype=jnp.int32)[:, None], idx.shape)
    gathered = counters[:, r, idx]  # (B, d, n)
    return jnp.min(gathered, axis=1)


def cm_update(
    counters: jnp.ndarray,
    keys: jnp.ndarray,
    items: jnp.ndarray,
    cfg,
    *,
    row_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas cm ingest: d-expanded stream through the cm_scatter kernel.

    The d column indices per item come from the one-murmur double-hash
    (shared with the jnp path, so routing is bit-identical); the stream is
    then expanded d-fold into (key, cell, hit) triples and summed into
    ``row_block`` whole (d, w) counter slabs held VMEM-resident per grid
    step, exactly as ``bank_update`` tiles the HLL bank.  Padding and
    foreign keys are masked to hit 0 (the additive identity), never
    clamped into a neighbor.  Counters are bitcast uint32<->int32 around
    the kernel: int32 two's-complement adds are bit-identical to uint32
    mod-2^32 adds.  Small-slab banks only (d*w under the VMEM cell cap).
    """
    _cms = _cm_kernel_module()
    _cm = _cm_module()
    interpret = _resolve_interpret(interpret)
    rows, depth, width = counters.shape
    cells = depth * width
    if cells > _cms.MAX_BLOCK_CELLS:
        raise ValueError(
            f"pallas cm ingest supports d*w <= {_cms.MAX_BLOCK_CELLS}; use "
            f"the jnp scatter path for d*w={cells}"
        )
    flat_keys = keys.reshape(-1).astype(jnp.int32)
    flat_items = items.reshape(-1)
    valid = (flat_keys >= 0) & (flat_keys < rows)
    idx = _cm.cm_hash_index(flat_items, cfg)  # (d, n)
    keys_d = jnp.broadcast_to(flat_keys[None, :], idx.shape)
    col_d = jnp.arange(depth, dtype=jnp.int32)[:, None] * width + idx
    val_d = jnp.broadcast_to(valid[None, :], idx.shape)
    # same drop rule as the jnp path: foreign keys mask to hit 0 aimed at
    # cell 0 of row 0 — a no-op under the cell sum
    keys_d = jnp.where(val_d, keys_d, 0).reshape(-1)
    col_d = jnp.where(val_d, col_d, 0).reshape(-1)
    val_d = val_d.astype(jnp.int32).reshape(-1)
    tile_items = _cms.DEFAULT_BLOCK_ROWS * LANES
    keys_t, _ = _pad_to_tiles(keys_d, tile_items)
    col_t, _ = _pad_to_tiles(col_d, tile_items)
    val_t, _ = _pad_to_tiles(val_d, tile_items)

    if row_block is None:
        row_block = max(1, _cms.MAX_BLOCK_CELLS // cells)
    row_block = min(row_block, rows)
    padded_rows = -(-rows // row_block) * row_block
    cnt32 = jax.lax.bitcast_convert_type(counters, jnp.int32).reshape(rows, cells)
    if padded_rows != rows:
        # phantom rows receive nothing (keys < rows) and are sliced off
        cnt32 = jnp.pad(cnt32, ((0, padded_rows - rows), (0, 0)))
    out = _cms.cm_scatter_add(
        cnt32,
        keys_t,
        col_t,
        val_t,
        cells_per_row=cells,
        row_block=row_block,
        interpret=interpret,
    )
    out = out[:rows].reshape(rows, depth, width)
    return jax.lax.bitcast_convert_type(out, counters.dtype)


@jax.jit
def cm_window_fold_jnp(ring: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Reference cm ring fold: ONE masked SUM-reduce over the W axis.

    Expired/unselected buckets fold as all-zero counters (0 is the
    identity of the cell sum), so any suffix window is bit-identical to
    summing its live buckets one by one.  uint32 arithmetic wraps.
    """
    masked = jnp.where(mask[:, None, None, None], ring, jnp.zeros_like(ring))
    return jnp.sum(masked, axis=0, dtype=ring.dtype)


def cm_window_fold(
    ring: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    row_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Pallas cm ring fold: the cm_window_fold_sum kernel over row blocks.

    The fourth sibling of ``window_fold`` — same (W, B, ·) sweep with a
    VMEM scratch accumulator, + replacing max.  Counters are bitcast
    uint32<->int32 around the kernel (two's-complement adds are exact mod
    2^32).  Small-slab banks only (d*w under the VMEM cell cap).
    """
    _cms = _cm_kernel_module()
    interpret = _resolve_interpret(interpret)
    window, rows, depth, width = ring.shape
    cells = depth * width
    if cells > _cms.MAX_BLOCK_CELLS:
        raise ValueError(
            f"pallas cm window fold supports d*w <= {_cms.MAX_BLOCK_CELLS}; "
            f"use the jnp fold for d*w={cells}"
        )
    if row_block is None:
        row_block = max(1, _cms.MAX_BLOCK_CELLS // cells)
    row_block = min(row_block, rows)
    padded_rows = -(-rows // row_block) * row_block
    ring32 = jax.lax.bitcast_convert_type(ring, jnp.int32).reshape(
        window, rows, cells
    )
    if padded_rows != rows:
        # phantom rows fold all-zero counters and are sliced off
        ring32 = jnp.pad(ring32, ((0, 0), (0, padded_rows - rows), (0, 0)))
    out = _cms.cm_window_fold_sum(
        ring32,
        mask.astype(jnp.int32),
        cells_per_row=cells,
        row_block=row_block,
        interpret=interpret,
    )
    out = out[:rows].reshape(rows, depth, width)
    return jax.lax.bitcast_convert_type(out, ring.dtype)


def _jnp_cm_ingest(counters, keys, items, cfg, plan: ExecutionPlan):
    # the scatter-add is already one fused op; `pipelines` has no fold to
    # parallelize, exactly as in bank_update_jnp
    return cm_update_jnp(counters, keys, items, cfg)


def _jnp_cm_query(counters, items, cfg, plan: ExecutionPlan):
    return cm_query_jnp(counters, items, cfg)


def _pallas_cm_ingest(counters, keys, items, cfg, plan: ExecutionPlan):
    # one datapath, widest row block under the VMEM cap
    return cm_update(counters, keys, items, cfg, interpret=plan.interpret)


def _pallas_pipelined_cm_ingest(counters, keys, items, cfg, plan: ExecutionPlan):
    # tile the bank over k pipelines (paper Fig. 3 applied to rows): each
    # grid block owns ceil(B/k) sketches, still under the VMEM cell cap
    rows, depth, width = counters.shape
    row_block = max(1, -(-rows // plan.pipelines))
    _cms = _cm_kernel_module()
    row_block = min(row_block, max(1, _cms.MAX_BLOCK_CELLS // (depth * width)))
    return cm_update(
        counters, keys, items, cfg, row_block=row_block, interpret=plan.interpret
    )


# the query side is the same fused gather-min everywhere: a gather has no
# scatter hazard for a Pallas kernel to fuse away
register_cm_backend("jnp", _jnp_cm_ingest, _jnp_cm_query)
register_cm_backend("pallas", _pallas_cm_ingest, _jnp_cm_query)
register_cm_backend("pallas_pipelined", _pallas_pipelined_cm_ingest, _jnp_cm_query)


@register_cm_window_backend("jnp")
def _jnp_cm_window_backend(ring, mask, cfg, plan: ExecutionPlan):
    return cm_window_fold_jnp(ring, mask)


@register_cm_window_backend("pallas")
def _pallas_cm_window_backend(ring, mask, cfg, plan: ExecutionPlan):
    # one datapath, widest row block under the VMEM cap
    return cm_window_fold(ring, mask, interpret=plan.interpret)


@register_cm_window_backend("pallas_pipelined")
def _pallas_pipelined_cm_window_backend(ring, mask, cfg, plan: ExecutionPlan):
    # tile the fold over k pipelines: each grid block owns ceil(B/k)
    # sketches, still under the VMEM cell cap
    rows, depth, width = ring.shape[1], ring.shape[2], ring.shape[3]
    row_block = max(1, -(-rows // plan.pipelines))
    _cms = _cm_kernel_module()
    row_block = min(row_block, max(1, _cms.MAX_BLOCK_CELLS // (depth * width)))
    return cm_window_fold(
        ring, mask, row_block=row_block, interpret=plan.interpret
    )
