"""The single aggregation entry point: update_registers(regs, items, cfg, plan).

One call replaces the five historical surfaces (core.hll.update,
core.sketch.update_pipelined / update_sharded / datapath_tap and
kernels.ops.hll_update / pipelined_update): the ``ExecutionPlan`` chooses the
backend and placement, and every plan yields bit-identical registers on the
same stream (DESIGN.md §3).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import shard_map
from repro.obs import metrics as obs_metrics
from repro.sketch import hll
from repro.sketch.backends import cell_space_fits
from repro.sketch.hll import HLLConfig
from repro.sketch.plan import (
    DEFAULT_PLAN,
    ExecutionPlan,
    get_backend,
    get_sparse_backend,
)


def mesh_fold(plan: ExecutionPlan, registers, arrays, apply_fn):
    """The mesh placement rule, shared by sketch and bank dispatch.

    ``arrays`` is a tuple of equal-length flat streams (the item stream;
    or the key + item streams for a bank, DESIGN.md §9).  Each is sharded
    over ``plan.data_axes``; every device applies ``apply_fn(registers,
    *local_arrays)`` to its shard and one lax.pmax folds the partial
    register states — the paper's Merge-buckets module as a single
    collective.  Registers come back replicated.  Streams that do not
    divide the mesh axes are edge-padded: zero-padding would sketch
    phantom elements, while repeating a real element (or (key, item)
    pair) cannot move any register — the lattice is idempotent
    (DESIGN.md §6) — so no plan ever raises on stream length.
    """
    axes = plan.data_axes
    shards = 1
    for a in axes:
        shards *= plan.mesh.shape[a]
    n = arrays[0].shape[0]
    padded = -(-n // shards) * shards
    if padded != n:
        arrays = tuple(
            jnp.pad(x, (0, padded - n), mode="edge") for x in arrays
        )

    def local(regs, *local_arrays):
        return jax.lax.pmax(apply_fn(regs, *local_arrays), axes)

    in_specs = (P(),) + (P(axes),) * len(arrays)
    return shard_map(
        local, mesh=plan.mesh, in_specs=in_specs, out_specs=P()
    )(registers, *arrays)


def _shard_count(plan: ExecutionPlan) -> int:
    shards = 1
    for a in plan.data_axes:
        shards *= plan.mesh.shape[a]
    return shards


def _block_index(plan: ExecutionPlan):
    """This device's row-block index: the flattened position over
    ``plan.data_axes`` in the same row-major order ``P(axes)`` shards by."""
    idx = jax.lax.axis_index(plan.data_axes[0])
    for a in plan.data_axes[1:]:
        idx = idx * plan.mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _spec_at(axes, dim: int, rank: int):
    """A PartitionSpec sharding dimension ``dim`` of a rank-``rank`` array
    over ``axes``, replicating every other dimension."""
    entries = [None] * rank
    entries[dim] = axes
    return P(*entries)


def row_shard_fold(plan: ExecutionPlan, registers, keys, arrays, apply_fn):
    """The sharded placement rule for keyed bank ingest (DESIGN.md §16).

    ``registers`` is a (B, ...) bank whose ROW axis splits into contiguous
    blocks over ``plan.data_axes``; ``keys`` and the ``arrays`` streams are
    replicated to every device.  Each device re-bases the key stream into
    block-local coordinates (``key - block_start``) and applies
    ``apply_fn(block, local_keys, *local_arrays)``: keys owned by another
    device fall outside [0, block_rows) and the §9 drop rule discards
    them, so cross-device key ROUTING is the drop rule itself — no
    gather, scatter, or collective moves a register.  Row counts that do
    not divide the shard count pad with phantom rows (valid keys are
    < B by the same rule, so nothing can land in them) and slice back.
    The union of the blocks is exactly one local update: bit-identity to
    placement="local" holds by construction, not by a fold.
    """
    shards = _shard_count(plan)
    rows = registers.shape[0]
    padded = -(-rows // shards) * shards
    regs = registers
    if padded != rows:
        regs = jnp.pad(
            registers, [(0, padded - rows)] + [(0, 0)] * (registers.ndim - 1)
        )
    out = _sharded_fold_callable(
        apply_fn, plan, padded // shards, regs.ndim, len(arrays)
    )(regs, keys, *arrays)
    return out[:rows] if padded != rows else out


@functools.lru_cache(maxsize=512)
def _sharded_fold_callable(apply_fn, plan, block_rows, rank, n_arrays):
    """The jitted shard-mapped ingest for one (fn, plan, geometry) key.

    Eager ``shard_map`` re-traces on every call when handed a fresh
    closure, which turns the serve loop's once-per-tick dispatch into a
    once-per-tick recompile.  Caching here keeps the serve path's steady
    state at one compile per shape; it only works because call sites
    pass IDENTITY-STABLE apply functions (themselves lru_cached on the
    values they close over) rather than inline lambdas.
    """

    def local(block, ks, *rest):
        return apply_fn(block, ks - _block_index(plan) * block_rows, *rest)

    in_specs = (_spec_at(plan.data_axes, 0, rank),) + (P(),) * (1 + n_arrays)
    return jax.jit(
        shard_map(
            local,
            mesh=plan.mesh,
            in_specs=in_specs,
            out_specs=_spec_at(plan.data_axes, 0, rank),
        )
    )


def row_shard_apply(plan: ExecutionPlan, fn, arrays, in_dims, out_dim: int = 0):
    """Apply a ROW-INDEPENDENT map block-wise under the sharded placement.

    The read-side companion of :func:`row_shard_fold`: ``fn`` maps each
    array's row block to a per-row result (batched estimate finalization,
    window ring folds — anything with no cross-row dataflow), so running
    it per block and concatenating is bit-identical to the unsharded
    call.  ``in_dims[i]`` names the row dimension of ``arrays[i]`` (None
    replicates the whole array); the output's row dimension is
    ``out_dim``.  Non-divisible row counts pad with phantom zero rows —
    inert under every row-wise map here — and slice back.
    """
    shards = _shard_count(plan)
    rows = next(
        a.shape[d] for a, d in zip(arrays, in_dims) if d is not None
    )
    padded = -(-rows // shards) * shards
    staged = []
    for a, d in zip(arrays, in_dims):
        if d is not None and padded != rows:
            pad = [(0, 0)] * a.ndim
            pad[d] = (0, padded - rows)
            a = jnp.pad(a, pad)
        staged.append(a)
    out_rank = jax.eval_shape(fn, *staged).ndim  # abstract: no FLOPs
    out = _sharded_apply_callable(
        fn,
        plan,
        tuple(in_dims),
        out_dim,
        tuple(a.ndim for a in staged),
        out_rank,
    )(*staged)
    if padded != rows:
        out = jax.lax.slice_in_dim(out, 0, rows, axis=out_dim)
    return out


@functools.lru_cache(maxsize=512)
def _sharded_apply_callable(fn, plan, in_dims, out_dim, ranks, out_rank):
    """Jitted shard-mapped row map, cached like the fold companion."""
    in_specs = tuple(
        _spec_at(plan.data_axes, d, r) if d is not None else P()
        for d, r in zip(in_dims, ranks)
    )
    return jax.jit(
        shard_map(
            fn,
            mesh=plan.mesh,
            in_specs=in_specs,
            out_specs=_spec_at(plan.data_axes, out_dim, out_rank),
        )
    )


def cm_mesh_sum(plan: ExecutionPlan, counters, arrays, apply_fn):
    """The mesh placement rule for ADDITIVE sketch state (count-min).

    ``mesh_fold`` edge-pads non-divisible streams because repeating a
    (key, item) pair cannot move a max-lattice register — but under a sum
    it would double-count.  Here padding fills the key stream with -1
    instead, which the §9 drop rule discards on every backend.  Each
    device ingests its shard into a ZERO counter bank, one lax.psum folds
    the per-device deltas, and the delta lands on the incoming counters
    exactly once, outside the collective.
    """
    axes = plan.data_axes
    shards = 1
    for a in axes:
        shards *= plan.mesh.shape[a]
    n = arrays[0].shape[0]
    padded = -(-n // shards) * shards
    if padded != n:
        keys, rest = arrays[0], arrays[1:]
        arrays = (jnp.pad(keys, (0, padded - n), constant_values=-1),) + tuple(
            jnp.pad(x, (0, padded - n)) for x in rest
        )
    zeros = jnp.zeros(counters.shape, counters.dtype)

    def local(z, *local_arrays):
        return jax.lax.psum(apply_fn(z, *local_arrays), axes)

    in_specs = (P(),) + (P(axes),) * len(arrays)
    delta = shard_map(
        local, mesh=plan.mesh, in_specs=in_specs, out_specs=P()
    )(zeros, *arrays)
    return counters + delta


def update_registers(
    registers: jnp.ndarray,
    items: jnp.ndarray,
    cfg: HLLConfig,
    plan: Optional[ExecutionPlan] = None,
) -> jnp.ndarray:
    """Aggregate ``items`` into ``registers`` under ``plan`` (Phase 3).

    placement="local": the backend runs on the caller's device(s) as-is.
    placement="mesh":  ``items`` is flattened and sharded over
    ``plan.data_axes`` through :func:`mesh_fold` (per-device aggregation
    + one all-reduce-max; edge-padding for non-divisible streams).
    placement="sharded" degrades to the mesh rule here: a single sketch
    has no row axis to split, and stream-sharding is bit-identical to
    local by the same lattice laws (DESIGN.md §16).
    """
    plan = (DEFAULT_PLAN if plan is None else plan).validate()
    backend = get_backend(plan.backend)
    flat = items.reshape(-1)
    if flat.shape[0] == 0:
        # an empty stream cannot move a register: skip the dispatch entirely
        # (skips are counted so the no-dispatch contract stays observable)
        obs_metrics.inc("dispatch.update.skipped_empty")
        return registers
    obs_metrics.observe("update.batch_items", flat.shape[0])
    if plan.placement == "local":
        return backend(registers, items, cfg, plan)
    return mesh_fold(
        plan, registers, (flat,), lambda regs, x: backend(regs, x, cfg, plan)
    )


def dedup_pairs(
    row: jnp.ndarray,
    bucket: jnp.ndarray,
    rank: jnp.ndarray,
    rows: int,
    cfg: HLLConfig,
    plan: Optional[ExecutionPlan] = None,
):
    """Dedup a (row, bucket, rank) triple stream under ``plan`` (DESIGN.md §12).

    The HybridBank compaction's dispatch seam, mirroring
    :func:`update_registers`: the sparse-capable backend registered under
    ``plan.backend`` (jnp adaptive sort/scatter, or the sparse_scatter
    Pallas kernel) collapses the combined live-pair + append-buffer stream
    to each row's distinct bucket -> max-rank map and per-row distinct
    counts, returned as a :class:`repro.sketch.plan.SparseDedup`.  The
    dedup runs on the device of its inputs regardless of ``placement`` —
    compaction consumes host-resident COO state, and under the sharded
    placement each row block compacts on its own device (DESIGN.md §16).
    A bank whose ``rows * m`` reaches 2^31 ("wide") always takes the jnp
    (row, bucket) sort, the one layout that forms no flattened cell id.
    A backend with no sparse registration (e.g. a custom bank backend)
    falls back to the jnp dedup: every sparse path is bit-identical by
    contract, so the fallback cannot change the compacted state.
    """
    plan = (DEFAULT_PLAN if plan is None else plan).validate()
    if not cell_space_fits(rows, cfg.m):
        obs_metrics.inc("sparse.dedup.wide")
        backend = get_sparse_backend("jnp")
    else:
        try:
            backend = get_sparse_backend(plan.backend)
        except ValueError:
            obs_metrics.inc("dispatch.sparse_dedup.fallback")
            backend = get_sparse_backend("jnp")
    return backend(row, bucket, rank, rows, cfg, plan)


def datapath_tap(
    registers: jnp.ndarray, token_ids: jnp.ndarray, cfg: HLLConfig
) -> jnp.ndarray:
    """Sketch-on-the-datapath inside a jitted step (NIC analogue, DESIGN.md §2).

    Called from train_step/serve_step on tokens already resident on device;
    under pjit the segment_max partials and the replicated-output max-reduce
    are inserted by SPMD partitioning automatically.  Costs O(tokens) VPU
    ops + one (m,)-sized all-reduce — negligible next to model FLOPs.
    Equivalent to ``update_registers`` with the single-pipeline jnp plan.
    """
    return hll.update(registers, token_ids, cfg)
