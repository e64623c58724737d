"""Sparse tenant-row storage with automatic dense promotion (DESIGN.md §12).

The paper's premise is sub-linear memory on the input domain, yet a
``SketchBank`` allocates a dense (B, m) register block no matter how empty
its rows are — at "millions of users" scale most tenant rows hold a
handful of distinct items and waste ~m bytes each.  HyperLogLogLog
(arXiv:2205.11327) and the memory-efficient FPGA sketch follow-up
(arXiv:2504.16896) both show compressed/sparse register storage preserves
estimate quality while cutting memory by an order of magnitude; this
module is that idea over the bank subsystem of DESIGN.md §9.

A ``HybridBank`` keeps every row in one of two representations:

* **sparse** — the row's distinct ``(bucket_idx, rank)`` pairs, packed as
  ``bucket << 8 | rank`` int32 values in a capped per-row COO buffer of
  shape (B, C).  C adapts to the actual occupancy of the sparse rows
  (grown/shrunk at compaction), so near-empty tenants cost a few dozen
  bytes instead of m.
* **dense** — the usual (m,) uint8 register row, held in a compact
  (D, m) block that only promoted rows occupy (``slot_map`` maps row ->
  block slot, -1 for sparse rows).

**Promotion contract.** A row is promoted exactly when its distinct-bucket
count crosses ``threshold`` (default m // 4): sparse rows always satisfy
``len <= threshold``.  Promotion materializes the row's full
bucket -> max-rank map, so a promoted row's registers are **bit-identical**
to dense-from-scratch ingestion of the same stream, and estimates cannot
shift at the boundary (tests/test_sparse.py).  Promotion is one-way;
``merge`` keeps dense mode infectious (a row dense on either side stays
dense).

**Amortized ingest (append buffer + deferred compaction).**
``update_many(keys, items, plan)`` routes the whole keyed stream in one
pass with no python loop over rows: dense-destined items dispatch through
the registered bank backend of ``plan`` (the §9 scatter — jnp or the
Pallas bank kernel) at power-of-two padded shapes, so one executable
serves many ticks, while sparse-destined items land in a per-bank
**append buffer** of raw (row, item) entries with NO dedup — an O(new)
append, so steady-state ingest cost tracks new pairs instead of all live
pairs.  Dedup runs as a **compaction** step only under capacity pressure
(the buffer outgrowing ``max(_FLUSH_MIN_PAIRS, _FLUSH_FACTOR * live)``)
or before any read — every estimate / serialize / merge / to_dense /
introspection surface settles the bank first, so deferral is invisible:
compacted state is bit-identical to eagerly deduplicating every batch
(the register lattice is an associative, commutative, idempotent max).
Compaction hashes the buffered items once (pow2-padded, jitted), re-emits
the live COO pairs as triples, and dispatches the combined stream through
the **sparse backend registry** (``register_sparse_backend`` /
``dedup_pairs``): the jnp entry picks sort-merge or segment-max scatter by
stream-vs-bank size, the pallas entries run the ``sparse_scatter`` kernel
(VMEM-resident pair tiles per COO row block) — all bit-identical.  The §9
key-routing contract holds unchanged: out-of-range keys are dropped,
never buffered, and never counted.

**Estimation.** ``estimate_many`` finalizes sparse rows with the
linear-counting fast path: a sparse row has at most ``threshold <= m/2``
non-zero registers, which provably pins the ``original`` estimator to its
small-range LinearCounting branch (E_raw <= 2*alpha*m < 2.5m and V > 0),
so ``m * log(m / (m - len))`` is bit-identical to the dense device path
while reading only the per-row pair count.  Other registered estimators
build the (B, K) register histogram straight from the pairs
(C[0] = m - len) and run their normal device finalizer — also
bit-identical to the dense path, because the histogram is.

**Wire format v2.** ``to_bytes`` reuses the RHLB framing with
``version=2``: header + u32 threshold + per-row u64 counts + per-row mode
flags + per-row payloads (dense rows: m register bytes; sparse rows: u16
pair count + sorted (u16 bucket, u8 rank) pairs).  ``from_bytes`` parses
v2 strictly (mode flags, pair ordering, rank ranges, exact length) and
still accepts v1 dense blobs — version-gated, producing an all-dense
hybrid — while ``SketchBank.from_bytes`` keeps rejecting v2 with a
targeted error.  Serialization always writes the compacted state: the
append buffer is transient and never hits the wire.

**Row blocks under the sharded placement (DESIGN.md §16).** Given a
``placement="sharded"`` plan over more than one device, ``update_many``
splits the bank into contiguous tenant-row blocks, one per device of
``plan.data_axes`` (``block_rows = ceil(B / devices)``, the last block
holding the rest), and from then on the bank's per-row state lives in
``blocks``: each is a local ``HybridBank`` whose arrays sit on its own
device, with its own append buffer, pressure compaction, promotions and
dense block.  Each tick is split on the host by block (keys re-based by
``block_index * block_rows``; keys outside [0, B) reach no block — the §9
drop rule), and every per-block call runs with that block's device as
JAX's default, so compaction, dedup and the dense scatter run beside the
block's state.  Rows never interact, so every read (estimates, registers,
counters, modes, the wire format) is the concatenation of the blocks'
reads, bit-identical to the same ops under a local plan.  A bank that is
not split (a one-device mesh, or no ingest yet) runs every phase on its
own device under the local form of the plan.  A bank's cell
space ``B * m`` may pass 2^31: the dedup keys on (row, bucket) with no
flattened id (``backends.sparse_merge_sorted``), and the layouts that do
flatten (dense cells, the Pallas kernel) are only picked below 2^31.

``HybridBank`` is host-orchestrated (promotion reshapes the dense block),
so unlike ``SketchBank`` it is NOT a jit-traceable pytree; the fused
device work happens inside the jitted dedup/scatter kernels behind
``dedup_pairs``.
"""

from __future__ import annotations

import dataclasses
import struct
import threading
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as obs_metrics
from repro.obs.metrics import to_host
from repro.obs.tracing import span
from repro.sketch import hll, u64 as u64lib
from repro.sketch.bank import (
    _BANK_HEADER,
    _BANK_MAGIC,
    _ROW_COUNT,
    SketchBank,
    _counter_add_rows,
    update_bank_registers,
)
from repro.sketch.carrier import HyperLogLog
from repro.sketch.dispatch import _shard_count, dedup_pairs
from repro.sketch.hll import HLLConfig
from repro.sketch.plan import DEFAULT_PLAN, ExecutionPlan, SparseDedup

_PACK_SHIFT = 8  # packed pair = bucket << 8 | rank (rank <= 61 fits a byte)
_PACK_MASK = (1 << _PACK_SHIFT) - 1
_EMPTY = -1  # empty-slot sentinel in the packed pair buffer
_SPARSE_VERSION = 2
_THRESHOLD = struct.Struct("<I")
_NPAIRS = struct.Struct("<H")
_PAIR = struct.Struct("<HB")
MODE_SPARSE, MODE_DENSE = 0, 1

# Append-buffer pressure policy (DESIGN.md §12): a compaction is forced from
# inside update_many only once the buffered raw pairs pass BOTH floors —
# an absolute floor (below it the buffer is cheap: 8 bytes/pair of host
# memory, nothing device-resident) and a multiple of the live deduped pairs
# (so each compaction ingests at least _FLUSH_FACTOR times the pairs it
# re-sorts, keeping total compaction work O(total appends) — the classic
# amortized-doubling argument).  Reads never see the buffer: every
# estimate/serialize/merge/introspection surface compacts first.
_FLUSH_MIN_PAIRS = 1 << 22
_FLUSH_FACTOR = 4


def default_threshold(cfg: HLLConfig) -> int:
    """The default promotion threshold: m // 4 distinct buckets."""
    return max(1, cfg.m // 4)


def _check_threshold(threshold: int, cfg: HLLConfig) -> int:
    """Thresholds above m // 2 would leave the LC-regime guarantee (the
    proof in the module docstring needs V = m - len >= m/2)."""
    threshold = int(threshold)
    if not 1 <= threshold <= max(1, cfg.m // 2):
        raise ValueError(
            f"sparse threshold must be in [1, {max(1, cfg.m // 2)}] "
            f"(m // 2 keeps sparse rows in the LinearCounting regime), "
            f"got {threshold}"
        )
    return threshold


def _pending_pressure(pending, pair_len) -> bool:
    """True once the buffer passes both flush floors (module note)."""
    if pending is None or pending.total < _FLUSH_MIN_PAIRS:
        return False
    live = int(to_host(pair_len, np.int64).sum())
    return pending.total >= max(_FLUSH_MIN_PAIRS, _FLUSH_FACTOR * live)


def _pow2_bucket(n: int, floor: int = 6) -> int:
    """Smallest power of two >= ``n`` and >= 2^floor: the jit-shape buckets
    that bound recompiles of every padded dispatch in this module."""
    return 1 << max(floor, (n - 1).bit_length())


def _bytes_limit(device) -> Optional[int]:
    """The bytes ``device`` reports it can hold; None where it reports no
    limit (a CPU device)."""
    return (device.memory_stats() or {}).get("bytes_limit")


def _device_of(bank: "HybridBank"):
    """The device that holds a local bank's per-row arrays."""
    return next(iter(bank.n_items.devices()))


def _block_devices(plan: ExecutionPlan) -> list:
    """Devices of a sharded plan's row blocks, in block order: row-major
    over ``plan.data_axes`` (as ``P(axes)`` shards), first index on any
    other mesh axis."""
    names = plan.mesh.axis_names
    order = [names.index(a) for a in plan.data_axes]
    order += [i for i in range(len(names)) if i not in order]
    devs = np.transpose(plan.mesh.devices, order)
    return list(devs.reshape(_shard_count(plan), -1)[:, 0])


def _local_plan(plan: Optional[ExecutionPlan]) -> Optional[ExecutionPlan]:
    """``plan`` for one row block: same backend and estimator, local."""
    if plan is None or plan.placement == "local":
        return plan
    return dataclasses.replace(plan, placement="local", mesh=None)


# (row bucket, length bucket) pairs the dense dispatch has sent this process
_DENSE_SHAPES_SEEN: set = set()
_DENSE_SHAPES_LOCK = threading.Lock()


def _fit_capacity(needed: int, threshold: int) -> int:
    """Smallest pow2-ish pair capacity holding ``needed`` entries."""
    if needed <= 0:
        return 0
    return min(threshold, max(4, 1 << (needed - 1).bit_length()))


@dataclasses.dataclass(frozen=True)
class _PendingLog:
    """The append buffer: raw sparse-destined (keys, items) sub-streams.

    Appending is a tuple concat of host arrays — O(chunks), no device
    dispatch, no dedup — so ingest cost between compactions tracks NEW
    pairs only.  ``plan`` remembers the most recent ingest plan so a
    read-triggered compaction runs the same registered sparse backend the
    writer chose (the differential harness depends on this to exercise
    every backend's dedup path).
    """

    chunks: Tuple[Tuple[np.ndarray, np.ndarray], ...]
    total: int
    plan: ExecutionPlan


# ----------------------------------------------------------------------------
# fused device kernels (jitted; static shapes per (stream, capacity) pair)
# ----------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("cfg",))
def _hash_stream(items, cfg: HLLConfig):
    """Jitted phase-1+3a hash of the buffered sparse-destined sub-stream.

    ``hash_index_rank`` is ~a hundred murmur3 ops; running it eagerly
    would dominate the whole hybrid compaction pass.
    """
    return hll.hash_index_rank(items, cfg)


@partial(jax.jit, static_argnames=("rows", "cap"))
def _compact_pairs(row_s, bucket_s, rank_s, survivor, keep_row, *, rows, cap):
    """Scatter surviving pairs of still-sparse rows into a (B, cap) buffer.

    Survivors arrive sorted by (row, bucket); each kept entry's slot is
    its running index within its row, so the output rows are bucket-sorted
    with ``-1`` padding — the invariant the v2 wire format serializes.
    The scatter indexes (row, slot) directly, so no id of the form
    ``row * cap + slot`` can overflow int32 on a large bank.
    """
    safe_row = jnp.clip(row_s, 0, rows - 1)
    take = survivor & keep_row[safe_row] & (row_s < rows)
    pos = jnp.cumsum(take.astype(jnp.int32)) - 1
    row_counts = jnp.bincount(
        jnp.where(take, row_s, rows), length=rows + 1
    )[:rows]
    row_start = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(row_counts)[:-1].astype(jnp.int32)]
    )
    offset = pos - row_start[safe_row]
    ok = take & (offset < cap)
    packed = (bucket_s << _PACK_SHIFT) | rank_s
    out = jnp.full((rows, cap), _EMPTY, jnp.int32)
    # rows index past the buffer is dropped: that is every entry not kept
    return out.at[jnp.where(ok, safe_row, rows), jnp.where(ok, offset, 0)].set(
        packed, mode="drop"
    )


def _compact_cells(cells_np, keep_row, distinct, *, cap):
    """Dense-cells twin of ``_compact_pairs``: (B, m) max-rank map -> pairs.

    Host-side on purpose: an XLA scatter over all B*m cells lowers to a
    serial loop on CPU (seconds at B=16384), while a row-major
    ``np.flatnonzero`` scan is one vectorized pass — and it emits each
    row's surviving buckets in ascending order, exactly the slot order
    the sorted path produces, so the two layouts compact to bit-identical
    buffers.  ``distinct`` is the dedup's per-row survivor count, reused
    as the per-row offset base instead of re-counting the mask.
    """
    rows, m = cells_np.shape
    nz = np.flatnonzero(cells_np.reshape(-1))
    r = nz // m
    c = nz - r * m
    sel_rows = keep_row[r]
    r, c = r[sel_rows], c[sel_rows]
    kept_counts = np.where(keep_row, distinct, 0)
    start = np.concatenate([[0], np.cumsum(kept_counts)[:-1]])
    off = np.arange(r.size) - start[r]
    pairs = np.full((rows, cap), _EMPTY, np.int32)
    sel = off < cap
    pairs[r[sel], off[sel]] = (c[sel].astype(np.int32) << _PACK_SHIFT) | (
        cells_np[r[sel], c[sel]].astype(np.int32)
    )
    return jnp.asarray(pairs)


@partial(jax.jit, static_argnames=("slots", "rows", "m"))
def _materialize_rows(
    row_s, bucket_s, rank_s, survivor, slot_of_row, *, slots, rows, m
):
    """Scatter surviving pairs of promoted rows into fresh dense registers.

    ``slot_of_row`` maps each promoted row to a local slot in [0, slots);
    every other row maps to -1 and contributes nothing.  The scatter sees
    the row's FULL deduped bucket -> max-rank map, so the produced
    registers are bit-identical to dense-from-scratch ingestion.
    """
    slot = slot_of_row[jnp.clip(row_s, 0, rows - 1)]
    take = survivor & (row_s < rows) & (slot >= 0)
    regs = jnp.zeros((slots, m), hll.REGISTER_DTYPE)
    # slot ``slots`` lies past the block: entries not taken are dropped
    return regs.at[jnp.where(take, slot, slots), jnp.where(take, bucket_s, 0)].max(
        rank_s.astype(hll.REGISTER_DTYPE), mode="drop"
    )


def _dedup_products(
    dd: SparseDedup,
    keep: np.ndarray,
    slot_of_row: np.ndarray,
    *,
    rows: int,
    m: int,
    cap: int,
    slots: int,
):
    """Compacted (B, cap) pairs + (slots, m) promoted registers from a dedup.

    Handles both :class:`SparseDedup` layouts; either way the promoted
    rows' registers carry the full deduped bucket -> max-rank map (in the
    cells layout that map IS the register row — promotion is a gather).
    ``slot_of_row`` must assign slots in ascending row order, which both
    call sites do.
    """
    if dd.cells is not None:
        cells_np = to_host(dd.cells)
        pairs = _compact_cells(cells_np, keep, to_host(dd.distinct), cap=cap)
        dense = (
            jnp.asarray(
                cells_np[np.nonzero(slot_of_row >= 0)[0]].astype(
                    hll.REGISTER_DTYPE
                )
            )
            if slots
            else None
        )
    else:
        pairs = _compact_pairs(
            dd.row_s,
            dd.bucket_s,
            dd.rank_s,
            dd.survivor,
            jnp.asarray(keep),
            rows=rows,
            cap=cap,
        )
        dense = (
            _materialize_rows(
                dd.row_s,
                dd.bucket_s,
                dd.rank_s,
                dd.survivor,
                jnp.asarray(slot_of_row),
                slots=slots,
                rows=rows,
                m=m,
            )
            if slots
            else None
        )
    return pairs, dense


@partial(jax.jit, static_argnames=("rows", "m"))
def _scatter_pairs_dense(pairs, *, rows, m):
    """(B, C) packed pairs -> (B, m) uint8 registers (one scatter-max)."""
    regs = jnp.zeros((rows, m), hll.REGISTER_DTYPE)
    if pairs.shape[1] == 0:
        return regs
    valid = pairs >= 0
    row = jnp.broadcast_to(
        jnp.arange(rows, dtype=jnp.int32)[:, None], pairs.shape
    )
    bucket = jnp.where(valid, pairs >> _PACK_SHIFT, 0)
    rank = jnp.where(valid, pairs & _PACK_MASK, 0)
    return regs.at[row, bucket].max(rank.astype(hll.REGISTER_DTYPE))


@partial(jax.jit, static_argnames=("m",))
def _lc_estimate(sparse_len, *, m):
    """Closed-form LinearCounting over per-row distinct counts.

    Jitted (not eager) so the float32 log lowers through the same XLA
    codegen as the dense device finalizer — eager batched transcendentals
    can differ in the last ulp, and the sparse fast path is pinned
    bit-identical to the dense path (tests/test_sparse.py).
    """
    fm = float(m)
    v = (fm - sparse_len).astype(jnp.float32)
    return fm * jnp.log(fm / jnp.maximum(v, 1.0))


@partial(jax.jit, static_argnames=("cfg", "estimator"))
def _finalize_histograms(hist, cfg: HLLConfig, estimator: str):
    """Jitted registry finalizer over prebuilt (B, K) histograms."""
    from repro.sketch import estimators as _estimators

    return _estimators.get_estimator(estimator).device(
        hist.astype(jnp.float32), cfg
    )


# ----------------------------------------------------------------------------
# the hybrid carrier
# ----------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class HybridBank:
    """B same-config sketches, each row sparse (COO pairs) or dense.

    The stored fields are the SETTLED state plus the transient append
    buffer; external readers should use the ``pairs`` / ``sparse_len`` /
    ``dense`` / ``dense_slot`` properties (or any read method), which
    compact the buffer first — raw fields are only safe on a bank whose
    ``pending`` is None.  A row-blocked bank (sharded placement, module
    note) keeps its rows in ``blocks`` and its own array fields are None.
    """

    pair_buf: Optional[jnp.ndarray]  # (B, C) int32 bucket<<8|rank, -1 = empty
    pair_len: Optional[jnp.ndarray]  # (B,) int32 distinct buckets (0 if dense)
    dense_block: Optional[jnp.ndarray]  # (D, m) uint8 registers of promoted rows
    slot_map: Optional[jnp.ndarray]  # (B,) int32 slot into dense_block, -1 = sparse
    n_items: Optional[jnp.ndarray]  # (B, 2) uint32 limb pairs, exact counts
    cfg: HLLConfig
    threshold: int  # promote when a row's distinct buckets exceed this
    pending: Optional[_PendingLog] = None  # un-deduplicated append buffer
    # contiguous row blocks, one local bank per device (sharded placement)
    blocks: Tuple["HybridBank", ...] = ()

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def empty(
        cls,
        rows: int,
        cfg: Optional[HLLConfig] = None,
        threshold: Optional[int] = None,
    ) -> "HybridBank":
        cfg = cfg or HLLConfig()
        if rows < 1:
            raise ValueError(f"a bank needs at least one row, got {rows}")
        threshold = _check_threshold(
            default_threshold(cfg) if threshold is None else threshold, cfg
        )
        return cls(
            jnp.zeros((rows, 0), jnp.int32),
            jnp.zeros((rows,), jnp.int32),
            jnp.zeros((0, cfg.m), hll.REGISTER_DTYPE),
            jnp.full((rows,), -1, jnp.int32),
            jnp.zeros((rows, 2), jnp.uint32),
            cfg,
            threshold,
        )

    @classmethod
    def from_dense(
        cls,
        bank: SketchBank,
        threshold: Optional[int] = None,
        dense_rows=None,
    ) -> "HybridBank":
        """Demote a dense bank: rows at or under ``threshold`` distinct
        buckets become sparse unless forced dense via ``dense_rows``."""
        cfg = bank.cfg
        threshold = _check_threshold(
            default_threshold(cfg) if threshold is None else threshold, cfg
        )
        regs = to_host(bank.registers)
        rows = regs.shape[0]
        occ = (regs > 0).sum(axis=1).astype(np.int64)
        force = (
            np.zeros(rows, bool)
            if dense_rows is None
            else to_host(dense_rows, bool)
        )
        if force.shape != (rows,):
            raise ValueError(
                f"dense_rows must be a ({rows},) mask, got {force.shape}"
            )
        dense_mask = force | (occ > threshold)
        sparse_mask = ~dense_mask
        sr, sb = np.nonzero(np.where(sparse_mask[:, None], regs, 0))
        counts = np.bincount(sr, minlength=rows)
        cap = _fit_capacity(int(counts.max(initial=0)), threshold)
        pairs = np.full((rows, cap), _EMPTY, np.int32)
        if sr.size:
            start = np.concatenate([[0], np.cumsum(counts)[:-1]])
            off = np.arange(sr.size) - start[sr]
            pairs[sr, off] = (sb.astype(np.int32) << _PACK_SHIFT) | regs[
                sr, sb
            ].astype(np.int32)
        dense_idx = np.nonzero(dense_mask)[0]
        dense_slot = np.full(rows, -1, np.int32)
        dense_slot[dense_idx] = np.arange(dense_idx.size, dtype=np.int32)
        return cls(
            jnp.asarray(pairs),
            jnp.asarray(np.where(sparse_mask, occ, 0).astype(np.int32)),
            jnp.asarray(regs[dense_idx]),
            jnp.asarray(dense_slot),
            bank.n_items,
            cfg,
            threshold,
        )

    @classmethod
    def from_sketches(
        cls,
        sketches: Sequence[HyperLogLog],
        threshold: Optional[int] = None,
    ) -> "HybridBank":
        return cls.from_dense(SketchBank.from_sketches(sketches), threshold)

    # ------------------------------------------------------------------
    # row blocks (sharded placement; module note)
    # ------------------------------------------------------------------

    def _split(self, devices) -> "HybridBank":
        """This local bank as contiguous row blocks, block d on
        ``devices[d]``; blocks are ``ceil(B / len(devices))`` rows, the
        last one the rest.  Dense slots renumber in row order per block."""
        s = self.compact()
        rows = len(s)
        block_rows = -(-rows // len(devices))
        pairs = to_host(s.pair_buf)
        pair_len = to_host(s.pair_len)
        slots = to_host(s.slot_map)
        limbs = to_host(s.n_items)
        dense = to_host(s.dense_block)
        blocks = []
        for dev, lo in zip(devices, range(0, rows, block_rows)):
            hi = min(rows, lo + block_rows)
            slot = slots[lo:hi]
            mine = slot >= 0
            local_slot = np.full(hi - lo, -1, np.int32)
            local_slot[mine] = np.arange(int(mine.sum()), dtype=np.int32)
            # pair rows fill from slot 0, so the block's own capacity suffices
            cap = _fit_capacity(int(pair_len[lo:hi].max(initial=0)), s.threshold)
            put = partial(jax.device_put, device=dev)
            blocks.append(
                HybridBank(
                    put(pairs[lo:hi, :cap]),
                    put(pair_len[lo:hi]),
                    put(dense[slot[mine]]),
                    put(local_slot),
                    put(limbs[lo:hi]),
                    s.cfg,
                    s.threshold,
                )
            )
        return HybridBank(
            None, None, None, None, None, s.cfg, s.threshold, blocks=tuple(blocks)
        )

    def _blocked_like(self, like: "HybridBank") -> "HybridBank":
        """This bank in ``like``'s row blocks (splitting a local bank)."""
        devices = [_device_of(b) for b in like.blocks]
        if not self.blocks:
            return self._split(devices)
        if [len(b) for b in self.blocks] != [len(b) for b in like.blocks] or [
            _device_of(b) for b in self.blocks
        ] != devices:
            raise ValueError("cannot combine banks split into different row blocks")
        return self

    def _per_block(self, fn, *per_block_args) -> list:
        """``fn(block_d, *args_d)`` for every row block d, results in block
        order.  The blocks run concurrently, one host thread each with its
        block's device as JAX's default device, so one block's compiles,
        transfers and host scans overlap the others' (jit compiles once
        per device; the registry and the dense-shape set are locked)."""

        def run(d):
            block = self.blocks[d]
            with jax.default_device(_device_of(block)):
                return fn(block, *(a[d] for a in per_block_args))

        with ThreadPoolExecutor(len(self.blocks)) as pool:
            return list(pool.map(run, range(len(self.blocks))))

    def _with_blocks(self, blocks) -> "HybridBank":
        return dataclasses.replace(self, blocks=tuple(blocks))

    def _local_view(self) -> "HybridBank":
        """The settled state of a local bank; a row-blocked bank has no
        single (B, C) pair buffer or (D, m) block to hand out."""
        if self.blocks:
            raise ValueError(
                "a row-blocked bank (sharded placement) keeps no whole-bank "
                "pair buffer or dense block; read it through row_registers, "
                "row, counts, modes, density or estimate_many"
            )
        return self.compact()

    # ------------------------------------------------------------------
    # compaction (the append buffer's one exit; every read routes here)
    # ------------------------------------------------------------------

    @property
    def pending_pairs(self) -> int:
        """Raw (bucket, rank) appends buffered since the last compaction."""
        if self.blocks:
            return sum(b.pending_pairs for b in self.blocks)
        return 0 if self.pending is None else self.pending.total

    def compact(self, _reason: str = "read") -> "HybridBank":
        """Settle the append buffer: dedup, recompact, promote — one pass.

        Idempotent and cached (a bank is immutable, so its settled form
        is too): repeated reads on the same instance compact once.  The
        result is bit-identical to having eagerly deduplicated every
        ``update_many`` batch — the register lattice is an associative,
        commutative, idempotent max, so batching order is invisible.

        ``_reason`` labels the flush for the metrics registry: "read" for
        settle-reads (a read surface forcing the buffer down), "pressure"
        when the ingest path crossed the flush floors.  A row-blocked
        bank compacts block by block, each on its own device.
        """
        if self.blocks:
            return self._with_blocks(self._per_block(lambda b: b.compact(_reason)))
        if self.pending is None:
            return self
        cached = self.__dict__.get("_settled")
        if cached is None:
            obs_metrics.inc(f"sparse.flush.{_reason}")
            with span(f"sparse.compact.{_reason}"):
                cached = self._compact_now()
            object.__setattr__(self, "_settled", cached)
        return cached

    def _compact_now(self) -> "HybridBank":
        pend = self.pending
        rows, m = len(self), self.cfg.m
        with span("sparse.compact.hash"):
            keys_np = np.concatenate([k for k, _ in pend.chunks])
            items_np = np.concatenate([v for _, v in pend.chunks])
            n = keys_np.size
            # pow2 padding (row = -1, dropped by the dedup validity mask)
            # bounds jit recompiles of the hash and dedup kernels
            pad = _pow2_bucket(n)
            items_pad = np.zeros(pad, items_np.dtype)
            items_pad[:n] = items_np
            new_rows = np.full(pad, -1, np.int32)
            new_rows[:n] = keys_np
            idx, rank = _hash_stream(jnp.asarray(items_pad), self.cfg)
        with span("sparse.compact.pairs"):
            old_rows, old_buckets, old_ranks = self._pair_triples()
        with span("sparse.compact.dedup"):
            dd = dedup_pairs(
                jnp.concatenate([jnp.asarray(old_rows), jnp.asarray(new_rows)]),
                jnp.concatenate([jnp.asarray(old_buckets), idx]),
                jnp.concatenate([jnp.asarray(old_ranks), rank]),
                rows,
                self.cfg,
                pend.plan,
            )
            distinct_np = to_host(dd.distinct)
            slot_np = to_host(self.slot_map)
        was_sparse = slot_np < 0
        promote = was_sparse & (distinct_np > self.threshold)
        keep = was_sparse & ~promote
        cap = _fit_capacity(
            int(distinct_np[keep].max(initial=0)), self.threshold
        )
        promoted = np.nonzero(promote)[0]
        if promoted.size:
            obs_metrics.inc("sparse.promotions", int(promoted.size))
        slot_of_row = np.full(rows, -1, np.int32)
        slot_of_row[promoted] = np.arange(promoted.size, dtype=np.int32)
        with span("sparse.compact.products"):
            new_pairs, fresh = _dedup_products(
                dd, keep, slot_of_row, rows=rows, m=m, cap=cap, slots=promoted.size
            )
            new_dense = self.dense_block
            new_slot = slot_np
            if promoted.size:
                new_dense = (
                    jnp.concatenate([new_dense, fresh])
                    if new_dense.shape[0]
                    else fresh
                )
                new_slot = slot_np.copy()
                new_slot[promoted] = self.dense_block.shape[0] + np.arange(
                    promoted.size, dtype=np.int32
                )
            return dataclasses.replace(
                self,
                pair_buf=new_pairs,
                pair_len=jnp.asarray(np.where(keep, distinct_np, 0).astype(np.int32)),
                dense_block=new_dense,
                slot_map=jnp.asarray(new_slot),
                pending=None,
            )

    # ------------------------------------------------------------------
    # introspection (every surface reads the SETTLED state)
    # ------------------------------------------------------------------

    def __len__(self) -> int:
        if self.blocks:
            return sum(len(b) for b in self.blocks)
        return int(self.n_items.shape[0])

    @property
    def pairs(self) -> jnp.ndarray:
        """(B, C) packed pair buffer of the settled state."""
        return self._local_view().pair_buf

    @property
    def sparse_len(self) -> jnp.ndarray:
        """(B,) int32 distinct-bucket counts of the settled state."""
        return self._local_view().pair_len

    @property
    def dense(self) -> jnp.ndarray:
        """(D, m) uint8 dense block of the settled state."""
        return self._local_view().dense_block

    @property
    def dense_slot(self) -> jnp.ndarray:
        """(B,) int32 row -> dense slot map of the settled state."""
        return self._local_view().slot_map

    @property
    def capacity(self) -> int:
        """Current per-row sparse pair capacity C (the widest block's)."""
        if self.blocks:
            return max(b.capacity for b in self.compact().blocks)
        return int(self.compact().pair_buf.shape[1])

    @property
    def dense_rows(self) -> int:
        """Number of promoted rows (the D of the dense block)."""
        if self.blocks:
            return sum(b.dense_rows for b in self.compact().blocks)
        return int(self.compact().dense_block.shape[0])

    @property
    def modes(self) -> np.ndarray:
        """(B,) uint8 row modes: MODE_SPARSE (0) or MODE_DENSE (1)."""
        if self.blocks:
            return np.concatenate([b.modes for b in self.compact().blocks])
        return (to_host(self.compact().slot_map) >= 0).astype(np.uint8)

    @property
    def counts(self) -> np.ndarray:
        """(B,) exact per-row observation counts as uint64.

        Counters update eagerly at ingest (one bincount per batch), so
        they never wait on a compaction.
        """
        if self.blocks:
            return np.concatenate([b.counts for b in self.blocks])
        limbs = to_host(self.n_items)
        hi = limbs[:, 0].astype(np.uint64)
        lo = limbs[:, 1].astype(np.uint64)
        return (hi << np.uint64(32)) | lo

    @property
    def nbytes(self) -> int:
        """Storage footprint of the settled hybrid representation."""
        if self.blocks:
            return sum(b.nbytes for b in self.compact().blocks)
        s = self.compact()
        return int(
            s.pair_buf.nbytes
            + s.pair_len.nbytes
            + s.dense_block.nbytes
            + s.slot_map.nbytes
            + s.n_items.nbytes
        )

    def density(self) -> dict:
        """Storage introspection: modes, occupancy, and the memory win."""
        rows = len(self)
        m = self.cfg.m
        dense_nbytes = rows * m + rows * 8  # what a SketchBank would cost
        if self.blocks:
            per = [b.density() for b in self.compact().blocks]
            nbytes = sum(d["nbytes"] for d in per)
            dense_rows = sum(d["dense_rows"] for d in per)
            return {
                "rows": rows,
                "dense_rows": dense_rows,
                "sparse_rows": rows - dense_rows,
                "capacity": max(d["capacity"] for d in per),
                "threshold": self.threshold,
                "occupancy_mean": sum(d["occupancy_mean"] * d["rows"] for d in per)
                / rows,
                "nbytes": nbytes,
                "dense_nbytes": dense_nbytes,
                "reduction": dense_nbytes / nbytes if nbytes else 0.0,
            }
        s = self.compact()
        d = int(s.dense_block.shape[0])
        occ = to_host(s.pair_len).astype(np.int64)
        if d:
            dense_occ = (to_host(s.dense_block) > 0).sum(axis=1)
            slot_np = to_host(s.slot_map)
            occ = occ + np.zeros_like(occ)
            occ[slot_np >= 0] = dense_occ[slot_np[slot_np >= 0]]
        return {
            "rows": rows,
            "dense_rows": d,
            "sparse_rows": rows - d,
            "capacity": int(s.pair_buf.shape[1]),
            "threshold": s.threshold,
            "occupancy_mean": float(occ.mean() / m) if rows else 0.0,
            "nbytes": s.nbytes,
            "dense_nbytes": dense_nbytes,
            "reduction": dense_nbytes / s.nbytes if s.nbytes else 0.0,
        }

    def row(self, i: int) -> HyperLogLog:
        """Row ``i`` materialized as a standalone dense carrier."""
        rows = len(self)
        if not -rows <= i < rows:
            raise IndexError(f"row {i} out of range for a {rows}-row bank")
        i = i % rows
        if self.blocks:
            block_rows = len(self.blocks[0])
            block = self.blocks[i // block_rows]
            with jax.default_device(_device_of(block)):
                return block.row(i % block_rows)
        s = self.compact()
        slot = int(s.slot_map[i])
        if slot >= 0:
            regs = s.dense_block[slot]
        else:
            regs_np = np.zeros(s.cfg.m, np.uint8)
            p = to_host(s.pair_buf[i])
            p = p[p >= 0]
            regs_np[p >> _PACK_SHIFT] = (p & _PACK_MASK).astype(np.uint8)
            regs = jnp.asarray(regs_np)
        return HyperLogLog(regs, s.n_items[i], s.cfg)

    def row_registers(self, start: int, stop: int) -> np.ndarray:
        """(stop - start, m) uint8 registers of rows [start, stop), on host.

        Reads only those rows (of the blocks that hold them), so a bank too
        large to materialize whole — on one device or on the host — can be
        read and checked range by range.
        """
        rows = len(self)
        if not 0 <= start <= stop <= rows:
            raise IndexError(
                f"rows [{start}, {stop}) out of range for a {rows}-row bank"
            )
        m = self.cfg.m
        if self.blocks:
            block_rows = len(self.blocks[0])
            parts = []
            for d, block in enumerate(self.blocks):
                base = d * block_rows
                lo, hi = max(start, base), min(stop, base + len(block))
                if lo < hi:
                    with jax.default_device(_device_of(block)):
                        parts.append(block.row_registers(lo - base, hi - base))
            return np.concatenate(parts) if parts else np.zeros((0, m), np.uint8)
        s = self.compact()
        n = stop - start
        regs = np.zeros((n, m), np.uint8)
        # a traced start keeps one slice executable per range length
        pairs = to_host(jax.lax.dynamic_slice_in_dim(s.pair_buf, start, n))
        r, c = np.nonzero(pairs >= 0)
        p = pairs[r, c]
        regs[r, p >> _PACK_SHIFT] = (p & _PACK_MASK).astype(np.uint8)
        slot = to_host(jax.lax.dynamic_slice_in_dim(s.slot_map, start, n))
        dense = np.nonzero(slot >= 0)[0]
        if dense.size:
            regs[dense] = to_host(s.dense_block[jnp.asarray(slot[dense])])
        return regs

    # ------------------------------------------------------------------
    # conversion
    # ------------------------------------------------------------------

    def _pair_triples(self):
        """Live pairs as (row, bucket, rank) int32 triples, pow2-padded.

        Reads the raw ``pair_buf`` — settled banks only (compaction and
        merge call it after settling).  The pair buffer allocates capacity
        C for every row, but only ``sum(pair_len)`` slots are live;
        extracting them (host-side, one vectorized pass) keeps the dedup
        cost proportional to LIVE pairs, not B*C, and the pow2 padding
        (row = -1, dropped by the dedup validity mask) bounds jit
        recompiles.
        """
        pairs_np = to_host(self.pair_buf)
        rows_np, slots = np.nonzero(pairs_np >= 0)
        packed = pairs_np[rows_np, slots]
        p = packed.size
        pad = _pow2_bucket(p)
        row = np.full(pad, -1, np.int32)
        bucket = np.zeros(pad, np.int32)
        rank = np.zeros(pad, np.int32)
        row[:p] = rows_np
        bucket[:p] = packed >> _PACK_SHIFT
        rank[:p] = packed & _PACK_MASK
        return row, bucket, rank

    def _dense_registers(self) -> jnp.ndarray:
        """The settled bank materialized as (B, m) uint8 registers."""
        s = self.compact()
        rows = len(s)
        regs = _scatter_pairs_dense(s.pair_buf, rows=rows, m=s.cfg.m)
        d = int(s.dense_block.shape[0])
        if d:
            slot = jnp.clip(s.slot_map, 0, d - 1)
            regs = jnp.where(
                (s.slot_map >= 0)[:, None], s.dense_block[slot], regs
            )
        return regs

    def to_dense(self) -> SketchBank:
        """Materialize to a plain dense ``SketchBank`` (lossless).

        A row-blocked bank gathers its (B, m) registers on its first
        block's device, and refuses where they pass the bytes that device
        reports it can hold; read such a bank in ranges with
        ``row_registers``.
        """
        if self.blocks:
            rows, m = len(self), self.cfg.m
            device = _device_of(self.blocks[0])
            limit = _bytes_limit(device)
            if limit is not None and rows * m > limit:
                raise ValueError(
                    f"to_dense of a {rows}-row bank at m={m} needs {rows * m} "
                    f"B, more than the {limit} B its device holds; read it in "
                    f"row ranges with row_registers"
                )
            limbs = np.concatenate([to_host(b.n_items) for b in self.blocks])
            put = partial(jax.device_put, device=device)
            return SketchBank(put(self.row_registers(0, rows)), put(limbs), self.cfg)
        return SketchBank(self._dense_registers(), self.n_items, self.cfg)

    def to_sketches(self) -> list:
        return [self.row(i) for i in range(len(self))]

    # ------------------------------------------------------------------
    # aggregation (paper phase 3, hybrid-routed)
    # ------------------------------------------------------------------

    def update_many(
        self,
        keys: jnp.ndarray,
        items: jnp.ndarray,
        plan: Optional[ExecutionPlan] = None,
    ) -> "HybridBank":
        """Route each item to row ``keys[i]``'s current representation.

        One host-orchestrated pass, no python loop over rows: the
        dense-destined sub-stream dispatches through the bank backend
        registered under ``plan.backend`` (§9) immediately, while the
        sparse-destined sub-stream APPENDS to the raw pair buffer — no
        hash, no dedup, no device dispatch — and only compacts here if
        the buffer passes the pressure floors (module note).  Promotions
        therefore fire at compaction rather than per batch, which cannot
        change the outcome: the register lattice is a max, so the settled
        state is bit-identical to eager per-batch dedup.  Zero-length
        streams and zero-row banks return ``self`` without dispatching
        any backend.  A sharded ``plan`` over more than one device splits
        a local bank into row blocks first; a row-blocked bank splits the
        stream by block and ingests each part on its block's device under
        the local form of ``plan`` (module note).
        """
        if self.blocks:
            return self._update_blocks(keys, items, plan)
        if (
            plan is not None
            and plan.placement == "sharded"
            and len(self)
            and _shard_count(plan.validate()) > 1
        ):
            return self._split(_block_devices(plan))._update_blocks(keys, items, plan)
        with span("sparse.route"):
            keys_np = to_host(keys).reshape(-1)
            items_np = to_host(items).reshape(-1)
            if keys_np.shape[0] != items_np.shape[0]:
                raise ValueError(
                    f"keys ({keys_np.shape[0]}) and items "
                    f"({items_np.shape[0]}) must flatten to the same length"
                )
            rows = len(self)
            if items_np.shape[0] == 0 or rows == 0:
                return self
            # an unsplit bank (one device) runs every phase locally
            plan = _local_plan((DEFAULT_PLAN if plan is None else plan).validate())
            keys_np = keys_np.astype(np.int32, copy=False)
            slot_np = to_host(self.slot_map)
            valid = (keys_np >= 0) & (keys_np < rows)
            dest = np.where(valid, slot_np[np.clip(keys_np, 0, rows - 1)], -1)
            dense_sel = valid & (dest >= 0)
            sparse_sel = valid & (dest < 0)

            pending = self.pending
            if sparse_sel.any():
                appended = int(sparse_sel.sum())
                chunk = (keys_np[sparse_sel], items_np[sparse_sel])
                chunks = (chunk,) if pending is None else pending.chunks + (chunk,)
                total = appended + (pending.total if pending else 0)
                pending = _PendingLog(chunks, total, plan)
                obs_metrics.inc("sparse.pending.appends")
                obs_metrics.inc("sparse.pending.pairs", appended)

            # one host bincount keeps the counters exact without a device
            # round-trip on the pure-append path
            counts = np.bincount(keys_np[valid], minlength=rows)[:rows]
            n_items = _counter_add_rows(
                self.n_items, jnp.asarray(counts.astype(np.uint32))
            )
            pressure = _pending_pressure(pending, self.pair_len)

        new_dense = self.dense_block
        if dense_sel.any():
            with span("sparse.dense"):
                new_dense = self._dense_update(
                    dest[dense_sel], items_np[dense_sel], plan
                )
        out = dataclasses.replace(
            self, dense_block=new_dense, n_items=n_items, pending=pending
        )
        if pressure:
            return out.compact(_reason="pressure")
        return out

    def _update_blocks(self, keys, items, plan) -> "HybridBank":
        """Split a tick on the host by row block and ingest each part on
        its block's device: ``key - block_index * block_rows``."""
        with span("sparse.shard.split"):
            keys_np = to_host(keys).reshape(-1)
            items_np = to_host(items).reshape(-1)
            if keys_np.shape[0] != items_np.shape[0]:
                raise ValueError(
                    f"keys ({keys_np.shape[0]}) and items "
                    f"({items_np.shape[0]}) must flatten to the same length"
                )
            keys_np = keys_np.astype(np.int32, copy=False)
            block_rows = len(self.blocks[0])
            # a key outside [0, B) reaches no block: the §9 drop rule
            block = np.where(
                (keys_np >= 0) & (keys_np < len(self)), keys_np // block_rows, -1
            )
            parts = []
            for d in range(len(self.blocks)):
                sel = block == d
                obs_metrics.inc(f"sparse.shard.pairs.{d}", int(sel.sum()))
                parts.append((keys_np[sel] - d * block_rows, items_np[sel]))
        local = _local_plan(plan)
        return self._with_blocks(
            self._per_block(lambda b, kx: b.update_many(kx[0], kx[1], local), parts)
        )

    def _dense_update(self, slots, items, plan: ExecutionPlan) -> jnp.ndarray:
        """Scatter a dense-destined (slot, item) sub-stream at bucketed shapes.

        The backend compiles per input shape, and both the sub-stream's
        length and the block's D change from tick to tick, so the stream
        is padded on the host to the next power of two (floor 2^6) with
        slot -1 / item 0, and the block to the next power of two of D with
        zero rows.  Slot -1 is dropped by every backend's §9 rule, and no
        slot points past D, so the padding lands nothing; the result is
        sliced back to (D, m) and the stored block keeps its exact shape.
        """
        d, n = int(self.dense_block.shape[0]), int(slots.shape[0])
        d_pad, n_pad = _pow2_bucket(d, floor=0), _pow2_bucket(n)
        with _DENSE_SHAPES_LOCK:
            new_shape = (d_pad, n_pad) not in _DENSE_SHAPES_SEEN
            _DENSE_SHAPES_SEEN.add((d_pad, n_pad))
        if new_shape:
            obs_metrics.inc("sparse.dense.new_shapes")
        obs_metrics.inc("sparse.dense.pad_pairs", n_pad - n)
        keys = np.full(n_pad, -1, np.int32)
        keys[:n] = slots
        vals = np.zeros(n_pad, items.dtype)
        vals[:n] = items
        block = self.dense_block
        if d_pad != d:
            block = jnp.pad(block, ((0, d_pad - d), (0, 0)))
        out = update_bank_registers(
            block, jnp.asarray(keys), jnp.asarray(vals), self.cfg, plan
        )
        return out if d_pad == d else out[:d]

    def merge(
        self, other: "HybridBank", plan: Optional[ExecutionPlan] = None
    ) -> "HybridBank":
        """Row-wise Merge-buckets fold; dense mode is infectious.

        Both sides settle their append buffers first (each under its own
        recorded ingest plan), then the fold dedups both sides' live
        sparse pairs through the same ``dedup_pairs`` dispatch as
        compaction — under ``plan`` (default jnp) — rows staying sparse
        recompact, and only the dense result rows (dense on either side,
        or a sparse union crossing the threshold) materialize registers
        overlaid with each side's dense blocks, so cost tracks live pairs
        + promoted rows — which is what lets
        ``HybridWindowedBank.fold_window`` stay sparse-sized.  Row-blocked
        banks merge block by block (a local side is split to match).
        """
        if self.cfg != other.cfg:
            raise ValueError(
                f"cannot merge banks with different configs: "
                f"{self.cfg} vs {other.cfg}"
            )
        if len(self) != len(other):
            raise ValueError(
                f"cannot merge banks of different sizes: "
                f"{len(self)} vs {len(other)} rows"
            )
        if self.threshold != other.threshold:
            raise ValueError(
                f"cannot merge banks with different sparse thresholds: "
                f"{self.threshold} vs {other.threshold}"
            )
        if self.blocks or other.blocks:
            like = self if self.blocks else other
            a, b = self._blocked_like(like), other._blocked_like(like)
            local = _local_plan(plan)
            return like._with_blocks(
                a._per_block(lambda x, y: x.merge(y, local), b.blocks)
            )
        a, b = self.compact(), other.compact()
        rows = len(a)
        m = a.cfg.m
        limbs = u64lib.add(
            u64lib.U64(a.n_items[:, 0], a.n_items[:, 1]),
            u64lib.U64(b.n_items[:, 0], b.n_items[:, 1]),
        )
        n_items = jnp.stack([limbs.hi, limbs.lo], axis=-1)
        if rows == 0:
            return dataclasses.replace(a, n_items=n_items)
        plan = _local_plan((DEFAULT_PLAN if plan is None else plan).validate())
        slot_a = to_host(a.slot_map)
        slot_b = to_host(b.slot_map)
        force_dense = (slot_a >= 0) | (slot_b >= 0)
        # a row dense on one side still contributes the OTHER side's pairs
        # through the triple stream; its dense registers overlay below
        ra, ba, ka = a._pair_triples()
        rb, bb, kb = b._pair_triples()
        dd = dedup_pairs(
            jnp.asarray(np.concatenate([ra, rb])),
            jnp.asarray(np.concatenate([ba, bb])),
            jnp.asarray(np.concatenate([ka, kb])),
            rows,
            a.cfg,
            plan,
        )
        distinct_np = to_host(dd.distinct)
        promote = ~force_dense & (distinct_np > a.threshold)
        keep = ~force_dense & ~promote
        cap = _fit_capacity(int(distinct_np[keep].max(initial=0)), a.threshold)
        dense_idx = np.nonzero(force_dense | promote)[0]
        slot_of_row = np.full(rows, -1, np.int32)
        slot_of_row[dense_idx] = np.arange(dense_idx.size, dtype=np.int32)
        pairs, dense = _dedup_products(
            dd, keep, slot_of_row, rows=rows, m=m, cap=cap, slots=dense_idx.size
        )
        if dense_idx.size:
            for side, side_slot in ((a, slot_a), (b, slot_b)):
                d = int(side.dense_block.shape[0])
                if d:
                    sel = side_slot[dense_idx]
                    contrib = jnp.where(
                        (jnp.asarray(sel) >= 0)[:, None],
                        side.dense_block[jnp.clip(jnp.asarray(sel), 0, d - 1)],
                        0,
                    )
                    dense = jnp.maximum(dense, contrib)
        else:
            dense = jnp.zeros((0, m), hll.REGISTER_DTYPE)
        return dataclasses.replace(
            a,
            pair_buf=pairs,
            pair_len=jnp.asarray(np.where(keep, distinct_np, 0).astype(np.int32)),
            dense_block=dense,
            slot_map=jnp.asarray(slot_of_row),
            n_items=n_items,
        )

    __or__ = merge

    # ------------------------------------------------------------------
    # estimation (paper phase 4, sparse-aware)
    # ------------------------------------------------------------------

    def _sparse_histograms(self) -> jnp.ndarray:
        """(B, K) int32 histograms straight from the settled pairs
        (C[0] = m - len)."""
        from repro.sketch import estimators as _estimators

        s = self.compact()
        rows = len(s)
        k = _estimators.histogram_size(s.cfg)
        cap = int(s.pair_buf.shape[1])
        flat = s.pair_buf.reshape(-1)
        valid = flat >= 0
        rank = jnp.where(valid, flat & _PACK_MASK, 0)
        row = jnp.repeat(jnp.arange(rows, dtype=jnp.int32), max(1, cap))
        if cap == 0:
            counts = jnp.zeros((rows, k), jnp.int32)
        else:
            idx = jnp.where(valid, row * k + rank, rows * k)
            counts = jnp.bincount(idx, length=rows * k + 1)[: rows * k]
            counts = counts.reshape(rows, k).astype(jnp.int32)
        return counts.at[:, 0].set(s.cfg.m - s.pair_len)

    def estimate_many(
        self,
        estimator: Optional[str] = None,
        *,
        lc_fast: bool = True,
        plan: Optional[ExecutionPlan] = None,
    ) -> jnp.ndarray:
        """(B,) float32 estimates, sparse rows via the LC fast path.

        For the default ``original`` estimator, sparse rows finalize with
        the closed-form LinearCounting read (bit-identical to the dense
        device path — see the module docstring proof); other estimators
        (or ``lc_fast=False``) build histograms from the pairs and run
        the registered device finalizer.  Dense rows always finalize
        through the §8 batched ``estimate_many``.  A row-blocked bank
        finalizes block by block, each on its own device, and returns the
        concatenation; an unsplit bank finalizes on its own device under
        any ``plan``.
        """
        from repro.sketch import estimators as _estimators

        s = self.compact()
        rows = len(s)
        if rows == 0:
            return jnp.zeros((0,), jnp.float32)
        if s.blocks:
            local = _local_plan(plan)
            return jnp.asarray(
                np.concatenate(
                    s._per_block(
                        lambda b: to_host(
                            b.estimate_many(estimator, lc_fast=lc_fast, plan=local)
                        )
                    )
                )
            )
        name = _estimators.resolve_estimator(
            estimator or (plan.estimator if plan is not None else None)
        )
        if name == "original" and lc_fast:
            sparse_est = _lc_estimate(s.pair_len, m=s.cfg.m)
        else:
            hist = s._sparse_histograms()
            sparse_est = _finalize_histograms(hist, s.cfg, name)
        d = int(s.dense_block.shape[0])
        if d:
            dense_est = _estimators.estimate_many(s.dense_block, s.cfg, estimator=name)
            slot = jnp.clip(s.slot_map, 0, d - 1)
            return jnp.where(s.slot_map >= 0, dense_est[slot], sparse_est)
        return sparse_est

    def estimate(self, i: int, estimator: Optional[str] = None) -> float:
        """Exact host-side estimate of one row."""
        return self.row(i).estimate(estimator)

    # ------------------------------------------------------------------
    # serialization (RHLB v2: per-row mode flags + sparse payloads)
    # ------------------------------------------------------------------

    def to_bytes(self) -> bytes:
        """RHLB v2: header + threshold + counts + mode flags + payloads.

        Always serializes the SETTLED state — buffered appends compact
        first, so the wire never carries (and never needs to encode) the
        transient append log.
        """
        s = self.compact()
        header = _BANK_HEADER.pack(
            _BANK_MAGIC,
            _SPARSE_VERSION,
            s.cfg.p,
            s.cfg.hash_bits,
            0,
            s.cfg.seed,
            len(s),
        )
        out = [header, _THRESHOLD.pack(s.threshold)]
        out.append(s.counts.astype("<u8").tobytes())
        out.append(s.modes.tobytes())
        for part in s.blocks or (s,):
            out.extend(part._row_payloads())
        return b"".join(out)

    def _row_payloads(self) -> list:
        """Per-row v2 payloads of a settled local bank, in row order."""
        pairs_np = to_host(self.pair_buf)
        dense_np = to_host(self.dense_block, dtype=np.uint8)
        slot_np = to_host(self.slot_map)
        out = []
        for i in range(len(self)):
            if slot_np[i] >= 0:
                out.append(dense_np[slot_np[i]].tobytes())
            else:
                p = pairs_np[i]
                p = p[p >= 0]
                out.append(_NPAIRS.pack(p.size))
                buckets = (p >> _PACK_SHIFT).astype("<u2")
                ranks = (p & _PACK_MASK).astype(np.uint8)
                pair_bytes = np.zeros((p.size, 3), np.uint8)
                pair_bytes[:, :2] = buckets.view(np.uint8).reshape(-1, 2)
                pair_bytes[:, 2] = ranks
                out.append(pair_bytes.tobytes())
        return out

    @classmethod
    def from_bytes(cls, data: bytes) -> "HybridBank":
        """Parse RHLB v2 strictly; v1 dense blobs parse as all-dense."""
        if len(data) < _BANK_HEADER.size:
            raise ValueError(f"truncated bank: {len(data)} bytes")
        magic, version, p, hash_bits, _flags, seed, rows = _BANK_HEADER.unpack(
            data[: _BANK_HEADER.size]
        )
        if magic != _BANK_MAGIC:
            raise ValueError(f"bad magic {magic!r}; not a serialized bank")
        if version == 1:
            # dense blobs still parse, version-gated: every row stays dense
            bank = SketchBank.from_bytes(data)
            return cls.from_dense(
                bank, dense_rows=np.ones(len(bank), bool)
            )
        if version != _SPARSE_VERSION:
            raise ValueError(f"unsupported bank version {version}")
        if rows < 1:
            raise ValueError(f"bank header claims {rows} rows")
        cfg = HLLConfig(p=p, hash_bits=hash_bits, seed=seed)
        off = _BANK_HEADER.size
        if len(data) < off + _THRESHOLD.size:
            raise ValueError("truncated bank: threshold missing")
        (threshold,) = _THRESHOLD.unpack_from(data, off)
        threshold = _check_threshold(threshold, cfg)
        off += _THRESHOLD.size
        counts_end = off + rows * _ROW_COUNT.size
        modes_end = counts_end + rows
        if len(data) < modes_end:
            raise ValueError("truncated bank: counts/mode flags cut short")
        raw_counts = np.frombuffer(data[off:counts_end], dtype="<u8")
        modes = np.frombuffer(data[counts_end:modes_end], dtype=np.uint8)
        if not np.isin(modes, (MODE_SPARSE, MODE_DENSE)).all():
            raise ValueError(
                f"corrupt mode flag {int(modes.max())}; rows are sparse (0) "
                f"or dense (1)"
            )
        off = modes_end
        sparse_pairs, dense_regs = [], []
        for i in range(rows):
            if modes[i] == MODE_DENSE:
                if len(data) < off + cfg.m:
                    raise ValueError(f"row {i}: dense payload cut short")
                dense_regs.append(
                    np.frombuffer(data[off : off + cfg.m], np.uint8)
                )
                off += cfg.m
                continue
            if len(data) < off + _NPAIRS.size:
                raise ValueError(f"row {i}: pair count cut short")
            (npairs,) = _NPAIRS.unpack_from(data, off)
            off += _NPAIRS.size
            if npairs > threshold:
                raise ValueError(
                    f"row {i}: {npairs} pairs exceeds threshold {threshold}"
                )
            end = off + npairs * 3
            if len(data) < end:
                raise ValueError(f"row {i}: pair list cut short")
            raw = np.frombuffer(data[off:end], np.uint8).reshape(npairs, 3)
            buckets = raw[:, :2].copy().view("<u2").reshape(-1).astype(np.int64)
            ranks = raw[:, 2].astype(np.int64)
            if npairs:
                if buckets.max() >= cfg.m:
                    raise ValueError(
                        f"row {i}: bucket {int(buckets.max())} out of range "
                        f"for m={cfg.m}"
                    )
                if not (np.diff(buckets) > 0).all():
                    raise ValueError(
                        f"row {i}: pair buckets must be strictly increasing"
                    )
                if ranks.min() < 1 or ranks.max() > cfg.max_rank:
                    raise ValueError(
                        f"row {i}: rank outside [1, {cfg.max_rank}]"
                    )
            sparse_pairs.append(
                ((buckets << _PACK_SHIFT) | ranks).astype(np.int32)
            )
            off = end
        if off != len(data):
            raise ValueError(
                f"bank payload is {len(data)} bytes, expected {off}"
            )
        cap = _fit_capacity(
            max((p.size for p in sparse_pairs), default=0), threshold
        )
        pairs = np.full((rows, cap), _EMPTY, np.int32)
        sparse_len = np.zeros(rows, np.int32)
        dense_slot = np.full(rows, -1, np.int32)
        # assign dense slots in row order (matching to_bytes)
        d = s = 0
        for i in range(rows):
            if modes[i] == MODE_DENSE:
                dense_slot[i] = d
                d += 1
            else:
                pr = sparse_pairs[s]
                pairs[i, : pr.size] = pr
                sparse_len[i] = pr.size
                s += 1
        limbs = np.stack(
            [(raw_counts >> 32).astype(np.uint32), raw_counts.astype(np.uint32)],
            axis=-1,
        )
        dense = (
            np.stack(dense_regs)
            if dense_regs
            else np.zeros((0, cfg.m), np.uint8)
        )
        return cls(
            jnp.asarray(pairs),
            jnp.asarray(sparse_len),
            jnp.asarray(dense),
            jnp.asarray(dense_slot),
            jnp.asarray(limbs),
            cfg,
            threshold,
        )


# ----------------------------------------------------------------------------
# module-level entry point (mirrors bank.update_many)
# ----------------------------------------------------------------------------


def update_many(
    bank: HybridBank,
    keys: jnp.ndarray,
    items: jnp.ndarray,
    plan: Optional[ExecutionPlan] = None,
) -> HybridBank:
    """Batched hybrid ingestion: sparse/dense routing in one fused pass."""
    return bank.update_many(keys, items, plan)
