"""WindowedBank: time-bucketed bank rings with fused sliding-window estimates.

Every query the flat carriers answer is "distinct items since the beginning
of time"; production traffic analytics asks "distinct users in the last 60
seconds".  The sliding-window FPGA follow-up (arXiv:2504.16896) keeps one
BRAM sketch slice per time bucket and merges the live slices on query —
this module is that structure over :class:`repro.sketch.bank.SketchBank`
primitives: a window is a ring of W time-bucket banks, and a windowed
estimate is ONE fused masked max-fold across the ring axis followed by the
existing batched ``estimate_many`` (estimator registry, DESIGN.md §8).

Ring/rotation contract (DESIGN.md §11):

* ``registers`` is (W, B, m): W time buckets of a B-row bank sharing one
  static ``HLLConfig``; ``n_items`` is (W, B, 2) exact per-bucket-per-row
  uint32 limb counters.
* ``epochs`` labels each slot with the absolute time bucket it holds;
  slot s always holds an epoch congruent to s modulo W, and the slot at
  ``cursor`` holds the newest epoch.  ``advance()`` rotates the cursor and
  zero-fills the slot it enters; ``advance_to(t)`` jumps forward any
  distance, expiring every overwritten bucket, with no python loop.
* ``observe(keys, items, plan)`` ingests into the CURRENT bucket through
  the same fused bank scatter as ``SketchBank.update_many`` (key-routing
  and drop rules of DESIGN.md §9 apply unchanged).
* ``estimate_window(last_k, plan)`` masks the k newest live epochs, folds
  the ring with the window backend registered under ``plan.backend``
  (``register_window_backend`` in plan.py), and finalizes the scratch
  (B, m) bank with one batched ``estimate_many`` — never a python loop
  over buckets or rows.  Every registered fold is bit-identical to
  merging the live buckets one by one (tests/test_window.py).

Incremental maintenance (DESIGN.md §14): the dense ring additionally
carries a host-side prefix/suffix fold decomposition so the full-window
read costs O(1) in W instead of refolding the (W, B, m) ring per query.
``advance()`` threads the decomposition forward in O(1) amortized per
rotation (the prefix stack rebuilds only once per W rotations),
``observe()`` leaves it untouched (the dirty head bucket is read live at
merge time), and a per-instance ``last_k`` fold cache — the same
immutable-instance memoization as ``HybridBank.compact``'s settled view
(DESIGN.md §12) — serves repeated reads without touching the ring.  All
of it is invisible state: instances stay 4-leaf jit-traceable pytrees,
and every cached or incremental read is bit-identical to the cold full
fold because register max is an associative, commutative, idempotent
lattice (DESIGN.md §6).

``MultiResWindowedBank`` is the long-horizon construction option: an
exponential histogram keeping the newest epochs at full resolution and
pairwise-merging older ones, so a ``base * (2**levels - 1)``-epoch
horizon costs O(base * levels) bucket slots instead of one slot per
epoch (DESIGN.md §14).  Its fold rides the same
``register_window_backend`` axis over the O(log horizon) bucket stack.

``to_bytes``/``from_bytes`` is the RHLW wire format: a 28-byte window
header + W int32 epoch labels + W per-bucket RHLB payloads, with the same
garbage/truncation rejection contract as RHLL/RHLB (DESIGN.md §7, §9).
Version 2 is the hybrid sparse ring; version 3 the multi-resolution ring.
"""

from __future__ import annotations

import dataclasses
import functools
import struct
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import trace_state_clean
from repro.obs import metrics as obs_metrics
from repro.sketch import hll
from repro.sketch.bank import SketchBank, _sharded_estimate_fn
from repro.sketch.dispatch import row_shard_apply
from repro.sketch.hll import HLLConfig
from repro.sketch.plan import (
    DEFAULT_PLAN,
    ExecutionPlan,
    get_window_backend,
    get_window_merge_backend,
)

_WINDOW_HEADER = struct.Struct("<4sBBBBQIII")
# magic, ver, p, H, flags, seed, W, B, cursor
_WINDOW_MAGIC = b"RHLW"
_WINDOW_VERSION = 1
_EPOCH = np.dtype("<i4")


def _initial_epochs(window: int) -> np.ndarray:
    """Epoch labels of a fresh ring at epoch 0: slot s holds the unique
    epoch in (0 - W, 0] congruent to s mod W (negative = never filled)."""
    slots = np.arange(window, dtype=np.int64)
    return (0 - np.mod(0 - slots, window)).astype(_EPOCH)


def _check_last_k_value(last_k: Optional[int], window: int) -> int:
    """Shared ``last_k`` validation for every ring flavor (dense, hybrid,
    multi-resolution) — one helper so the bound check and its error
    message cannot drift between carriers (tests/test_window_incremental.py
    pins the messages identical)."""
    if last_k is None:
        return window
    if not 1 <= int(last_k) <= window:
        raise ValueError(f"last_k must be in [1, {window}], got {last_k}")
    return int(last_k)


def _ring_fold(backend, ring, mask, cfg, plan: ExecutionPlan):
    """One masked ring fold under ``plan``'s placement.

    Folds are per-row maps over the bank axis (dim 1 of the (W, B, m)
    ring), so placement="sharded" runs the SAME backend on each device's
    row block (DESIGN.md §16) — bit-identical to the flat fold by row
    independence; every other placement folds the replicated ring as-is.
    """
    if plan.placement == "sharded":
        # the mask rides along replicated (in_dim None) so the cached
        # apply fn closes only over hashables — dispatch memoizes the
        # jitted shard_map per fn identity, and a per-call lambda would
        # force a re-trace on every serve-loop read
        return row_shard_apply(
            plan, _sharded_masked_fn(backend, cfg, plan), (ring, mask), (1, None)
        )
    return backend(ring, mask, cfg, plan)


@functools.lru_cache(maxsize=128)
def _sharded_masked_fn(backend, cfg, plan: ExecutionPlan):
    """Identity-stable (ring-block, mask) fold for the sharded cache."""

    def apply(ring, mask):
        return backend(ring, mask, cfg, plan)

    return apply


def _parts_merge(parts, cfg, plan: ExecutionPlan):
    """Merge (K, B, m) fold fragments under ``plan``'s placement — the
    sharded mirror of :func:`_ring_fold` for the §14 incremental read."""
    merge = get_window_merge_backend(plan.backend)
    if plan.placement == "sharded":
        return row_shard_apply(
            plan, _sharded_merge_fn(merge, cfg, plan), (parts,), (1,)
        )
    return merge(parts, cfg, plan)


@functools.lru_cache(maxsize=128)
def _sharded_merge_fn(merge, cfg, plan: ExecutionPlan):
    """Identity-stable fragment merge for the sharded cache."""

    def apply(parts):
        return merge(parts, cfg, plan)

    return apply


def _finalize_many(folded, cfg, plan: ExecutionPlan, estimator):
    """Batched finalization of a folded (B, m) scratch bank under
    ``plan``'s placement: sharded plans finalize per row block (§16),
    everything else in one flat dispatch (§8)."""
    from repro.sketch import estimators as _estimators

    name = estimator or plan.estimator
    if plan.placement == "sharded":
        return row_shard_apply(plan, _sharded_estimate_fn(cfg, name), (folded,), (0,))
    return _estimators.estimate_many(folded, cfg, estimator=name)


def _pack_limbs(totals: np.ndarray) -> np.ndarray:
    """(B,) uint64 exact counts -> (B, 2) uint32 hi/lo limb pairs."""
    return np.stack(
        [
            (totals >> np.uint64(32)).astype(np.uint32),
            totals.astype(np.uint32),
        ],
        axis=-1,
    )


class _RingReads:
    """Window reads shared verbatim by the dense and hybrid rings.

    Both carriers expose the same ``counts`` / ``_live_mask`` surface, so
    the exact-counter suffix sum and the ``last_k`` validation live here
    once instead of being copied per class.
    """

    def _check_last_k(self, last_k: Optional[int]) -> int:
        return _check_last_k_value(last_k, self.window)

    def window_counts(self, last_k: Optional[int] = None) -> np.ndarray:
        """(B,) exact observation counts over the last ``last_k`` epochs."""
        mask = np.asarray(self._live_mask(self._check_last_k(last_k)))
        return self.counts[mask].sum(axis=0, dtype=np.uint64)


@dataclasses.dataclass(frozen=True)
class _SuffixFold:
    """The prefix/suffix decomposition of a ring's CLOSED buckets.

    Host-side, non-pytree state stashed on a ``WindowedBank`` instance's
    ``__dict__`` (never a dataclass field — instances stay 4-leaf
    pytrees).  With the closed buckets ordered oldest → newest as
    a_1..a_C (C = W - 1; the bucket at ``cursor`` is the dirty head and
    never enters the decomposition):

    * ``prefix`` is the (C, B, m) suffix-fold stack built at the last
      rebuild: ``prefix[i] = fold(a_{i+1} .. a_F)`` over the front
      segment a_1..a_F.  Only the top entry ``prefix[head]`` is ever
      read; a rotation expires the oldest front bucket by bumping
      ``head`` — an O(1) pop.
    * ``suffix`` is the (B, m) running fold of every closed bucket NEWER
      than the front segment; each rotation folds the just-closed head
      bucket into it — one O(B·m) max, W-independent.
    * ``epoch`` is the absolute epoch this state describes; a mismatch
      (stale threading) forces a rebuild instead of a wrong answer.

    Full-window read = merge(prefix[head], suffix, ring[cursor]) through
    the ``register_window_merge_backend`` axis.  When ``head`` drains
    past the stack the next rotation rebuilds the stack from the ring —
    one reverse-cummax scan, so rebuilds cost O(W) only once per W
    rotations: O(1) amortized (DESIGN.md §14).
    """

    prefix: jnp.ndarray  # (C, B, m) suffix folds of the front segment
    head: int  # first live prefix entry; == C means the front is drained
    suffix: jnp.ndarray  # (B, m) fold of closed buckets newer than the front
    epoch: int  # absolute epoch the decomposition is valid for


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WindowedBank(_RingReads):
    """A (W, B, m) ring of time-bucket banks as one frozen pytree.

    Reads are incrementally maintained (DESIGN.md §14): instances carry a
    hidden prefix/suffix fold decomposition plus a per-instance ``last_k``
    fold cache in ``__dict__`` (mirroring ``HybridBank.compact``'s settled
    view, DESIGN.md §12), so steady-state ``estimate_window`` costs O(1)
    in W while staying bit-identical to the full ring fold.  The hidden
    state is dropped — never copied — by ``dataclasses.replace``, jit
    boundaries, and ``from_bytes``, which is exactly the invalidation
    rule: a new instance re-derives or re-threads what it can prove valid.
    """

    registers: jnp.ndarray  # (W, B, m) uint8
    n_items: jnp.ndarray  # (W, B, 2) uint32 limb pairs per bucket row
    cursor: jnp.ndarray  # () int32: ring slot of the newest epoch
    epochs: jnp.ndarray  # (W,) int32: absolute epoch held by each slot
    cfg: HLLConfig = dataclasses.field(metadata=dict(static=True))

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def empty(
        cls, window: int, rows: int, cfg: Optional[HLLConfig] = None
    ) -> "WindowedBank":
        cfg = cfg or HLLConfig()
        if window < 1:
            raise ValueError(f"a window needs at least one bucket, got {window}")
        if rows < 1:
            raise ValueError(f"a bank needs at least one row, got {rows}")
        return cls(
            jnp.zeros((window, rows, cfg.m), hll.REGISTER_DTYPE),
            jnp.zeros((window, rows, 2), jnp.uint32),
            jnp.zeros((), jnp.int32),
            jnp.asarray(_initial_epochs(window)),
            cfg,
        )

    def with_rows(self, rows: int) -> "WindowedBank":
        """Grow the bank axis to ``rows`` (new rows start empty)."""
        have = self.rows
        if rows < have:
            raise ValueError(f"cannot shrink a {have}-row window to {rows}")
        if rows == have:
            return self
        pad = ((0, 0), (0, rows - have), (0, 0))
        return dataclasses.replace(
            self,
            registers=jnp.pad(self.registers, pad),
            n_items=jnp.pad(self.n_items, pad),
        )

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def window(self) -> int:
        return int(self.registers.shape[0])

    @property
    def rows(self) -> int:
        return int(self.registers.shape[1])

    def __len__(self) -> int:
        return self.rows

    @property
    def epoch(self) -> int:
        """The newest (current) absolute epoch — host-side read."""
        return int(self.epochs[self.cursor])

    @property
    def counts(self) -> np.ndarray:
        """(W, B) exact per-bucket-per-row observation counts as uint64."""
        limbs = np.asarray(self.n_items)
        hi = limbs[..., 0].astype(np.uint64)
        lo = limbs[..., 1].astype(np.uint64)
        return (hi << np.uint64(32)) | lo

    def _live_mask(self, last_k: int) -> jnp.ndarray:
        """(W,) bool: slots holding one of the ``last_k`` newest epochs."""
        newest = self.epochs[self.cursor]
        return self.epochs > newest - last_k

    # ------------------------------------------------------------------
    # incremental fold state (hidden, host-side; DESIGN.md §14)
    # ------------------------------------------------------------------

    def _concrete(self) -> bool:
        """True when the ring is host-readable (no jit tracers).

        Under a jit trace the hidden state machinery stands down entirely:
        tracers must never leak into instance ``__dict__``s, and the
        traced instance returned by jit is rebuilt from pytree leaves
        anyway, so it could not carry the state out.  The trace-state
        check matters even when every leaf is concrete: a closure-captured
        instance used inside someone else's jit binds its ops to the
        active trace, so any derived value (``self.epoch``, a fold) would
        still come back abstract.
        """
        return trace_state_clean() and not any(
            isinstance(leaf, jax.core.Tracer)
            for leaf in (self.registers, self.n_items, self.cursor, self.epochs)
        )

    def _suffix_state(self) -> _SuffixFold:
        """The live decomposition — threaded forward by ``advance_to``,
        rebuilt from the ring when absent or stale."""
        state = self.__dict__.get("_inc")
        if state is None or state.epoch != self.epoch:
            state = self._rebuild_suffix()
            object.__setattr__(self, "_inc", state)
        return state

    def _rebuild_suffix(self) -> _SuffixFold:
        """One O(W) reverse-cummax scan over the closed buckets.

        ``prefix[i]`` folds closed buckets i..C-1 in age order, so popping
        the oldest is a pointer bump.  Runs once per W rotations in steady
        state (the amortization of DESIGN.md §14); expired slots were
        zero-filled by ``advance_to`` and fold as the rank-0 identity.
        """
        obs_metrics.inc("window.prefix_rebuilds")
        window, cursor = self.window, int(self.cursor)
        bank_shape = self.registers.shape[1:]
        if window == 1:
            prefix = jnp.zeros((0,) + bank_shape, self.registers.dtype)
        else:
            order = (cursor + 1 + np.arange(window - 1)) % window
            closed = self.registers[jnp.asarray(order, jnp.int32)]
            prefix = jax.lax.cummax(closed, axis=0, reverse=True)
        suffix = jnp.zeros(bank_shape, self.registers.dtype)
        return _SuffixFold(prefix, 0, suffix, self.epoch)

    def _thread_state(self, out: "WindowedBank", steps: int) -> None:
        """Carry the decomposition from ``self`` onto ``out`` after a
        rotation of ``steps`` epochs — O(1): fold the just-closed head
        bucket into the suffix accumulator and pop ``steps`` expired front
        buckets off the prefix stack.  Bails (leaving ``out`` stateless,
        to rebuild lazily) when the rotation outruns the stack."""
        state = self.__dict__.get("_inc")
        if steps <= 0:
            if state is not None and state.epoch == self.epoch:
                object.__setattr__(out, "_inc", state)
            return
        if state is None or state.epoch != self.epoch or steps >= self.window:
            return
        if steps > state.prefix.shape[0] - state.head:
            # the jump expires buckets already folded into the suffix
            # accumulator; max has no inverse, so rebuild from the ring
            return
        head_bucket = jax.lax.dynamic_index_in_dim(
            self.registers, self.cursor, 0, keepdims=False
        )
        object.__setattr__(
            out,
            "_inc",
            _SuffixFold(
                state.prefix,
                state.head + steps,
                jnp.maximum(state.suffix, head_bucket),
                self.epoch + steps,
            ),
        )

    # ------------------------------------------------------------------
    # ingestion (current bucket; paper phase 3)
    # ------------------------------------------------------------------

    def observe(
        self,
        keys: jnp.ndarray,
        items: jnp.ndarray,
        plan: Optional[ExecutionPlan] = None,
    ) -> "WindowedBank":
        """Route each item to row ``keys[i]`` of the CURRENT time bucket.

        The current bucket IS a ``SketchBank``, so the ingest delegates to
        ``SketchBank.update_many`` wholesale — one fused bank scatter, and
        the §9 validation/drop/counter rules cannot drift from the flat
        path.  Empty streams return ``self`` without dispatching anything.
        """
        cur = SketchBank(
            jax.lax.dynamic_index_in_dim(
                self.registers, self.cursor, 0, keepdims=False
            ),
            jax.lax.dynamic_index_in_dim(self.n_items, self.cursor, 0, keepdims=False),
            self.cfg,
        )
        new = cur.update_many(keys, items, plan)
        if new is cur:  # the empty-stream short-circuit: nothing to write back
            return self
        out = dataclasses.replace(
            self,
            registers=jax.lax.dynamic_update_index_in_dim(
                self.registers, new.registers, self.cursor, 0
            ),
            n_items=jax.lax.dynamic_update_index_in_dim(
                self.n_items, new.n_items, self.cursor, 0
            ),
        )
        # the decomposition describes CLOSED buckets only; an observe
        # dirties just the head bucket (read live at merge time), so the
        # state threads through unchanged.  The fold cache does NOT: `out`
        # is a fresh instance, so its cache starts empty — exactly the
        # invalidation an ingest requires.
        if self._concrete():
            self._thread_state(out, 0)
        return out

    # ------------------------------------------------------------------
    # rotation (the sliding part of the window)
    # ------------------------------------------------------------------

    def advance(self, steps: int = 1) -> "WindowedBank":
        """Open ``steps`` new epochs, expiring the buckets they overwrite."""
        if steps < 1:
            raise ValueError(f"advance needs steps >= 1, got {steps}")
        return self.advance_to(self.epochs[self.cursor] + steps)

    def advance_to(self, epoch) -> "WindowedBank":
        """Rotate forward so ``epoch`` is current; the past never returns.

        Every slot whose label changes is zero-filled (its old bucket has
        slid out of the window); jumping W or more epochs expires the whole
        ring.  ``epoch`` at or before the current epoch is a no-op, so
        replaying an old timestamp cannot resurrect expired data.  All
        vectorized — no python loop over buckets.
        """
        target = jnp.maximum(jnp.asarray(epoch, jnp.int32), self.epochs[self.cursor])
        window = self.window
        slots = jnp.arange(window, dtype=jnp.int32)
        # the unique epoch in (target - W, target] congruent to s mod W
        new_epochs = target - jnp.mod(target - slots, window)
        stale = new_epochs > self.epochs  # slots being overwritten
        keep = ~stale[:, None, None]
        out = dataclasses.replace(
            self,
            registers=jnp.where(keep, self.registers, 0).astype(self.registers.dtype),
            n_items=jnp.where(keep, self.n_items, 0).astype(self.n_items.dtype),
            cursor=jnp.mod(target, window).astype(jnp.int32),
            epochs=new_epochs.astype(jnp.int32),
        )
        # O(1)-amortized incremental maintenance (DESIGN.md §14): fold the
        # just-closed head bucket into the suffix accumulator and pop the
        # expired front buckets.  Host-side only — a traced rotation
        # leaves the new instance stateless (reads rebuild lazily).
        if self._concrete() and not isinstance(target, jax.core.Tracer):
            self._thread_state(out, int(target) - self.epoch)
        return out

    # ------------------------------------------------------------------
    # estimation (paper phase 4, windowed)
    # ------------------------------------------------------------------

    def estimate_window(
        self,
        last_k: Optional[int] = None,
        plan: Optional[ExecutionPlan] = None,
        estimator: Optional[str] = None,
    ) -> jnp.ndarray:
        """(B,) float32 distinct counts over the ``last_k`` newest epochs.

        ONE fused masked max-reduce over the ring axis (the window backend
        registered under ``plan.backend``) into a scratch (B, m) bank,
        then one batched ``estimate_many`` dispatch — never a python loop
        over buckets or rows.  The fold reads replicated ring state, so
        mesh plans fold locally (placement only moves ingest streams).
        """
        folded = self._fold_registers(self._check_last_k(last_k), plan)
        plan = DEFAULT_PLAN if plan is None else plan
        return _finalize_many(folded, self.cfg, plan, estimator)

    def _fold_registers(
        self, last_k: int, plan: Optional[ExecutionPlan]
    ) -> jnp.ndarray:
        """(B, m) fold of the ``last_k`` newest epochs — cached, and O(1)
        in W for the full window (DESIGN.md §14).

        The per-instance cache is the settled-view idiom of
        ``HybridBank.compact`` (§12): an instance is immutable, so its
        folds are too, and every mutation returns a NEW instance whose
        cache starts empty — invalidation by construction.  The key
        carries the plan's dispatch identity so distinct backends still
        exercise their own fold paths (the equivalence tests depend on
        that).  A full-window read merges the three decomposition
        fragments through the ``register_window_merge_backend`` axis
        instead of refolding the ring; suffix windows (last_k < W) fall
        back to the masked ring fold, cached the same way.
        """
        plan = (DEFAULT_PLAN if plan is None else plan).validate()
        backend = get_window_backend(plan.backend)
        if not self._concrete():
            return _ring_fold(
                backend, self.registers, self._live_mask(last_k), self.cfg, plan
            )
        cache = self.__dict__.setdefault("_fold_cache", {})
        key = (last_k, plan.backend, plan.pipelines, plan.placement)
        hit = cache.get(key)
        if hit is not None:
            obs_metrics.inc("window.fold_cache.hits")
            return hit
        obs_metrics.inc("window.fold_cache.misses")
        if last_k == self.window:
            regs = self._fold_incremental(plan)
        else:
            regs = _ring_fold(
                backend, self.registers, self._live_mask(last_k), self.cfg, plan
            )
        cache[key] = regs
        return regs

    def _fold_incremental(self, plan: ExecutionPlan) -> jnp.ndarray:
        """merge(prefix top, suffix accumulator, dirty head) — three (B, m)
        fragments, whatever W is.  Bit-identical to the masked ring fold:
        the fragments partition the live buckets (empty slots fold as the
        rank-0 identity) and register max is order-invisible (§6)."""
        state = self._suffix_state()
        if state.head < state.prefix.shape[0]:
            prefix_top = state.prefix[state.head]
        else:  # front segment fully drained (or W == 1): identity
            prefix_top = jnp.zeros(self.registers.shape[1:], self.registers.dtype)
        head_bucket = jax.lax.dynamic_index_in_dim(
            self.registers, self.cursor, 0, keepdims=False
        )
        parts = jnp.stack([prefix_top, state.suffix, head_bucket])
        return _parts_merge(parts, self.cfg, plan)

    def fold_window(
        self,
        last_k: Optional[int] = None,
        plan: Optional[ExecutionPlan] = None,
    ) -> SketchBank:
        """The ``last_k``-epoch suffix collapsed to a flat ``SketchBank``.

        Registers come from the (cached, incrementally maintained) ring
        fold; the exact per-row counters sum the live buckets' counts
        (host-side, exact to 2^64).
        """
        last_k = self._check_last_k(last_k)
        regs = self._fold_registers(last_k, plan)
        totals = self.window_counts(last_k)
        return SketchBank(regs, jnp.asarray(_pack_limbs(totals)), self.cfg)

    # ------------------------------------------------------------------
    # serialization (RHLW: window header + epochs + RHLB payloads)
    # ------------------------------------------------------------------

    def to_bytes(self) -> bytes:
        """28-byte window header + W int32 epochs + W RHLB bucket blobs."""
        header = _WINDOW_HEADER.pack(
            _WINDOW_MAGIC,
            _WINDOW_VERSION,
            self.cfg.p,
            self.cfg.hash_bits,
            0,
            self.cfg.seed,
            self.window,
            self.rows,
            int(self.cursor),
        )
        epochs = np.asarray(self.epochs, dtype=_EPOCH).tobytes()
        buckets = b"".join(
            SketchBank(self.registers[w], self.n_items[w], self.cfg).to_bytes()
            for w in range(self.window)
        )
        return header + epochs + buckets

    @classmethod
    def from_bytes(cls, data: bytes) -> "WindowedBank":
        if len(data) < _WINDOW_HEADER.size:
            raise ValueError(f"truncated window: {len(data)} bytes")
        magic, version, p, hash_bits, _flags, seed, window, rows, cursor = (
            _WINDOW_HEADER.unpack(data[: _WINDOW_HEADER.size])
        )
        if magic != _WINDOW_MAGIC:
            raise ValueError(f"bad magic {magic!r}; not a serialized window")
        if version != _WINDOW_VERSION:
            hints = {
                2: "; version 2 is the hybrid sparse ring — parse it with "
                "HybridWindowedBank.from_bytes",
                3: "; version 3 is the multi-resolution ring — parse it "
                "with MultiResWindowedBank.from_bytes",
            }
            raise ValueError(
                f"unsupported window version {version}{hints.get(version, '')}"
            )
        if window < 1 or rows < 1:
            raise ValueError(f"window header claims {window} buckets x {rows} rows")
        if cursor >= window:
            raise ValueError(f"cursor {cursor} out of range for W={window}")
        cfg = HLLConfig(p=p, hash_bits=hash_bits, seed=seed)
        epochs_end = _WINDOW_HEADER.size + window * _EPOCH.itemsize
        bucket_size = 20 + rows * 8 + rows * cfg.m
        expected = epochs_end + window * bucket_size
        if len(data) != expected:
            # covers payloads cut mid-bucket and mid-row alike
            raise ValueError(
                f"window payload is {len(data)} bytes, expected {expected} "
                f"for W={window}, B={rows}, m={cfg.m}"
            )
        epochs = np.frombuffer(data[_WINDOW_HEADER.size : epochs_end], _EPOCH)
        epochs = epochs.astype(np.int64)
        _validate_epoch_ring(epochs, cursor, window)
        regs, limbs = [], []
        for w in range(window):
            start = epochs_end + w * bucket_size
            bucket = SketchBank.from_bytes(data[start : start + bucket_size])
            if bucket.cfg != cfg or len(bucket) != rows:
                raise ValueError(f"bucket {w} disagrees with the window header")
            regs.append(bucket.registers)
            limbs.append(bucket.n_items)
        return cls(
            jnp.stack(regs),
            jnp.stack(limbs),
            jnp.asarray(cursor, jnp.int32),
            jnp.asarray(epochs.astype(_EPOCH)),
            cfg,
        )


# ----------------------------------------------------------------------------
# hybrid (sparse-bucket) rings — DESIGN.md §12
# ----------------------------------------------------------------------------

_WINDOW_VERSION_SPARSE = 2
_BUCKET_LEN = struct.Struct("<Q")


def _validate_epoch_ring(epochs: np.ndarray, cursor: int, window: int) -> None:
    """The slot-congruence invariant shared by RHLW v1 and v2 parsers."""
    epochs = epochs.astype(np.int64)
    slots = np.arange(window, dtype=np.int64)
    if not (
        np.array_equal(np.mod(epochs, window), slots)
        and int(np.argmax(epochs)) == cursor
        and int(epochs.max() - epochs.min()) == window - 1
    ):
        raise ValueError("corrupt epoch labels: ring invariant violated")


@dataclasses.dataclass(frozen=True)
class HybridWindowedBank(_RingReads):
    """A ring of W sparse/dense ``HybridBank`` time buckets.

    The dense ``WindowedBank`` above carries a (W, B, m) block no matter
    how empty the tenants are; this ring carries one hybrid bank per time
    bucket instead, so near-empty rows cost COO pairs per epoch rather
    than m bytes per epoch.  The ring/rotation contract (epoch labels,
    cursor, expiry-on-overwrite, monotone ``advance_to``) is identical to
    ``WindowedBank``; promotion state is PER BUCKET and rides the slot as
    it ages — a bucket promoted while current stays dense until the slot
    is overwritten, so ``advance()`` never demotes or re-ingests anything.

    Like ``HybridBank``, the ring is host-orchestrated (bucket shapes
    change under promotion), so it is not a jit-traceable pytree; each
    bucket's ingest still runs the fused hybrid dispatch.  Window folds
    merge the live hybrid buckets pairwise (W is small — the fused ring
    fold of §11 stays the dense path's job) and finalize with one batched
    ``estimate_many``; merges and serialization settle each bucket's
    deferred append buffer first (``HybridBank.compact``), so every read
    of the ring observes fully deduped state.  ``to_bytes``/``from_bytes`` is RHLW v2: the window
    header with version=2, the epoch labels, then W length-prefixed RHLB
    v2 bucket payloads (v1 dense bucket payloads still parse,
    version-gated, matching ``HybridBank.from_bytes``).
    """

    buckets: tuple  # W HybridBanks, slot order
    cursor: int
    epochs: np.ndarray  # (W,) int32 absolute epoch per slot

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def empty(
        cls,
        window: int,
        rows: int,
        cfg: Optional[HLLConfig] = None,
        threshold: Optional[int] = None,
    ) -> "HybridWindowedBank":
        from repro.sketch.sparse import HybridBank

        if window < 1:
            raise ValueError(f"a window needs at least one bucket, got {window}")
        return cls(
            tuple(
                HybridBank.empty(rows, cfg, threshold) for _ in range(window)
            ),
            0,
            _initial_epochs(window),
        )

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def window(self) -> int:
        return len(self.buckets)

    @property
    def rows(self) -> int:
        return len(self.buckets[0])

    def __len__(self) -> int:
        return self.rows

    @property
    def cfg(self) -> HLLConfig:
        return self.buckets[0].cfg

    @property
    def threshold(self) -> int:
        return self.buckets[0].threshold

    @property
    def epoch(self) -> int:
        return int(self.epochs[self.cursor])

    @property
    def counts(self) -> np.ndarray:
        """(W, B) exact per-bucket-per-row observation counts as uint64."""
        return np.stack([b.counts for b in self.buckets])

    def density(self) -> dict:
        """Ring-wide storage stats: the §12 introspection summed over W."""
        per = [b.density() for b in self.buckets]
        nbytes = sum(d["nbytes"] for d in per)
        dense_nbytes = sum(d["dense_nbytes"] for d in per)
        return {
            "window": self.window,
            "rows": self.rows,
            "dense_rows": sum(d["dense_rows"] for d in per),
            "sparse_rows": sum(d["sparse_rows"] for d in per),
            "threshold": self.threshold,
            "occupancy_mean": float(
                np.mean([d["occupancy_mean"] for d in per])
            ),
            "nbytes": nbytes,
            "dense_nbytes": dense_nbytes,
            "reduction": dense_nbytes / nbytes if nbytes else 0.0,
        }

    def _live_mask(self, last_k: int) -> np.ndarray:
        newest = int(self.epochs[self.cursor])
        return np.asarray(self.epochs) > newest - last_k

    # ------------------------------------------------------------------
    # ingestion + rotation
    # ------------------------------------------------------------------

    def observe(
        self,
        keys: jnp.ndarray,
        items: jnp.ndarray,
        plan: Optional[ExecutionPlan] = None,
    ) -> "HybridWindowedBank":
        """Hybrid-route each item into the CURRENT time bucket.

        Delegates to ``HybridBank.update_many`` wholesale (sparse/dense
        routing, promotion, §9 drop/counter rules — including the
        deferred append buffer: sparse-destined pairs accumulate raw in
        the current bucket's pending log and dedup only under capacity
        pressure or when a read settles the bucket, so per-epoch ingest
        stays O(append)); empty streams return ``self`` without
        dispatching anything.
        """
        cur = self.buckets[self.cursor]
        new = cur.update_many(keys, items, plan)
        if new is cur:  # the empty-stream short-circuit
            return self
        buckets = list(self.buckets)
        buckets[self.cursor] = new
        return dataclasses.replace(self, buckets=tuple(buckets))

    def advance(self, steps: int = 1) -> "HybridWindowedBank":
        if steps < 1:
            raise ValueError(f"advance needs steps >= 1, got {steps}")
        return self.advance_to(self.epoch + steps)

    def advance_to(self, epoch: int) -> "HybridWindowedBank":
        """Rotate forward; overwritten buckets expire (same rules as the
        dense ring: monotone, whole-ring expiry on jumps >= W)."""
        from repro.sketch.sparse import HybridBank

        target = max(int(epoch), self.epoch)
        window = self.window
        slots = np.arange(window, dtype=np.int64)
        new_epochs = target - np.mod(target - slots, window)
        stale = new_epochs > np.asarray(self.epochs, np.int64)
        fresh = lambda: HybridBank.empty(self.rows, self.cfg, self.threshold)
        buckets = tuple(
            fresh() if stale[s] else self.buckets[s] for s in range(window)
        )
        return dataclasses.replace(
            self,
            buckets=buckets,
            cursor=int(target % window),
            epochs=new_epochs.astype(_EPOCH),
        )

    # ------------------------------------------------------------------
    # estimation
    # ------------------------------------------------------------------

    def fold_window(self, last_k: Optional[int] = None):
        """The live ``last_k``-epoch suffix merged into one ``HybridBank``.

        Pairwise hybrid merges over at most W (small) live buckets;
        promotion stays infectious, so a row dense in ANY live bucket is
        dense in the fold.  Memoized per instance and per ``last_k`` —
        the same settled-view idiom as ``HybridBank.compact`` (DESIGN.md
        §12/§14): the ring is immutable, so its folds are too, and any
        mutation returns a fresh instance with an empty cache.
        """
        last_k = self._check_last_k(last_k)
        # under an active trace the merge ops would come back abstract;
        # caching them would leak dead tracers into later eager reads
        cacheable = trace_state_clean()
        if cacheable:
            cache = self.__dict__.setdefault("_fold_cache", {})
            hit = cache.get(last_k)
            if hit is not None:
                obs_metrics.inc("window.fold_cache.hits")
                return hit
            obs_metrics.inc("window.fold_cache.misses")
        mask = self._live_mask(last_k)
        live = [self.buckets[s] for s in range(self.window) if mask[s]]
        out = live[0]
        for b in live[1:]:
            out = out.merge(b)
        if cacheable:
            cache[last_k] = out
        return out

    def estimate_window(
        self,
        last_k: Optional[int] = None,
        plan: Optional[ExecutionPlan] = None,
        estimator: Optional[str] = None,
    ) -> jnp.ndarray:
        """(B,) float32 distinct counts over the ``last_k`` newest epochs."""
        plan = DEFAULT_PLAN if plan is None else plan
        return self.fold_window(last_k).estimate_many(
            estimator or plan.estimator
        )

    # ------------------------------------------------------------------
    # serialization (RHLW v2: length-prefixed hybrid bucket payloads)
    # ------------------------------------------------------------------

    def to_bytes(self) -> bytes:
        header = _WINDOW_HEADER.pack(
            _WINDOW_MAGIC,
            _WINDOW_VERSION_SPARSE,
            self.cfg.p,
            self.cfg.hash_bits,
            0,
            self.cfg.seed,
            self.window,
            self.rows,
            self.cursor,
        )
        out = [header, np.asarray(self.epochs, dtype=_EPOCH).tobytes()]
        for b in self.buckets:
            blob = b.to_bytes()
            out.append(_BUCKET_LEN.pack(len(blob)))
            out.append(blob)
        return b"".join(out)

    @classmethod
    def from_bytes(cls, data: bytes) -> "HybridWindowedBank":
        from repro.sketch.sparse import HybridBank

        if len(data) < _WINDOW_HEADER.size:
            raise ValueError(f"truncated window: {len(data)} bytes")
        magic, version, p, hash_bits, _flags, seed, window, rows, cursor = (
            _WINDOW_HEADER.unpack(data[: _WINDOW_HEADER.size])
        )
        if magic != _WINDOW_MAGIC:
            raise ValueError(f"bad magic {magic!r}; not a serialized window")
        if version == _WINDOW_VERSION:
            # dense rings still parse, version-gated: all-dense buckets
            dense = WindowedBank.from_bytes(data)
            buckets = tuple(
                SketchBank(
                    dense.registers[w], dense.n_items[w], dense.cfg
                ).to_hybrid(dense_rows=np.ones(dense.rows, bool))
                for w in range(dense.window)
            )
            return cls(
                buckets, int(dense.cursor), np.asarray(dense.epochs, _EPOCH)
            )
        if version != _WINDOW_VERSION_SPARSE:
            hint = (
                "; version 3 is the multi-resolution ring — parse it "
                "with MultiResWindowedBank.from_bytes"
                if version == _WINDOW_VERSION_MULTI
                else ""
            )
            raise ValueError(f"unsupported window version {version}{hint}")
        if window < 1 or rows < 1:
            raise ValueError(f"window header claims {window} buckets x {rows} rows")
        if cursor >= window:
            raise ValueError(f"cursor {cursor} out of range for W={window}")
        cfg = HLLConfig(p=p, hash_bits=hash_bits, seed=seed)
        epochs_end = _WINDOW_HEADER.size + window * _EPOCH.itemsize
        if len(data) < epochs_end:
            raise ValueError("truncated window: epoch labels cut short")
        epochs = np.frombuffer(data[_WINDOW_HEADER.size : epochs_end], _EPOCH)
        _validate_epoch_ring(epochs, cursor, window)
        off = epochs_end
        buckets, was_v1 = [], []
        for w in range(window):
            if len(data) < off + _BUCKET_LEN.size:
                raise ValueError(f"bucket {w}: length prefix cut short")
            (blen,) = _BUCKET_LEN.unpack_from(data, off)
            off += _BUCKET_LEN.size
            if len(data) < off + blen:
                raise ValueError(f"bucket {w}: payload cut short")
            payload = data[off : off + blen]
            bucket = HybridBank.from_bytes(payload)
            if bucket.cfg != cfg or len(bucket) != rows:
                raise ValueError(f"bucket {w} disagrees with the window header")
            buckets.append(bucket)
            # a version-gated v1 dense payload carries no threshold of its
            # own; it adopts the ring's below instead of vetoing it
            was_v1.append(len(payload) > 5 and payload[4] == 1)
            off += blen
        if off != len(data):
            raise ValueError(
                f"window payload is {len(data)} bytes, expected {off}"
            )
        v2_thresholds = {
            b.threshold for b, v1 in zip(buckets, was_v1) if not v1
        }
        if len(v2_thresholds) > 1:
            raise ValueError(
                f"bucket thresholds disagree across the ring: "
                f"{sorted(v2_thresholds)}"
            )
        if v2_thresholds:
            (ring_threshold,) = v2_thresholds
            buckets = [
                dataclasses.replace(b, threshold=ring_threshold)
                if v1
                else b
                for b, v1 in zip(buckets, was_v1)
            ]
        return cls(tuple(buckets), int(cursor), epochs.copy())


# ----------------------------------------------------------------------------
# multi-resolution rings (exponential histogram) — DESIGN.md §14
# ----------------------------------------------------------------------------

_WINDOW_VERSION_MULTI = 3
_MR_BASE = struct.Struct("<I")
_MR_BUCKET = struct.Struct("<iiI")  # start epoch, end epoch, logical size
_MR_MAX_LEVELS = 24  # keeps base * 2**levels (and every epoch label) in int32


@dataclasses.dataclass(frozen=True)
class _MRBucket:
    """One closed exponential-histogram bucket.

    ``start``/``end`` are the absolute epochs the bucket spans (label
    width may exceed ``size`` when empty epochs fell inside a merge);
    ``size`` is the logical level size — always a power of two: two
    size-s buckets merge into one size-2s bucket, never anything else.
    """

    start: int
    end: int
    size: int
    bank: SketchBank


@dataclasses.dataclass(frozen=True)
class MultiResWindowedBank:
    """An exponential-histogram window: O(base·levels) slots, long horizon.

    The dense ring pays one (B, m) bucket per epoch, so a million-epoch
    horizon is a million buckets.  This carrier keeps the newest epochs
    at full resolution and PAIRWISE-MERGES older ones (the classic
    exponential histogram, composing with the sliding-window FPGA
    sketches of arXiv:2504.16896): each resolution level holds at most
    ``base`` buckets of logical size 2^ℓ, ℓ < ``levels``; when a level
    overflows, its two oldest buckets merge into one bucket of the next
    level (register max + exact counter add — lossless for the union,
    since the register lattice is a true union).  A
    ``horizon = base * (2**levels - 1)`` epoch span therefore costs at
    most ``base * levels`` closed buckets.

    What is approximated: never the registers — only the window BOUNDARY.
    A query over the last k epochs folds every bucket that intersects it,
    so the answer covers a superset of the exact window, rounded up to
    bucket edges: at most one extra bucket of size ≤ 2^(levels-1) at the
    tail.  The newest epochs are exact (size-1 buckets), which is where
    sliding-window queries concentrate.

    Queries stack the O(log horizon) live buckets and fold them through
    the SAME ``register_window_backend`` axis as the dense ring, then
    finalize with one batched ``estimate_many`` — and are memoized per
    instance like every other window read (DESIGN.md §14).  Like the
    hybrid ring, this carrier is host-orchestrated (the bucket list
    changes shape under merges), not a jit-traceable pytree.

    ``to_bytes``/``from_bytes`` is RHLW version 3: the window header
    (flags byte = levels, W = total buckets, cursor field = current
    epoch), a uint32 ``base``, then per bucket a (start, end, size) label
    and a fixed-size RHLB payload, newest first, current bucket first.
    """

    current: SketchBank  # the open bucket at `epoch`
    closed: tuple  # _MRBuckets, NEWEST first, strictly older, non-overlapping
    epoch: int
    base: int  # max buckets per resolution level
    levels: int  # level sizes 1, 2, ..., 2**(levels-1)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def empty(
        cls,
        base: int,
        rows: int,
        cfg: Optional[HLLConfig] = None,
        levels: int = 4,
    ) -> "MultiResWindowedBank":
        cfg = cfg or HLLConfig()
        if base < 1:
            raise ValueError(f"a window needs at least one bucket, got {base}")
        if rows < 1:
            raise ValueError(f"a bank needs at least one row, got {rows}")
        _check_mr_shape(base, levels)
        return cls(SketchBank.empty(rows, cfg), (), 0, base, levels)

    def with_rows(self, rows: int) -> "MultiResWindowedBank":
        """Grow the bank axis to ``rows`` (new rows start empty)."""
        have = self.rows
        if rows < have:
            raise ValueError(f"cannot shrink a {have}-row window to {rows}")
        if rows == have:
            return self
        grow = lambda bank: dataclasses.replace(
            bank,
            registers=jnp.pad(bank.registers, ((0, rows - have), (0, 0))),
            n_items=jnp.pad(bank.n_items, ((0, rows - have), (0, 0))),
        )
        return dataclasses.replace(
            self,
            current=grow(self.current),
            closed=tuple(
                dataclasses.replace(b, bank=grow(b.bank)) for b in self.closed
            ),
        )

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def cfg(self) -> HLLConfig:
        return self.current.cfg

    @property
    def rows(self) -> int:
        return len(self.current)

    def __len__(self) -> int:
        return self.rows

    @property
    def horizon(self) -> int:
        """The answerable span in epochs: base * (2**levels - 1)."""
        return self.base * ((1 << self.levels) - 1)

    @property
    def window(self) -> int:
        """Alias of ``horizon`` — the bound ``last_k`` validates against,
        mirroring the dense ring's W (shared helper, shared message)."""
        return self.horizon

    @property
    def slots(self) -> int:
        """Buckets currently held (current + closed): O(base · levels)."""
        return 1 + len(self.closed)

    def _check_last_k(self, last_k: Optional[int]) -> int:
        return _check_last_k_value(last_k, self.window)

    def _live_buckets(self, last_k: int) -> list:
        """Closed buckets intersecting the last ``last_k`` epochs, newest
        first.  The current bucket is always live and not listed here."""
        floor = self.epoch - last_k
        return [b for b in self.closed if b.end > floor]

    def window_counts(self, last_k: Optional[int] = None) -> np.ndarray:
        """(B,) exact observation counts over the covered buckets.

        Covers the same rounded-up-to-bucket-edges span as the register
        fold, so counters and estimates always describe one window.
        """
        last_k = self._check_last_k(last_k)
        totals = self.current.counts.copy()
        for b in self._live_buckets(last_k):
            totals += b.bank.counts
        return totals

    def density(self) -> dict:
        """Slot/storage introspection: the multi-res counterpart of the
        ring carriers' density surface."""
        per_level = {}
        for b in self.closed:
            per_level[b.size] = per_level.get(b.size, 0) + 1
        nbytes = self.current.nbytes + sum(b.bank.nbytes for b in self.closed)
        dense_slots = min(self.horizon, self.epoch + 1)
        return {
            "horizon": self.horizon,
            "slots": self.slots,
            "rows": self.rows,
            "base": self.base,
            "levels": self.levels,
            "buckets_per_size": dict(sorted(per_level.items())),
            "nbytes": nbytes,
            "dense_ring_nbytes": dense_slots * self.current.nbytes,
            "reduction": (dense_slots * self.current.nbytes) / nbytes
            if nbytes
            else 0.0,
        }

    # ------------------------------------------------------------------
    # ingestion + rotation
    # ------------------------------------------------------------------

    def observe(
        self,
        keys: jnp.ndarray,
        items: jnp.ndarray,
        plan: Optional[ExecutionPlan] = None,
    ) -> "MultiResWindowedBank":
        """Route each item to row ``keys[i]`` of the CURRENT epoch bucket
        (the same fused bank scatter as every other window carrier)."""
        new = self.current.update_many(keys, items, plan)
        if new is self.current:  # the empty-stream short-circuit
            return self
        return dataclasses.replace(self, current=new)

    def advance(self, steps: int = 1) -> "MultiResWindowedBank":
        if steps < 1:
            raise ValueError(f"advance needs steps >= 1, got {steps}")
        return self.advance_to(self.epoch + steps)

    def advance_to(self, epoch: int) -> "MultiResWindowedBank":
        """Rotate forward to ``epoch``, running the slot-merge schedule.

        The just-closed current bucket enters level 0; any level left
        holding more than ``base`` buckets merges its two oldest into the
        next level (top-level overflow drops the oldest bucket — it is at
        the horizon boundary by then, the standard exponential-histogram
        tail).  Skipped epochs insert nothing: empty epochs are implicit
        gaps in the labels, which is why a label's width can exceed its
        logical size.  Monotone like the rings — replaying an old epoch
        is a no-op — and buckets entirely past the horizon expire even
        when no merge touches them.
        """
        target = max(int(epoch), self.epoch)
        if target == self.epoch:
            return self
        closed = list(self.closed)
        if int(self.current.counts.sum()) > 0:
            closed.insert(
                0, _MRBucket(self.epoch, self.epoch, 1, self.current)
            )
            closed = _mr_carry(closed, self.base, self.levels)
        floor = target - self.horizon
        closed = [b for b in closed if b.end > floor]
        return dataclasses.replace(
            self,
            current=SketchBank.empty(self.rows, self.cfg),
            closed=tuple(closed),
            epoch=target,
        )

    # ------------------------------------------------------------------
    # estimation
    # ------------------------------------------------------------------

    def _fold_registers(
        self, last_k: int, plan: Optional[ExecutionPlan]
    ) -> jnp.ndarray:
        """(B, m) fold of every bucket covering the last ``last_k`` epochs.

        Stacks the O(log horizon) live buckets and folds the stack with
        the ring-fold backend registered under ``plan.backend`` — the EH
        rides the same ``register_window_backend`` axis as the dense
        ring, just with a logarithmic ring.  Memoized per instance
        (settled-view idiom, DESIGN.md §14).
        """
        plan = (DEFAULT_PLAN if plan is None else plan).validate()
        backend = get_window_backend(plan.backend)
        # same trace-state rule as the dense ring's cache: never memoize
        # values minted under someone else's jit trace
        cacheable = trace_state_clean()
        if cacheable:
            cache = self.__dict__.setdefault("_fold_cache", {})
            key = (last_k, plan.backend, plan.pipelines, plan.placement)
            hit = cache.get(key)
            if hit is not None:
                obs_metrics.inc("window.fold_cache.hits")
                return hit
            obs_metrics.inc("window.fold_cache.misses")
        stack = jnp.stack(
            [self.current.registers]
            + [b.bank.registers for b in self._live_buckets(last_k)]
        )
        mask = jnp.ones((stack.shape[0],), bool)
        regs = _ring_fold(backend, stack, mask, self.cfg, plan)
        if cacheable:
            cache[key] = regs
        return regs

    def estimate_window(
        self,
        last_k: Optional[int] = None,
        plan: Optional[ExecutionPlan] = None,
        estimator: Optional[str] = None,
    ) -> jnp.ndarray:
        """(B,) float32 distinct counts over (at least) the last ``last_k``
        epochs — rounded up to bucket edges at the tail, exact at the
        full-resolution head."""
        folded = self._fold_registers(self._check_last_k(last_k), plan)
        plan = DEFAULT_PLAN if plan is None else plan
        return _finalize_many(folded, self.cfg, plan, estimator)

    def fold_window(
        self,
        last_k: Optional[int] = None,
        plan: Optional[ExecutionPlan] = None,
    ) -> SketchBank:
        """The covered suffix collapsed to a flat ``SketchBank`` (same
        surface as the ring carriers, so StreamSketch reads are
        carrier-agnostic)."""
        last_k = self._check_last_k(last_k)
        regs = self._fold_registers(last_k, plan)
        totals = self.window_counts(last_k)
        return SketchBank(regs, jnp.asarray(_pack_limbs(totals)), self.cfg)

    # ------------------------------------------------------------------
    # serialization (RHLW v3)
    # ------------------------------------------------------------------

    def to_bytes(self) -> bytes:
        header = _WINDOW_HEADER.pack(
            _WINDOW_MAGIC,
            _WINDOW_VERSION_MULTI,
            self.cfg.p,
            self.cfg.hash_bits,
            self.levels,
            self.cfg.seed,
            self.slots,
            self.rows,
            self.epoch,
        )
        out = [header, _MR_BASE.pack(self.base)]
        labelled = [(self.epoch, self.epoch, 1, self.current)] + [
            (b.start, b.end, b.size, b.bank) for b in self.closed
        ]
        for start, end, size, bank in labelled:
            out.append(_MR_BUCKET.pack(start, end, size))
            out.append(bank.to_bytes())
        return b"".join(out)

    @classmethod
    def from_bytes(cls, data: bytes) -> "MultiResWindowedBank":
        if len(data) < _WINDOW_HEADER.size + _MR_BASE.size:
            raise ValueError(f"truncated window: {len(data)} bytes")
        magic, version, p, hash_bits, levels, seed, slots, rows, epoch = (
            _WINDOW_HEADER.unpack(data[: _WINDOW_HEADER.size])
        )
        if magic != _WINDOW_MAGIC:
            raise ValueError(f"bad magic {magic!r}; not a serialized window")
        if version != _WINDOW_VERSION_MULTI:
            raise ValueError(
                f"unsupported window version {version}; versions 1/2 are "
                "the dense/hybrid rings — parse them with "
                "WindowedBank/HybridWindowedBank.from_bytes"
            )
        if slots < 1 or rows < 1:
            raise ValueError(
                f"window header claims {slots} buckets x {rows} rows"
            )
        (base,) = _MR_BASE.unpack_from(data, _WINDOW_HEADER.size)
        _check_mr_shape(base, levels)
        cfg = HLLConfig(p=p, hash_bits=hash_bits, seed=seed)
        bucket_size = _MR_BUCKET.size + (20 + rows * 8 + rows * cfg.m)
        expected = _WINDOW_HEADER.size + _MR_BASE.size + slots * bucket_size
        if len(data) != expected:
            raise ValueError(
                f"window payload is {len(data)} bytes, expected {expected} "
                f"for {slots} buckets, B={rows}, m={cfg.m}"
            )
        horizon = base * ((1 << levels) - 1)
        size_max = 1 << (levels - 1)
        buckets = []
        off = _WINDOW_HEADER.size + _MR_BASE.size
        for w in range(slots):
            start, end, size = _MR_BUCKET.unpack_from(data, off)
            off += _MR_BUCKET.size
            bank = SketchBank.from_bytes(
                data[off : off + bucket_size - _MR_BUCKET.size]
            )
            off += bucket_size - _MR_BUCKET.size
            if bank.cfg != cfg or len(bank) != rows:
                raise ValueError(f"bucket {w} disagrees with the window header")
            buckets.append((start, end, size, bank))
        start0, end0, size0, current = buckets[0]
        if not (start0 == end0 == epoch and size0 == 1):
            raise ValueError(
                "corrupt multi-resolution labels: the first bucket must be "
                "the open current epoch"
            )
        prev_start, prev_size = start0, None
        closed = []
        for w, (start, end, size, bank) in enumerate(buckets[1:], start=1):
            if not (
                0 <= start <= end < prev_start
                and 1 <= size <= size_max
                and size & (size - 1) == 0
                and size <= end - start + 1
                and (prev_size is None or size >= prev_size)
                and end > epoch - horizon
            ):
                raise ValueError(
                    f"corrupt multi-resolution labels: bucket {w} violates "
                    "the slot-merge schedule invariants"
                )
            prev_start, prev_size = start, size
            closed.append(_MRBucket(start, end, size, bank))
        return cls(current, tuple(closed), epoch, base, levels)


def _check_mr_shape(base: int, levels: int) -> None:
    """Bounds shared by the constructor and the RHLW v3 parser."""
    if base < 1:
        raise ValueError(f"multi-resolution base must be >= 1, got {base}")
    if not 1 <= levels <= _MR_MAX_LEVELS:
        raise ValueError(
            f"multi-resolution levels must be in [1, {_MR_MAX_LEVELS}], "
            f"got {levels}"
        )
    if base * (1 << levels) >= 1 << 31:
        raise ValueError(
            f"horizon base * (2**levels - 1) overflows int32 epochs "
            f"(base={base}, levels={levels})"
        )


def _mr_carry(closed: list, base: int, levels: int) -> list:
    """The exponential-histogram slot-merge schedule (DESIGN.md §14).

    ``closed`` is newest-first with level sizes non-decreasing toward the
    old end.  For each level size s = 1, 2, 4, ...: while the level holds
    more than ``base`` buckets, its two OLDEST merge into one size-2s
    bucket (register max — a lossless union — plus exact counter add).
    The merged bucket is the newest of its new level, so the
    monotone-size invariant is preserved; a top-level overflow drops the
    oldest bucket instead (it sits at the horizon boundary).  Each
    insertion cascades at most once per level: O(levels) merges amortized
    O(1) per epoch.
    """
    size_max = 1 << (levels - 1)
    out = list(closed)
    size = 1
    while size <= size_max:
        idxs = [i for i, b in enumerate(out) if b.size == size]
        while len(idxs) > base:
            oldest = idxs[-1]
            if 2 * size > size_max:
                out.pop(oldest)
                idxs.pop()
                continue
            older, newer = out[oldest], out[oldest - 1]
            out[oldest - 1] = _MRBucket(
                older.start,
                newer.end,
                2 * size,
                newer.bank.merge(older.bank),
            )
            out.pop(oldest)
            idxs.pop()
            idxs.pop()
        size *= 2
    return out
