"""HyperLogLog: the frozen-pytree sketch carrier — the public object API.

Bundles the (m,) uint8 register array with an exact 64-bit item counter and
the static HLLConfig, so a sketch moves through jit, shard_map, checkpoints
and process boundaries as one value.  All methods are pure (return new
carriers); ``merge``/``|`` is the paper's Merge-buckets fold and obeys the
max-lattice laws (associative, commutative, idempotent — DESIGN.md §6).

The item counter is carried as two uint32 limbs (TPU has no int64 datapath;
int32 overflows at 2.1e9 items, far below the paper's high-cardinality
regime), giving an exact count to 2^64 items.

``to_bytes``/``from_bytes`` is the dense wire format (DESIGN.md §7): a 24-byte
header + the raw registers, so a p=16 sketch checkpoints in 64 KiB and merges
across machines that share nothing but this file format.
"""

from __future__ import annotations

import dataclasses
import struct
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracing import span
from repro.sketch import hll, setops, u64 as u64lib
from repro.sketch.dispatch import update_registers
from repro.sketch.hll import HLLConfig
from repro.sketch.plan import ExecutionPlan

_HEADER = struct.Struct("<4sBBBBQQ")  # magic, ver, p, H, flags, seed, n_items
_MAGIC = b"RHLL"
_VERSION = 1


def _counter_zero() -> jnp.ndarray:
    return jnp.zeros((2,), jnp.uint32)


def _counter_add(counter: jnp.ndarray, value) -> jnp.ndarray:
    """64-bit add on the (hi, lo) uint32 limb pair; value is int or limbs."""
    if isinstance(value, (int, np.integer)):
        b = u64lib.from_py(int(value))
    else:
        b = u64lib.U64(value[0], value[1])
    s = u64lib.add(u64lib.U64(counter[0], counter[1]), b)
    return jnp.stack([s.hi, s.lo])


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HyperLogLog:
    """Registers + exact item counter + static config, as one pytree."""

    registers: jnp.ndarray  # (m,) uint8
    n_items: jnp.ndarray  # (2,) uint32: (hi, lo) limbs of the 64-bit count
    cfg: HLLConfig = dataclasses.field(metadata=dict(static=True))

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def empty(cls, cfg: Optional[HLLConfig] = None) -> "HyperLogLog":
        cfg = cfg or HLLConfig()
        return cls(hll.init_registers(cfg), _counter_zero(), cfg)

    @classmethod
    def of(
        cls,
        items: jnp.ndarray,
        cfg: Optional[HLLConfig] = None,
        plan: Optional[ExecutionPlan] = None,
    ) -> "HyperLogLog":
        """One-shot: sketch a whole array."""
        return cls.empty(cfg).update(items, plan)

    # ------------------------------------------------------------------
    # aggregation (paper phase 3)
    # ------------------------------------------------------------------

    def update(
        self, items: jnp.ndarray, plan: Optional[ExecutionPlan] = None
    ) -> "HyperLogLog":
        """Aggregate a batch under ``plan`` (any backend/placement/pipelines).

        A zero-length batch returns ``self`` without dispatching any
        backend (the update is the lattice identity).
        """
        with span("hll.update"):
            if items.size == 0:
                return self
            with span("hll.update.registers"):
                regs = update_registers(self.registers, items, self.cfg, plan)
            with span("hll.update.counter"):
                n_items = _counter_add(self.n_items, items.size)
            return dataclasses.replace(self, registers=regs, n_items=n_items)

    def merge(self, other: "HyperLogLog") -> "HyperLogLog":
        """Merge-buckets fold: element-wise max; counters add exactly."""
        if self.cfg != other.cfg:
            raise ValueError(
                f"cannot merge sketches with different configs: "
                f"{self.cfg} vs {other.cfg}"
            )
        return dataclasses.replace(
            self,
            registers=jnp.maximum(self.registers, other.registers),
            n_items=_counter_add(self.n_items, other.n_items),
        )

    __or__ = merge

    # ------------------------------------------------------------------
    # estimation (paper phase 4) + set algebra
    # ------------------------------------------------------------------

    def estimate(self, estimator: Optional[str] = None) -> float:
        """Exact host-side cardinality estimate (registry-dispatched)."""
        return hll.estimate(self.registers, self.cfg, estimator=estimator)

    def estimate_device(self, estimator: Optional[str] = None) -> jnp.ndarray:
        """Float32 on-device estimator for in-step telemetry."""
        return hll.estimate_device(
            self.registers, self.cfg, estimator=estimator
        )

    def histogram(self) -> jnp.ndarray:
        """Register-value histogram C[k] — the phase-4 intermediate."""
        from repro.sketch.estimators import register_histogram

        return register_histogram(self.registers, self.cfg)

    def union_estimate(
        self, other: "HyperLogLog", estimator: Optional[str] = None
    ) -> float:
        self._check_peer(other)
        return setops.union_estimate(
            self.registers, other.registers, self.cfg, estimator=estimator
        )

    def intersection_estimate(
        self, other: "HyperLogLog", estimator: Optional[str] = None
    ) -> Tuple[float, float]:
        """(|A ∩ B| estimate, absolute-error bound) via inclusion-exclusion."""
        self._check_peer(other)
        return setops.intersection_estimate(
            self.registers, other.registers, self.cfg, estimator=estimator
        )

    def difference_estimate(
        self, other: "HyperLogLog", estimator: Optional[str] = None
    ) -> float:
        self._check_peer(other)
        return setops.difference_estimate(
            self.registers, other.registers, self.cfg, estimator=estimator
        )

    def jaccard(
        self, other: "HyperLogLog", estimator: Optional[str] = None
    ) -> float:
        self._check_peer(other)
        return setops.jaccard_estimate(
            self.registers, other.registers, self.cfg, estimator=estimator
        )

    def _check_peer(self, other: "HyperLogLog") -> None:
        if self.cfg != other.cfg:
            raise ValueError(
                f"set operations need matching configs: {self.cfg} vs {other.cfg}"
            )

    # ------------------------------------------------------------------
    # counters / introspection
    # ------------------------------------------------------------------

    @property
    def count(self) -> int:
        """Exact number of items observed (python int, up to 2^64)."""
        limbs = np.asarray(self.n_items)
        return (int(limbs[0]) << 32) | int(limbs[1])

    @property
    def standard_error(self) -> float:
        return hll.standard_error(self.cfg)

    def duplication(self) -> float:
        """items seen / distinct estimate (stream redundancy factor)."""
        est = self.estimate()
        return (self.count / est) if est > 0 else float("nan")

    # ------------------------------------------------------------------
    # serialization (DESIGN.md §7)
    # ------------------------------------------------------------------

    def to_bytes(self) -> bytes:
        """Dense wire format: 24-byte header + m raw register bytes."""
        header = _HEADER.pack(
            _MAGIC, _VERSION, self.cfg.p, self.cfg.hash_bits, 0,
            self.cfg.seed, self.count,
        )
        regs = np.asarray(self.registers, dtype=np.uint8)
        return header + regs.tobytes()

    @classmethod
    def from_bytes(cls, data: bytes) -> "HyperLogLog":
        if len(data) < _HEADER.size:
            raise ValueError(f"truncated sketch: {len(data)} bytes")
        magic, version, p, hash_bits, _flags, seed, n_items = _HEADER.unpack(
            data[: _HEADER.size]
        )
        if magic != _MAGIC:
            raise ValueError(f"bad magic {magic!r}; not a serialized sketch")
        if version != _VERSION:
            raise ValueError(f"unsupported sketch version {version}")
        cfg = HLLConfig(p=p, hash_bits=hash_bits, seed=seed)
        body = data[_HEADER.size :]
        if len(body) != cfg.m:
            raise ValueError(
                f"register payload is {len(body)} bytes, expected {cfg.m}"
            )
        regs = jnp.asarray(np.frombuffer(body, dtype=np.uint8).copy())
        limbs = jnp.asarray(
            np.asarray([n_items >> 32, n_items & 0xFFFFFFFF], np.uint32)
        )
        return cls(regs, limbs, cfg)
