"""Request coalescing: many tenants' pending updates, ONE ingest per tick.

The paper's FPGA wins sustained line rate because ingest never waits on a
per-request round trip; the serving mirror of that (DESIGN.md §16) is a
coalescing queue in front of the bank.  Tenants ``submit()`` their keyed
token streams as they arrive — cheap host-side appends, no device work —
and a periodic tick ``drain()``s the queue into one merged (keys, items)
batch that lands with a single fused ``update_many`` dispatch.  N
per-tenant batches and their concatenation are bit-identical by the §6
lattice laws (register max is associative/commutative/idempotent, and the
exact counters add), so coalescing is pure batching: it can change WHEN a
register moves, never WHERE it lands (tests/test_serve_path.py).

Double-buffered host→device staging: ``drain(stage=True)`` device_puts
the merged batch through a two-slot ring.  jax transfers and kernel
dispatch are async, so while the device scatters tick N's batch the host
is already concatenating and staging tick N+1's into the other slot —
hashing overlaps scatter, the paper's ping-pong BRAM staging in XLA
terms.  The ring keeps a strong reference to both in-flight batches so
neither can be donated or collected before its scatter retires.
Host-orchestrated carriers (HybridBank's append buffer) consume the
merged batch on host instead via ``drain(stage=False)``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.obs import metrics as obs_metrics
from repro.obs.metrics import to_host

__all__ = ["CoalescingQueue", "DoubleBuffer", "SharedWindowRing"]


class DoubleBuffer:
    """Two-slot host→device staging ring (ping-pong transfer buffers)."""

    def __init__(self, depth: int = 2):
        if depth < 2:
            raise ValueError(f"staging needs >= 2 slots, got {depth}")
        self._slots = [None] * depth
        self._tick = 0

    @property
    def depth(self) -> int:
        return len(self._slots)

    def stage(self, *host_arrays, sharding=None) -> Tuple[jax.Array, ...]:
        """Async-transfer ``host_arrays``; returns the device handles.

        ``sharding`` places them (None: the default device).  Rotates
        through the slot ring, so the previous tick's buffers stay pinned
        while its scatter is still in flight and the slot being
        overwritten is always the oldest (already-retired) one.
        """
        staged = tuple(jax.device_put(a, sharding) for a in host_arrays)
        self._slots[self._tick % len(self._slots)] = staged
        self._tick += 1
        return staged


class CoalescingQueue:
    """Pending per-tenant updates, drained as one merged batch per tick."""

    def __init__(self, staging_depth: int = 2):
        self._chunks = []  # [(keys int32, items int32), ...] host-side
        self._staging = DoubleBuffer(staging_depth)
        self.ticks = 0

    def submit(self, keys, items) -> int:
        """Queue one tenant batch (host append, no device work); returns
        the number of items pending after the append."""
        keys = to_host(keys).reshape(-1).astype(np.int32, copy=False)
        items = to_host(items).reshape(-1)
        if keys.shape[0] != items.shape[0]:
            raise ValueError(
                f"keys ({keys.shape[0]}) and items ({items.shape[0]}) "
                f"must flatten to the same length"
            )
        if keys.shape[0]:
            self._chunks.append((keys, items))
            obs_metrics.inc("serve.coalesce.submitted")
        return self.pending_items()

    def submit_row(self, row: int, items) -> int:
        """``submit`` with every item routed to one tenant row."""
        items = to_host(items).reshape(-1)
        return self.submit(np.full(items.shape[0], row, np.int32), items)

    def pending_batches(self) -> int:
        return len(self._chunks)

    def pending_items(self) -> int:
        return sum(k.shape[0] for k, _ in self._chunks)

    def drain(self, stage: bool = True, sharding=None) -> Optional[Tuple]:
        """Pop everything pending as ONE merged (keys, items) batch.

        ``stage=True`` routes the merge through the double buffer (placed
        by ``sharding``) and returns device handles (the fused-scatter
        path); ``stage=False``
        returns the host arrays for host-orchestrated carriers.  An
        empty queue returns None — a tick with no traffic must not
        dispatch anything.
        """
        if not self._chunks:
            return None
        chunks, self._chunks = self._chunks, []
        keys = np.concatenate([k for k, _ in chunks])
        items = np.concatenate([x for _, x in chunks])
        self.ticks += 1
        obs_metrics.inc("serve.coalesce.ticks")
        obs_metrics.observe("serve.coalesce.batches_per_tick", len(chunks))
        obs_metrics.observe("serve.coalesce.batch_items", keys.shape[0])
        if stage:
            return self._staging.stage(keys, items, sharding=sharding)
        return keys, items

    def flush_into(self, bank, plan=None):
        """Drain into ``bank`` with ONE ``update_many``; returns the new
        bank (unchanged when nothing is pending).  Device-stages unless
        the carrier ingests on host (a ``pending_pairs`` surface marks
        the HybridBank append-buffer family); a sharded ``plan`` stages
        the batch replicated over its mesh, where every row block reads
        the whole stream, instead of on one device."""
        host_carrier = hasattr(bank, "pending_pairs")
        sharding = None
        if plan is not None and plan.placement == "sharded":
            sharding = NamedSharding(plan.mesh, PartitionSpec())
        merged = self.drain(stage=not host_carrier, sharding=sharding)
        if merged is None:
            return bank
        return bank.update_many(merged[0], merged[1], plan)


class SharedWindowRing:
    """Process-wide window rings shared across requests (DESIGN.md §16).

    The §14 fold decomposition and fold cache amortize per INSTANCE; a
    ring constructed per request pays the rebuild every time.  Serving
    code gets-or-creates one ring per (carrier, shape, config) key and
    writes functional updates back with ``swap``, so every request's
    read hits the same decomposed state.
    """

    _rings: dict = {}

    @classmethod
    def get_or_create(cls, key, factory):
        ring = cls._rings.get(key)
        if ring is None:
            ring = cls._rings[key] = factory()
            obs_metrics.inc("serve.window_ring.created")
        else:
            obs_metrics.inc("serve.window_ring.shared")
        return ring

    @classmethod
    def swap(cls, key, ring):
        """Publish an updated ring under ``key``; returns it."""
        cls._rings[key] = ring
        return ring

    @classmethod
    def reset(cls) -> None:
        """Drop every shared ring (tests and process teardown)."""
        cls._rings.clear()
