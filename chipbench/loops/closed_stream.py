"""Closed loop of host chunks into one sketch, back to back.

Traffic parameters: ``chunk_items`` (items per update call) and
``pool_items`` (a host pool of uniform 32-bit items made from the seed and
cycled chunk by chunk; repeats are idempotent for the registers and
identical in kernel work).

Set-up warms with one untimed chunk and one estimate.  The window updates
chunk after chunk without blocking, and closes with the estimate on the
host, which waits for every queued update.  ``ingest_items_per_s`` is the
window's items over the time from window start until that estimate.
"""

from __future__ import annotations

import types

from chipbench.bench import gen
from chipbench.bench.harness import Outcome, span


class Loop:
    def __init__(self, traffic: dict, system, rng):
        self.system = system
        self.chunk = int(traffic["chunk_items"])
        pool_items = int(traffic["pool_items"])
        if pool_items % self.chunk:
            raise ValueError("pool_items must be a multiple of chunk_items")
        self.pool = gen.items(pool_items, rng)
        self.n_chunks = pool_items // self.chunk
        with span("update"):
            system.update(self.pool[: self.chunk])
        with span("read"):
            system.estimate()
        self.done = 1  # chunks updated so far, the warm one included

    def _chunk(self, i: int):
        c = i % self.n_chunks
        return self.pool[c * self.chunk : (c + 1) * self.chunk]

    def run(self, window) -> Outcome:
        system, chunk = self.system, self.chunk
        i = self.done
        while window.now() < window.seconds:
            window.poll()
            with span("update"):
                system.update(self._chunk(i))
            i += 1
        with span("read"):
            est = system.estimate()
        elapsed = window.now()
        calls = i - self.done
        self.done = i
        log = types.SimpleNamespace(
            covered=[self.pool[: min(i, self.n_chunks) * chunk]],
            count=i * chunk,
            closing=est,
        )
        return Outcome(
            e2e={"ingest_items_per_s": calls * chunk / elapsed},
            attempted=calls,
            failed=0,
            log=log,
            counts={"calls": calls, "chunk_items": chunk},
            notes=[f"closed loop: {calls} update calls of {chunk} items "
                   f"in {elapsed:.6f} s"],
        )
