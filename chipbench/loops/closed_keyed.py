"""Closed loop of coalesced ticks into a keyed carrier, back to back.

Traffic parameters: ``tick_pairs`` (key, item) pairs per tick, submitted in
``submits_per_tick`` equal submits and landed with one flush; ``keys``
(their distribution over the tenants); ``pool_ticks`` (how many ticks of
keys and items the seed draws).  Tick ``t`` takes pool slot ``t mod
pool_ticks`` and XORs its items with a mask unique to ``t``, so items are
fresh every tick and no two ticks carry the same pairs.

Set-up warms with one untimed tick and one whole-bank read.  The window runs
ticks without blocking between them and closes with one whole-bank read on
the host.  ``ingest_items_per_s`` is the window's pairs over the time from
window start until that read is on the host.
"""

from __future__ import annotations

import time
import types

from chipbench.bench import gen
from chipbench.bench.harness import Outcome, span


class Loop:
    def __init__(self, traffic: dict, system, rng):
        self.system = system
        self.tick_pairs = int(traffic["tick_pairs"])
        self.submits = int(traffic["submits_per_tick"])
        if self.tick_pairs % self.submits:
            raise ValueError("tick_pairs must be a multiple of submits_per_tick")
        slots = int(traffic["pool_ticks"])
        n = slots * self.tick_pairs
        self.keys = gen.keys(traffic["keys"], system.rows, n, rng).reshape(slots, -1)
        self.items = gen.items(n, rng).reshape(slots, -1)
        self.log = types.SimpleNamespace(flushed=[], closing=None)
        self.flush_s = []  # host seconds of each tick's flush
        self._tick(0)
        with span("read"):
            system.read()
        self.t = 1

    def pairs(self, t: int):
        """Tick ``t``'s (keys, items)."""
        slot = t % self.keys.shape[0]
        return self.keys[slot], self.items[slot] ^ gen.tick_mask(t)

    def _tick(self, t: int) -> None:
        system = self.system
        with span("gen"):
            keys, items = self.pairs(t)
        step = self.tick_pairs // self.submits
        for s in range(0, self.tick_pairs, step):
            with span("submit"):
                system.submit(keys[s : s + step], items[s : s + step])
        t0 = time.perf_counter()
        with span("flush"):
            system.flush()
        self.flush_s.append(time.perf_counter() - t0)
        self.log.flushed.append([(keys, items)])

    def run(self, window) -> Outcome:
        ticks = 0
        self.flush_s.clear()
        while window.now() < window.seconds:
            window.poll()
            self._tick(self.t)
            self.t += 1
            ticks += 1
        with span("read"):
            self.log.closing = self.system.read()
        elapsed = window.now()
        return Outcome(
            e2e={"ingest_items_per_s": ticks * self.tick_pairs / elapsed},
            attempted=ticks,
            failed=0,
            log=self.log,
            counts={"ticks": ticks, "flush_s": list(self.flush_s)},
            notes=[f"closed loop: {ticks} ticks of {self.tick_pairs} pairs "
                   f"in {elapsed:.6f} s"],
        )
