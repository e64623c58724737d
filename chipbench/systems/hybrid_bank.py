"""A multi-tenant distinct-count service: ``CoalescingQueue`` in front of a
``HybridBank``, read through ``HybridBank.estimate_many``.

Set-up builds the start state from the configuration's history: one dense
``SketchBank.update_many`` over the history pairs (shapes fixed by the
configuration, not the seed), then ``HybridBank.from_dense`` with the
default promotion threshold.  Every call runs under the default
``ExecutionPlan()``.

``check`` holds the state after the window to the plain reference
(``chipbench.bench.reference``) computed from the same pairs: every
tenant's registers, dense row or sparse, the exact counters, and the
estimate of the closing read.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from chipbench.bench import gen, reference
from chipbench.bench.checks import Check

class System:
    kind = "keyed"

    def __init__(self, config: dict, rng, control: dict | None = None):
        from repro.serve.coalesce import CoalescingQueue
        from repro.sketch import ExecutionPlan, HLLConfig, HybridBank, SketchBank

        self.config = config
        self.rows = int(config["tenants"])
        self.p = int(config["p"])
        hash_bits = int(config["hash_bits"])
        if control and "hash_bits" in control:
            hash_bits = int(control["hash_bits"])
        self.plan = ExecutionPlan()
        cfg = HLLConfig(p=self.p, hash_bits=hash_bits)
        start = config["start_state"]
        n = int(start["history_pairs"])
        self.history = (
            gen.keys(start["keys"], self.rows, n, rng),
            gen.items(n, rng),
        )
        dense = SketchBank.empty(self.rows, cfg).update_many(
            jnp.asarray(self.history[0]), jnp.asarray(self.history[1]), self.plan
        )
        self.bank = HybridBank.from_dense(dense)
        del dense
        self.queue = CoalescingQueue()

    # -- the calls the loops make -------------------------------------------

    def submit(self, keys, items) -> None:
        self.queue.submit(keys, items)

    def flush(self) -> None:
        self.bank = self.queue.flush_into(self.bank, self.plan)

    def read(self) -> np.ndarray:
        """Every row's estimate on the host."""
        return np.asarray(self.bank.estimate_many(plan=self.plan))

    # -- the comparison with the plain reference -----------------------------

    def check(self, log, limits: dict) -> list:
        """``log.flushed``: per flush, its (keys, items) arrays, in order.
        ``log.closing``: the whole-bank estimates of the closing read."""
        rows, p = self.rows, self.p
        got_regs = np.asarray(self.bank.to_dense().registers)
        got_counts = self.bank.counts
        self.bank = None

        writes = [(k, x) for batch in log.flushed for k, x in batch]
        final = reference.bank_registers([self.history] + writes, rows, p)
        want_counts = np.bincount(self.history[0], minlength=rows).astype(np.uint64)
        for k, _ in writes:
            want_counts += np.bincount(k, minlength=rows).astype(np.uint64)
        checks = [
            Check(
                "reg_rows_wrong",
                int((got_regs != final).any(axis=1).sum()),
                limits["reg_rows_wrong"],
            ),
            Check(
                "count_rows_wrong",
                int((got_counts != want_counts).sum()),
                limits["count_rows_wrong"],
            ),
        ]
        chosen, alternative = reference.estimates(final, p)
        gap = reference.relative_gap(log.closing, chosen, alternative)
        checks.append(Check("est_rel_gap", float(gap.max()), limits["est_rel_gap"]))
        return checks

