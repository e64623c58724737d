"""One high-cardinality stream into one sketch: ``HyperLogLog.update(items,
plan)`` under the default ``ExecutionPlan()``, with the estimate on the host
at the end (``HyperLogLog.estimate``).

``check`` holds the registers, the exact item counter and the estimate to
the plain reference over the same items.
"""

from __future__ import annotations

import numpy as np

from chipbench.bench import reference
from chipbench.bench.checks import Check


class System:
    kind = "stream"

    def __init__(self, config: dict, rng, control: dict | None = None):
        from repro.sketch import ExecutionPlan, HLLConfig, HyperLogLog

        self.p = int(config["p"])
        hash_bits = int(config["hash_bits"])
        if control and "hash_bits" in control:
            hash_bits = int(control["hash_bits"])
        self.plan = ExecutionPlan()
        self.sketch = HyperLogLog.empty(HLLConfig(p=self.p, hash_bits=hash_bits))

    def update(self, items) -> None:
        self.sketch = self.sketch.update(items, self.plan)

    def estimate(self) -> float:
        """The estimate on the host: waits for every queued update."""
        return self.sketch.estimate()

    def check(self, log, limits: dict) -> list:
        """``log.covered``: arrays that together hold every item updated
        (each once); ``log.count``: items updated, repeats included;
        ``log.closing``: the estimate of the closing read."""
        got_regs = np.asarray(self.sketch.registers)
        got_count = self.sketch.count
        self.sketch = None
        want = reference.sketch_registers(log.covered, self.p)
        want_count = int(log.count)
        chosen, alternative = reference.estimates(want, self.p)
        gap = reference.relative_gap([log.closing], chosen, alternative)
        return [
            Check("reg_wrong", int((got_regs != want).sum()), limits["reg_wrong"]),
            Check("count_wrong", abs(got_count - want_count), limits["count_wrong"]),
            Check("est_rel_gap", float(gap.max()), limits["est_rel_gap"]),
        ]
