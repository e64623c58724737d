"""A million-tenant distinct-count fleet over the chips of one host:
``CoalescingQueue`` in front of a ``HybridBank`` under
``ExecutionPlan().with_sharding(mesh)``, so the bank keeps its tenant rows
in contiguous blocks, one per chip, and reads through
``HybridBank.estimate_many`` under the same plan.

Tenant popularity is YCSB's scrambled zipfian: the traffic's zipfian rank
maps to its tenant row by FNV-1a 64 of the rank (YCSB ``Utils.fnvhash64``:
the rank's 8 little-endian bytes, then ``Math.abs``) modulo the tenant
count.  The map is a table built once, so a submit costs one gather.

Set-up builds the start state through the program's own ingest path: one
``update_many`` of the configuration's history pairs under the sharded
plan, then ``compact``, so the whole-fleet (B, m) dense form never exists
anywhere.

``check`` holds the state after the window to the plain reference
(``chipbench.bench.reference``) in row ranges of at most 2^16 rows, so that
neither the host nor a chip holds a (B, m) array: every tenant's registers
(dense or sparse), the exact counters, and the closing read's estimates.
"""

from __future__ import annotations

import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.bench import gen, reference
from chipbench.bench.checks import Check

RANGE_BITS = 16  # rows per compared range: 2^16
CHECK_THREADS = 8  # ranges compared at once (each holds ~3 GiB at p=14)
_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def fnv1a64_rows(ranks: np.ndarray, rows: int) -> np.ndarray:
    """YCSB's scrambled-zipfian map: ``abs(fnvhash64(rank)) % rows``."""
    v = ranks.astype(np.uint64)
    h = np.full(v.shape, _FNV_OFFSET, np.uint64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h ^= (v >> np.uint64(8 * i)) & np.uint64(0xFF)
            h *= _FNV_PRIME
    signed = h.view(np.int64)
    return (np.abs(signed) % rows).astype(np.int32)


class System:
    kind = "keyed"

    def __init__(self, config: dict, rng, control: dict | None = None):
        import jax

        from repro.serve.coalesce import CoalescingQueue
        from repro.sketch import ExecutionPlan, HLLConfig, HybridBank

        self.rows = int(config["tenants"])
        self.p = int(config["p"])
        hash_bits = int(config["hash_bits"])
        if control and "hash_bits" in control:
            hash_bits = int(control["hash_bits"])
        blocks = int(config["row_blocks"])
        if jax.device_count() < blocks:
            raise RuntimeError(
                f"{blocks} row blocks need {blocks} devices, "
                f"JAX has {jax.device_count()}"
            )
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:blocks]), ("data",))
        self.plan = ExecutionPlan().with_sharding(mesh)
        self.row_of_rank = fnv1a64_rows(np.arange(self.rows), self.rows)
        start = config["start_state"]
        n = int(start["history_pairs"])
        self.history = (
            self.row_of_rank[gen.keys(start["keys"], self.rows, n, rng)],
            gen.items(n, rng),
        )
        cfg = HLLConfig(p=self.p, hash_bits=hash_bits)
        t0 = time.perf_counter()
        # settled, as a fleet that has run for a while is: promoted rows
        # take the dense path from the first tick
        self.bank = (
            HybridBank.empty(self.rows, cfg)
            .update_many(self.history[0], self.history[1], self.plan)
            .compact()
        )
        print(
            f"[hybrid_fleet] start state: {n} pairs ingested in "
            f"{time.perf_counter() - t0!r} s",
            file=sys.stderr,
            flush=True,
        )
        self.queue = CoalescingQueue()

    # -- the calls the loops make -------------------------------------------

    def submit(self, keys, items) -> None:
        self.queue.submit(self.row_of_rank[keys], items)

    def flush(self) -> None:
        self.bank = self.queue.flush_into(self.bank, self.plan)

    def read(self) -> np.ndarray:
        """Every row's estimate on the host."""
        return np.asarray(self.bank.estimate_many(plan=self.plan))

    # -- the comparison with the plain reference -----------------------------

    def check(self, log, limits: dict) -> list:
        """``log.flushed``: per flush, its (rank keys, items) arrays, in
        order.  ``log.closing``: the whole-fleet estimates of the closing
        read."""
        peaks = [
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.plan.mesh.devices.flat
        ]
        print(
            f"[hybrid_fleet] peak_bytes_in_use per chip {peaks}",
            file=sys.stderr,
            flush=True,
        )
        from repro.obs import metrics

        counters = metrics.snapshot()["counters"]  # filled in traced runs
        seen = {
            k: v
            for k, v in sorted(counters.items())
            if k.startswith(("sparse.flush.", "sparse.dedup.wide"))
        }
        print(f"[hybrid_fleet] window counters {seen}", file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        rows, p = self.rows, self.p
        streams = [self.history] + [
            (self.row_of_rank[k], x) for batch in log.flushed for k, x in batch
        ]
        want_counts = np.zeros(rows, np.uint64)
        n_ranges = (rows + (1 << RANGE_BITS) - 1) >> RANGE_BITS
        by_range = [[] for _ in range(n_ranges)]
        for keys, items in streams:
            want_counts += np.bincount(keys, minlength=rows).astype(np.uint64)
            r = (keys >> RANGE_BITS).astype(np.uint8)
            order = np.argsort(r, kind="stable")
            bounds = np.searchsorted(r[order], np.arange(n_ranges + 1))
            for i in range(n_ranges):
                sel = order[bounds[i] : bounds[i + 1]]
                by_range[i].append((keys[sel] - (i << RANGE_BITS), items[sel]))
        got_counts = self.bank.counts

        def compare(i):
            lo = i << RANGE_BITS
            hi = min(rows, lo + (1 << RANGE_BITS))
            want = reference.bank_registers(by_range[i], hi - lo, p)
            by_range[i] = None
            got = self.bank.row_registers(lo, hi)
            chosen, alternative = reference.estimates(want, p)
            gap = reference.relative_gap(log.closing[lo:hi], chosen, alternative)
            return int((got != want).any(axis=1).sum()), float(gap.max())

        with ThreadPoolExecutor(CHECK_THREADS) as pool:
            per_range = list(pool.map(compare, range(n_ranges)))
        print(
            f"[hybrid_fleet] check: {n_ranges} ranges in "
            f"{time.perf_counter() - t0!r} s",
            file=sys.stderr,
            flush=True,
        )
        reg_wrong = sum(w for w, _ in per_range)
        gap = max(g for _, g in per_range)
        self.bank = None
        return [
            Check("reg_rows_wrong", reg_wrong, limits["reg_rows_wrong"]),
            Check(
                "count_rows_wrong",
                int((got_counts != want_counts).sum()),
                limits["count_rows_wrong"],
            ),
            Check("est_rel_gap", gap, limits["est_rel_gap"]),
        ]
