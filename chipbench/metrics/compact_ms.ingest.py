"""Sparse compaction: host milliseconds per tick in ``HybridBank`` compactions
(the program's ``sparse.compact.read`` and ``sparse.compact.pressure``
spans: pressure compactions inside ingest plus the closing read's), summed
over the window and divided by its ticks."""

CAUSES = ("sparse.compact.read.seconds", "sparse.compact.pressure.seconds")


def read(ctx):
    ticks = ctx.counts.get("ticks", 0)
    found = [ctx.counters[k] for k in CAUSES if k in ctx.counters]
    if not ticks or not found:
        return None
    return 1e3 * sum(found) / ticks
