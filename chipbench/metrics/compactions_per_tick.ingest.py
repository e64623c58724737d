"""Sparse dedup: ``HybridBank`` compactions per tick over the window, from
the program's ``sparse.flush.<cause>`` counters (pressure compactions inside
ingest plus the read-time compaction of the closing read)."""


def read(ctx):
    ticks = ctx.counts.get("ticks", 0)
    found = [v for k, v in ctx.counters.items() if k.startswith("sparse.flush.")]
    if not ticks or not found:
        return None
    return sum(found) / ticks
