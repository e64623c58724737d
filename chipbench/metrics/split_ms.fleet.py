"""Row-block routing: mean host milliseconds per tick in which
``HybridBank.update_many`` splits a tick by tenant-row block under the
sharded placement (the program's ``sparse.shard.split`` span: key checks,
block ids, re-based per-block sub-streams), over every tick of the window."""


def read(ctx):
    ticks = ctx.counts.get("ticks", 0)
    seconds = ctx.counters.get("sparse.shard.split.seconds")
    if not ticks or seconds is None:
        return None
    return 1e3 * seconds / ticks
