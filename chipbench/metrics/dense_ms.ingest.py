"""Hybrid carrier: mean host milliseconds per tick in the dense-destined
scatter dispatch of ``HybridBank.update_many`` (the program's
``sparse.dense`` span, exact-shape compiles included), over every tick of
the window."""


def read(ctx):
    ticks = ctx.counts.get("ticks", 0)
    seconds = ctx.counters.get("sparse.dense.seconds")
    if not ticks or seconds is None:
        return None
    return 1e3 * seconds / ticks
