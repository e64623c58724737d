"""Hybrid carrier: mean host milliseconds per tick in the routing part of
``HybridBank.update_many`` (the program's ``sparse.route`` span: key checks,
the slot-map read-back, sub-stream selection, the pending-log append, the
exact counters and the pressure check), over every tick of the window."""


def read(ctx):
    ticks = ctx.counts.get("ticks", 0)
    seconds = ctx.counters.get("sparse.route.seconds")
    if not ticks or seconds is None:
        return None
    return 1e3 * seconds / ticks
