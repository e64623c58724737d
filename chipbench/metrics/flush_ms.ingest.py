"""Hybrid carrier: mean host milliseconds per tick's ``flush`` call
(``CoalescingQueue.flush_into`` -> ``HybridBank.update_many``: routing,
pair-log appends, dense dispatch, pressure compaction), over every tick of
the whole window, timed on the host clock around the harness's ``flush``
span, so the periodic pressure compactions weigh as often as they come."""


def read(ctx):
    flush_s = ctx.counts.get("flush_s")
    return 1e3 * sum(flush_s) / len(flush_s) if flush_s else None
