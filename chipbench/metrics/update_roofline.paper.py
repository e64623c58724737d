"""Update path: share of the HBM roofline in the traced sub-window.

The work the algorithm needs is read from the traffic, not from the
program: 4 bytes per item streamed in, plus one read and one write of the
m register bytes per update call, for each ``update`` span that lies
wholly inside the traced sub-window.  The least time for it is those bytes
over the device's published HBM bandwidth (``peaks.json``); the share is
that time over the device's busy time in the sub-window.  Bandwidth bounds
the update: the hash is integer VPU work with no published peak.  The same
bytes count whatever implements the update.
"""


def read(ctx):
    trace = ctx.trace
    calls = trace.span_count("update") if trace is not None else 0
    if not calls or not trace.busy or trace.busy_s <= 0 or not ctx.peaks:
        return None
    m = 1 << int(ctx.config["p"])
    chunk = int(ctx.counts["chunk_items"])
    needed = calls * (4 * chunk + 2 * m)
    return 100.0 * needed / ctx.peaks["hbm_bytes_per_s"] / trace.busy_s
