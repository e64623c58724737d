"""Device-to-host copies: MiB per tick that the hybrid carrier and the
coalescing queue read back from the device (the program's
``transfer.d2h_bytes`` counter: the bytes of every device array they turn
into a host array), over every tick of the window."""


def read(ctx):
    ticks = ctx.counts.get("ticks", 0)
    nbytes = ctx.counters.get("transfer.d2h_bytes")
    if not ticks or nbytes is None:
        return None
    return nbytes / 2**20 / ticks
