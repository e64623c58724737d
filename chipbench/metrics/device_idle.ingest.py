"""Device: idle share of the traced sub-window, 1 - (union of device-op
intervals / sub-window), in percent."""


def read(ctx):
    trace = ctx.trace
    if trace is None or not trace.busy or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
