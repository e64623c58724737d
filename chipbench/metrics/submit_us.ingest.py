"""Coalescing queue: mean host microseconds per ``submit`` call, from the
harness's ``submit`` spans that lie wholly inside the traced sub-window."""


def read(ctx):
    mean = ctx.trace.span_mean_s("submit") if ctx.trace is not None else None
    return None if mean is None else mean * 1e6
