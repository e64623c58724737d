"""Update path: mean host microseconds per ``HyperLogLog.update`` call (the
program's ``hll.update`` span: plan and registry dispatch of the register
update, and the exact item counter), over every call of the window."""


def read(ctx):
    seconds = ctx.counters.get("hll.update.seconds")
    calls = ctx.counters.get("hll.update.calls")
    if seconds is None or not calls:
        return None
    return 1e6 * seconds / calls
