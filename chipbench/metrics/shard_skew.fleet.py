"""Row-block routing: the largest block's share of the window's pairs over
the mean block's (the program's ``sparse.shard.pairs.<d>`` counters, one per
row block); 1.0 is an even split, and the busiest chip sets the pace."""

PREFIX = "sparse.shard.pairs."


def read(ctx):
    per_block = [v for k, v in ctx.counters.items() if k.startswith(PREFIX)]
    if not per_block or not sum(per_block):
        return None
    return max(per_block) * len(per_block) / sum(per_block)
