"""Mean rows in a micro-batch the engine ran in the window: requests
served over batches run (the engine's own counters)."""


def read(ctx):
    c = ctx["counters"]
    if c["batches"] <= 0:
        return None
    return c["served"] / c["batches"]
