"""Share of the window in which a collective ran on a chip and no other
operation did, averaged over the chips (from the profiler trace);
nothing when no collective ran in the window."""


def read(ctx):
    t = ctx["trace"]
    if t.collective_exposed_s is None or t.window_s <= 0:
        return None
    return 100.0 * t.collective_exposed_s / t.window_s
