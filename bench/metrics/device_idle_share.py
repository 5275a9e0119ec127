"""``device_idle_share``: the share of the traced window in which no
operation ran on a chip, averaged over the cell's chips."""
from bench import trace as tr


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    lo, hi = ctx.trace.window()
    idle = [1.0 - tr.busy_ns(evs, lo, hi) / (hi - lo)
            for evs in ctx.trace.devices.values()]
    return 100.0 * sum(idle) / len(idle)
