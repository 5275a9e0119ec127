"""``recompute_share``: the share of the device's busy time in the traced
window spent recomputing forward work (phase ``recompute`` of
:mod:`bench.scopes`: ``jax.checkpoint``'s second forward, and forward
work inside the fused executor's backward tasks), averaged over the
chips."""
from bench import scopes
from bench import trace as tr


def read(ctx):
    times = scopes.step_times(ctx)
    if times is None:
        return None
    recompute = {n for n, op in scopes.op_names(ctx.hlo).items()
                 if scopes.phase(op) == "recompute"}
    lo, hi = ctx.trace.window()
    shares = [sum(s for n, s in times[d].items() if n in recompute)
              / (tr.busy_ns(ctx.trace.devices[d], lo, hi) * 1e-9)
              for d in times]
    return 100.0 * sum(shares) / len(shares)
