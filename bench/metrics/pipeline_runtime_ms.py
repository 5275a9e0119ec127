"""``pipeline_runtime_ms``: device time a step of the schedule runtime's
own work: ops under the ``pipe`` scope (the tick loop: stash reads and
writes, selects, the tick counter, hops) and under no model scope, so a
stage's layers and the loss computed inside a tick are not counted."""
from bench import scopes


def _runtime(op):
    s = scopes.scopes(op)
    return "pipe" in s and not any(m in s for m in scopes.MODEL)


def read(ctx):
    return scopes.step_ms(ctx, _runtime)
