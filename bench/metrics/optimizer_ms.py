"""``optimizer_ms``: device time a step under the ``optimizer`` scope
(``optim.apply``): Adam, the non-finite guard, the gradient norm and
clipping, the bf16 copies of the fp32 masters."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, lambda op: "optimizer" in scopes.scopes(op))
