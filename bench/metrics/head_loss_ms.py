"""``head_loss_ms``: device time a step under the ``head_loss`` scope in
every phase: the final norm, the LM head and the chunked cross-entropy,
their backward and the chunks' recomputed forward."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, lambda op: "head_loss" in scopes.scopes(op))
