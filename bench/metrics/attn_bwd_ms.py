"""``attn_bwd_ms``: device time a step in the backward of the attention
sublayer (the ``attn`` scope, phase backward): the autodiff backward of
the q/k/v/o projections and RoPE, and the attention kernel's backward
(``ops._attention_bwd``, the blocked reference's VJP)."""
from bench import scopes


def read(ctx):
    return scopes.step_ms(ctx, lambda op: "attn" in scopes.scopes(op)
                          and scopes.phase(op) == "backward")
