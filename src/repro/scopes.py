"""The device scopes of the train step: one fixed set of names.

Each name is a ``jax.named_scope`` opened where that work is traced, so it
becomes a component of the ``op_name`` metadata of every HLO instruction
made under it: ``.../stage/.../attn/dot_general`` for a forward op, the
same path inside ``transpose(...)`` for its autodiff backward, and inside
``.../rematted_computation/...`` for a forward that ``jax.checkpoint``
recomputes.  A profiler trace of the compiled step then tells the device
time of each layer from the ops' names (``bench/scopes.py`` reads them).
Scopes add no equations: the compiled program is the same, with more
metadata.

Per-tick stage and micro-batch indices are traced values inside the tick
loop's ``lax.scan``, so they cannot be part of a name.
"""
from __future__ import annotations

import functools

import jax

EMBED = "embed"              # token embedding lookup, and its VJP
STAGE = "stage"              # one pipeline stage's layer loop
ATTN = "attn"                # attention sublayer: projections, RoPE, kernel
MLP = "mlp"
NORM = "norm"                # RMSNorm / LayerNorm
HEAD_LOSS = "head_loss"      # final norm, LM head, chunked cross-entropy
PIPE = "pipe"                # the tick loop: stash reads/writes, selects
PIPE_HOP = "pipe_hop"        # chain, skip-route and input-stream hops
PIPE_F = "pipe_f"            # fused executor's task branches, by kind
PIPE_B = "pipe_b"
PIPE_BX = "pipe_bx"
PIPE_BW = "pipe_bw"
GRAD_REDUCE = "grad_reduce"  # micro-axis gradient sums, DP compression
OPTIMIZER = "optimizer"      # optim.apply

NAMES = (EMBED, STAGE, ATTN, MLP, NORM, HEAD_LOSS, PIPE, PIPE_HOP, PIPE_F,
         PIPE_B, PIPE_BX, PIPE_BW, GRAD_REDUCE, OPTIMIZER)


def scoped(name: str):
    """Decorator: trace the function under ``jax.named_scope(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap
