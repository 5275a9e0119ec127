"""``attn_bwd_pallas_share``: the share of the attention sublayer's
backward (``attn_bwd_ms``: the ``attn`` scope, phase backward) that runs in
the Pallas backward kernels of ``kernels/attention_bwd.py``, known by their
kernel names.  0 where the step has that backward but none of these
kernels, as when it takes the blocked reference's VJP in XLA."""
from bench import trace as tr
from bench.metrics import attn_bwd_ms

KERNELS = ("attention_bwd_stats", "attention_bwd_grads")


def read(ctx):
    total = attn_bwd_ms.read(ctx)
    if not total:
        return None if total is None else 0.0
    events = [e for name in KERNELS
              for e, _ in tr.kernel_events(ctx.trace, ctx.hlo, name) or ()]
    per_step_ms = (1e-6 * sum(e.dur for e in events)
                   / len(ctx.trace.devices) / ctx.steps)
    return 100.0 * per_step_ms / total
