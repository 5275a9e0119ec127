"""``flash_attn_fwd_roofline``: the Pallas flash-attention forward
(``kernels/flash_attention.py``) against its roofline.

The kernels are the compiled step's ``tpu_custom_call`` instructions made
in ``flash_attention``.  For each call, from its shapes (q ``[BH, S, D]``,
k and v ``[BHkv, S, D]``, out ``[BH, S, D]``; causal self-attention):
FLOPs ``BH x S (S + 1) / 2 x 2 x (D + Dv)`` for QK and PV over the causal
pairs, and the bytes of those of q, k, v and the output that live in HBM,
each read or written once (XLA may hold an operand in the core's own
memory).  The least time of a call is the larger of FLOPs over the bf16
peak and bytes over the HBM bandwidth (FLOPs, at every cell's shapes); the
share is the sum of least times over the summed device time of the
kernel's operations.
"""
from bench import trace as tr


def cost(kernel):
    """(FLOPs, HBM bytes) of one call."""
    q, k, v = kernel.operands[:3]
    (bh, sq, d), (_, sk, _), (_, _, dv) = q.shape, k.shape, v.shape
    if sq != sk:
        raise ValueError(f"flash attention call {kernel.instruction} is not "
                         f"self-attention: {sq} queries, {sk} keys")
    flops = bh * sq * (sq + 1) / 2 * 2 * (d + dv)
    nbytes = sum(a.nbytes for a in (q, k, v, kernel.result) if a.space == 0)
    return flops, nbytes


def read(ctx):
    if ctx.trace is None:
        return None
    events = tr.kernel_events(ctx.trace, ctx.hlo, "flash_attention")
    if not events:
        return None
    least = 0.0
    for _, k in events:
        flops, nbytes = cost(k)
        least += max(flops / ctx.peaks["bf16_flops"],
                     nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / (sum(e.dur for e, _ in events) * 1e-9)
