"""``mfu``: model FLOPs of the window's steps over window x chips x peak.

The FLOPs of one step come from the configuration's reference module
(``step_flops``, e.g. :func:`bench.configs.llama.step_flops`), which counts
the matrix products and the attention the family needs; recomputed work is
not counted.
"""


def read(ctx):
    if ctx.steps == 0:
        return None
    flops = ctx.steps * ctx.reference.step_flops(
        ctx.sizes, ctx.traffic["global_batch"], ctx.traffic["seq_len"])
    return 100.0 * flops / (ctx.window_s * ctx.chips * ctx.peaks["bf16_flops"])
