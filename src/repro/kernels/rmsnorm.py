"""Fused RMSNorm as a Pallas TPU kernel.

Small but hot: the norm runs twice per block per token, and unfused it costs
three HBM passes (square-mean, rsqrt-scale, multiply).  The Pallas version
tiles rows into VMEM ([block_rows, d] per grid step) and does the whole
reduction + scale in one pass, fp32 accumulation, bf16 in/out.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x, scale, eps: float = 1e-6, *, block_rows: int = 256,
                   interpret: bool = False):
    """x: [..., D]; scale: [D]."""
    shape = x.shape
    d = shape[-1]
    xf = x.reshape(-1, d)
    rows = xf.shape[0]
    br = min(block_rows, rows)
    pad = (-rows) % br
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(xf.shape[0] // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(xf, scale)
    return out[:rows].reshape(shape)
