"""Blocked online-softmax attention (flash attention) as a Pallas TPU kernel.

TPU adaptation notes: the CUDA flash-attention kernel is a warp-level
tiling over SRAM; on TPU the same insight — never materialize the
[Sq, Sk] score matrix in HBM — maps onto a Pallas grid over
``(batch*heads, q blocks, k blocks)`` with the k-block axis innermost and
``arbitrary`` (sequential) semantics, VMEM BlockSpecs feeding the MXU, and
fp32 running-max / running-sum accumulators held in VMEM scratch across
k-block steps.

The sweep is the backward's (:mod:`repro.kernels.attention_bwd`) on the same
tiling (:mod:`repro.kernels.tiling`):

* Transposed scores ``sᵀ = k (q · scale)ᵀ`` (keys down, queries across), so
  the running max and sum are rows that broadcast down the sublanes and
  every reduction runs across vector registers; the output is accumulated
  as ``Oᵀ = vᵀ pᵀ`` and transposed once, in VMEM, when its q block ends.
* The products take their operands in the inputs' dtype with f32
  accumulation: q pre-scaled and rounded to that dtype, ``p`` cast to v's
  just before its product.  Scores, ``exp``, the running max and sum and
  the accumulator stay f32.  bf16 inputs make single-pass MXU products;
  f32 inputs keep f32 products.
* One block size ``b`` for queries and keys (``BLOCK``, or the whole
  shorter sequence); k/v blocks that the causal or window mask hides from
  a q block are skipped with ``pl.when`` and their index maps clamped to
  the nearest needed block, so they cost no DMA either.  Only pairs that
  straddle the mask's edge (or the padding) build a mask.

GQA is handled without materializing repeated KV: the kv index maps fold
the query-head index down by the group size.  Supports causal masking,
sliding windows (SWA), cross lengths (``Sq != Sk``) and a static
``q_offset`` so the same kernel serves chunked prefill.

The kernel body and index maps use ``lax`` primitives only, for the reason
:mod:`repro.kernels.tiling` gives: the backward kernels use jnp's helpers
at the same shapes, and must not inherit this file's source locations.
The ``pallas_call`` is named ``flash_attention``, as the backward's are
named: inside a rematerialised layer loop the body's operations carry the
call site's locations, not this file's, and a profile knows the kernel by
its name alone.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import Tiling, block_size, clamp, vmem_limit

NEG_INF = -1e30
BLOCK = 1024                        # most query / key rows of one grid step
_NT = (((1,), (1,)), ((), ()))      # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ @ b
_F32 = jnp.float32


def _kernel(q_ref, k_ref, v_ref, o_ref, qs_ref, ot_ref, m_ref, l_ref, *,
            scale: float, tiling: Tiling):
    t = tiling
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(lax.eq(ki, 0))
    def _init():
        q = q_ref[0]
        qs_ref[...] = lax.convert_element_type(
            lax.mul(lax.convert_element_type(q, _F32), scale), q.dtype)
        ot_ref[...] = lax.full(ot_ref.shape, 0.0, _F32)
        m_ref[...] = lax.full(m_ref.shape, NEG_INF, _F32)
        l_ref[...] = lax.full(l_ref.shape, 0.0, _F32)

    def step(masked):
        st = lax.dot_general(k_ref[0], qs_ref[...], _NT,
                             preferred_element_type=_F32)   # [keys, queries]
        if masked:
            st = t.mask(st, qi, ki)
        m_prev = m_ref[...]                                  # [1, queries]
        m_new = lax.max(m_prev, lax.expand_dims(lax.reduce_max(st, (0,)), (0,)))
        alpha = lax.exp(lax.sub(m_prev, m_new))
        pt = lax.exp(lax.sub(st, m_new))
        l_ref[...] = lax.add(lax.mul(alpha, l_ref[...]),
                             lax.expand_dims(lax.reduce_sum(pt, (0,)), (0,)))
        m_ref[...] = m_new
        v = v_ref[0]
        pv = lax.dot_general(v, lax.convert_element_type(pt, v.dtype), _TN,
                             preferred_element_type=_F32)   # [Dv, queries]
        ot_ref[...] = lax.add(lax.mul(alpha, ot_ref[...]), pv)

    t.when_needed(t.sees(qi, ki), qi, ki, step)

    @pl.when(lax.eq(ki, t.nk - 1))
    def _finish():
        l = lax.max(l_ref[...], 1e-30)
        o_ref[0] = lax.convert_element_type(
            lax.transpose(lax.div(ot_ref[...], l), (1, 0)), o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: Optional[float] = None,
                    block: Optional[int] = None, interpret: bool = False):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Sk, D]; Hq % Hkv == 0.  A grid step
    takes ``block`` queries against ``block`` keys: by default ``BLOCK``,
    or the whole shorter sequence."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    b = block or block_size(min(Sq, Sk), BLOCK)
    nq, nk = -(-Sq // b), -(-Sk // b)

    def flat(x, s, n):
        x = x.reshape(-1, s, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, n * b - s), (0, 0))) if n * b > s else x
    qf, kf, vf = flat(q, Sq, nq), flat(k, Sk, nk), flat(v, Sk, nk)
    tiling = Tiling(causal=causal, window=window, sk=Sk, b=b, nq=nq, nk=nk,
                    q_offset=q_offset)

    def kv_block(bh, qi, ki):
        return (lax.div(bh, group), clamp(ki, *tiling.k_range(qi)), 0)
    q_block = lambda bh, qi, ki: (bh, qi, 0)
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, tiling=tiling),
        grid=(B * Hq, nq, nk),
        in_specs=[pl.BlockSpec((1, b, D), q_block),
                  pl.BlockSpec((1, b, D), kv_block),
                  pl.BlockSpec((1, b, Dv), kv_block)],
        out_specs=pl.BlockSpec((1, b, Dv), q_block),
        out_shape=jax.ShapeDtypeStruct((B * Hq, nq * b, Dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((b, D), q.dtype),     # q * scale
                        pltpu.VMEM((Dv, b), _F32),       # Oᵀ, unnormalised
                        pltpu.VMEM((1, b), _F32),        # running max
                        pltpu.VMEM((1, b), _F32)],       # running sum
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(b)),
        interpret=interpret,
        name="flash_attention",
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, Hq, Sq, Dv)
