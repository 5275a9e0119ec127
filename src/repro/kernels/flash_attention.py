"""Blocked online-softmax attention (flash attention) as a Pallas TPU kernel.

TPU adaptation notes (DESIGN.md §2): the CUDA flash-attention kernel is a
warp-level tiling over SRAM; on TPU the same insight — never materialize the
[Sq, Sk] score matrix in HBM — maps onto a Pallas grid over
``(batch*heads, q_blocks, k_blocks)`` with the k-block axis innermost and
``arbitrary`` (sequential) semantics, VMEM BlockSpecs feeding the MXU with
(block_q × head_dim) @ (head_dim × block_k) tiles, and fp32 running-max /
running-sum accumulators held in VMEM scratch across k-block steps.  Block
shapes default to MXU-aligned 128/512 (hardware-aligned multiples of 128).

GQA is handled without materializing repeated KV: the kv BlockSpec index map
folds the query-head index down by the group size.

Supports causal masking, sliding windows (SWA), and a static ``q_offset`` so
the same kernel serves chunked prefill.  Fully-masked k-blocks are skipped
with ``pl.when`` (causal ⇒ ~2× fewer block visits; SWA ⇒ O(window) blocks).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, q_offset: int,
            block_q: int, block_k: int, nk: int, sk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_first = qi * block_q + q_offset
    k_first = ki * block_k
    needed = jnp.bool_(True)
    if causal:
        needed &= k_first <= q_first + block_q - 1
    if window > 0:
        needed &= k_first + block_k - 1 > q_first - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # [bq, D]
        k = k_ref[0].astype(jnp.float32)                   # [bk, D]
        v = v_ref[0].astype(jnp.float32)                   # [bk, Dv]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        rows = q_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        cols = k_first + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = cols < sk
        if causal:
            mask &= cols <= rows
        if window > 0:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_ref[...] = l_prev * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    q_offset: int = 0, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 512,
                    interpret: bool = False):
    """q: [B, Hq, Sq, D]; k/v: [B, Hkv, Sk, D]; Hq % Hkv == 0."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    assert Hq % Hkv == 0
    group = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    padq = (-Sq) % bq
    padk = (-Sk) % bk
    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, Dv)
    if padq:
        qf = jnp.pad(qf, ((0, 0), (0, padq), (0, 0)))
    if padk:
        kf = jnp.pad(kf, ((0, 0), (0, padk), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, padk), (0, 0)))
    nq = qf.shape[1] // bq
    nk = kf.shape[1] // bk

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_k=bk, nk=nk, sk=Sk)

    out = pl.pallas_call(
        kern,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
            pl.BlockSpec((1, bk, Dv), lambda bh, qi, ki, g=group: (bh // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, nq * bq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, Dv), jnp.float32),   # acc
            pltpu.VMEM((bq,), jnp.float32),      # running max
            pltpu.VMEM((bq,), jnp.float32),      # running sum
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out[:, :Sq].reshape(B, Hq, Sq, Dv)
