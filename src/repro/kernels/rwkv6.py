"""Chunked RWKV-6 (Finch) WKV recurrence as a Pallas TPU kernel.

TPU adaptation (DESIGN.md §2): the reference CUDA wkv6 kernel is a
per-timestep serial loop with one thread block per (batch, head) — a shape
that wastes the MXU entirely.  The TPU-native formulation is *chunked linear
attention*: split time into chunks of C steps; within a chunk all
interactions become two (C×C)·(C×K) matmul families (MXU work), and only one
[K, V] state matrix is carried serially between chunks.  The carried state
lives in VMEM scratch across grid steps; the grid is
``(batch*heads, T // C)`` with the chunk axis sequential ("arbitrary").

Math (see kernels/ref.py::wkv6): with cum_t = Σ_{j<=t} log w_j per chunk,
  out_t  = r_t·(exp(cum_{t-1})·S_in)                        (inter-chunk)
         + Σ_{i<t} exp(cum_{t-1}-cum_i)(r_t·k_i) v_i        (intra-chunk)
         + (r_t·(u⊙k_t)) v_t                                 (bonus)
  S_out  = exp(cum_C)·S_in + Σ_i exp(cum_C-cum_i) k_i ⊗ v_i

All decay algebra is fp32; r/k/v/w may be bf16 in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, ca: int, cb: int):
    """fp32 matmul contracting ``a``'s dim ``ca`` with ``b``'s dim ``cb``."""
    return jax.lax.dot_general(a, b, (((ca,), (cb,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref,
            state, *, chunk: int, nc: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)          # [C, K]
    k = k_ref[0].astype(jnp.float32)          # [C, K]
    v = v_ref[0].astype(jnp.float32)          # [C, V]
    w = w_ref[0].astype(jnp.float32)          # [C, K]
    u = u_ref[0].astype(jnp.float32)          # [1, K]

    C = chunk
    K = r.shape[1]
    ti = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    logw = jnp.log(jnp.maximum(w, 1e-30))
    # inclusive prefix sum over time as a lower-triangular matmul (Mosaic
    # has no cumsum), at full fp32 precision
    cum = _dot(jnp.where(si <= ti, 1.0, 0.0), logw, 1, 0)   # [C, K]
    qdecay = jnp.exp(cum - logw)              # exp(cum_{t-1}) (exclusive)
    kdecay_in = jnp.exp(-cum)                 # exp(-cum_i)
    total = cum[C - 1:C, :]                   # [1, K]

    s_in = state[...]                         # [K, V]
    # inter-chunk term
    inter = _dot(r * qdecay, s_in, 1, 0)
    # intra-chunk: att[t, i] = sum_k r_t q decay / k decay — computed as
    # (r*qdecay) @ (k*kdecay_in)^T, valid for i < t (strict lower triangle).
    att = _dot(r * qdecay, k * kdecay_in, 1, 1)              # [C, C]
    att = jnp.where(si < ti, att, 0.0)
    intra = _dot(att, v, 1, 0)
    bonus = jnp.sum(r * k * u, axis=1, keepdims=True) * v
    o_ref[0] = (inter + intra + bonus).astype(o_ref.dtype)

    # state update; the per-row decay exp(total_k) of the [K, V] state is a
    # diagonal matmul, which keeps the [1, K] row vector in lanes
    kout = k * jnp.exp(total - cum)           # exp(cum_C - cum_i) k_i
    ki = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    kj = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    decay = jnp.where(ki == kj, jnp.exp(total), 0.0)         # diag, [K, K]
    state[...] = _dot(decay, s_in, 1, 0) + _dot(kout, v, 0, 0)

    @pl.when(ci == nc - 1)
    def _fin():
        sT_ref[0] = state[...].astype(sT_ref.dtype)


def wkv6_pallas(r, k, v, w, u, state0=None, *, chunk: int = 64,
                interpret: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """r/k/w: [B, H, T, K]; v: [B, H, T, V]; u: [H, K]; state0: [B, H, K, V].

    Returns (out [B, H, T, V], state_T [B, H, K, V]).  The intra-chunk decay
    algebra divides by exp(cum_i); keep w bounded away from 0 (RWKV-6's decay
    parameterization w = exp(-exp(x)) does) or reduce ``chunk``.
    """
    B, H, T, K = r.shape
    V = v.shape[-1]
    C = min(chunk, T)
    assert T % C == 0, f"T={T} % chunk={C} != 0"
    nc = T // C
    BH = B * H

    rf = r.reshape(BH, T, K)
    kf = k.reshape(BH, T, K)
    vf = v.reshape(BH, T, V)
    wf = w.reshape(BH, T, K)
    # [H, 1, K]: a (1, K) block then spans the array's last two dims, as
    # the TPU tiling requires of blocks narrower than (8, 128)
    uf = u.reshape(H, 1, K)
    s0 = (jnp.zeros((BH, K, V), jnp.float32) if state0 is None
          else state0.reshape(BH, K, V).astype(jnp.float32))

    kern = functools.partial(_kernel, chunk=C, nc=nc)
    out, sT = pl.pallas_call(
        kern,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, C, K), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, C, K), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, C, V), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, C, K), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, K), lambda bh, ci: (bh % H, 0, 0)),
            pl.BlockSpec((1, K, V), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, V), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, K, V), lambda bh, ci: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, V), r.dtype),
            jax.ShapeDtypeStruct((BH, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf, s0)
    return out.reshape(B, H, T, V), sT.reshape(B, H, K, V)
