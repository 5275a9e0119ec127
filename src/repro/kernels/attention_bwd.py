"""Backward of the flash-attention forward as Pallas TPU kernels.

FlashAttention-2's backward in two kernels that never materialise the
[Sq, Sk] probabilities.  Both work on the transposed scores ``sᵀ = k qᵀ``
(keys down, queries across), so that every per-query quantity (the running
max and sum, lse, delta) is a row that broadcasts down the sublanes, and
every reduction runs across vector registers, not across lanes:

* ``_stats_kernel``: grid ``(batch*q heads, q blocks, k blocks)``, the k
  axis innermost and sequential.  The forward's sweep again, in bf16: the
  log-sum-exp (lse) of each query's scores and ``delta = rowsum(dO · O)``,
  with ``Oᵀ = vᵀ pᵀ`` accumulated as the running max grows.  The output O
  is recomputed, not kept from the forward: a residual per layer would
  stay live for every layer of a rematerialised stage.
* ``_grads_kernel``: grid ``(batch*kv heads, k blocks, group heads × q
  blocks)``, the last two axes sequential.  It recomputes ``pᵀ = exp(sᵀ -
  lse)`` and ``dSᵀ = pᵀ (v dOᵀ - delta)`` once per block pair and takes all
  three gradients from them: dk and dv summed over the kv head's whole
  query group in VMEM (GQA never repeats K/V), and ``dqᵀ = kᵀ dSᵀ`` summed
  over the k blocks in a VMEM block of the group's whole sequence.

Every matrix product takes bf16 operands with f32 accumulation, ``p`` and
``dS`` cast just before theirs; scores, ``exp``, lse, delta and ``dS`` stay
f32.  q enters the products pre-scaled, ``bf16(q · scale)``, as the
reference's ``(q * scale) @ kᵀ`` does, so ``dk = dSᵀ (q · scale)`` needs no
scale of its own.

Block pairs that the causal or window mask hides entirely are skipped with
``pl.when``, and their index maps are clamped to the nearest needed block,
so the pipeline issues no DMA for them either.  Only pairs that straddle
the mask's edge (or the padding) build a mask.  Self-attention only
(``Sq == Sk``, no query offset) and a group's dq within ``DQ_VMEM``
(:func:`supported`): :mod:`repro.kernels.ops` keeps the reference VJP for
the rest.

Block and index arithmetic is written with ``lax`` primitives, not jnp's
operators: jnp's jitted helpers cache their traced jaxprs with the source
locations of their first caller, which could be the forward kernel's index
maps, and a Pallas kernel is known in a profile by the names in its
locations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.tiling import Tiling, block_size, clamp, vmem_limit

NEG_INF = -1e30
BLOCK = 1024            # query / key rows of one grid step
DQ_VMEM = 16 << 20      # bytes of one query group's f32 dq, held in VMEM
_NT = (((1,), (1,)), ((), ()))      # a @ bᵀ
_NN = (((1,), (0,)), ((), ()))      # a @ b


def _dot(a, b, dims):
    return lax.dot_general(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                           dims, preferred_element_type=jnp.float32)


def _stats_kernel(q_ref, k_ref, vt_ref, do_ref, lse_ref, delta_ref,
                  qs_ref, ot_ref, m_ref, l_ref, *, scale, tiling):
    t = tiling
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(lax.eq(ki, 0))
    def _init():
        qs_ref[...] = (q_ref[0].astype(jnp.float32) * scale).astype(jnp.bfloat16)
        ot_ref[...] = jnp.zeros_like(ot_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def step(masked):
        st = _dot(k_ref[0], qs_ref[...], _NT)                # [keys, queries]
        if masked:
            st = t.mask(st, qi, ki)
        m_prev = m_ref[...]                                  # [1, queries]
        m_new = jnp.maximum(m_prev, st.max(axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pt = jnp.exp(st - m_new)
        l_ref[...] = alpha * l_ref[...] + pt.sum(axis=0, keepdims=True)
        m_ref[...] = m_new
        ot_ref[...] = alpha * ot_ref[...] + _dot(vt_ref[0], pt, _NN)

    t.when_needed(t.sees(qi, ki, by_k=False), qi, ki, step)

    @pl.when(lax.eq(ki, t.nk - 1))
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        lse_ref[0] = m_ref[...] + jnp.log(l)
        dot = jnp.transpose(do_ref[0].astype(jnp.float32))  # [Dv, queries]
        delta_ref[0] = jnp.sum(dot * ot_ref[...], axis=0, keepdims=True) / l


def _grads_kernel(q_ref, k_ref, kt_ref, v_ref, do_ref, lse_ref, delta_ref,
                  dq_ref, dk_ref, dv_ref, dqt_acc, dk_acc, dv_acc, *, scale,
                  tiling, group):
    t = tiling
    ki, j = pl.program_id(1), pl.program_id(2)
    qi = lax.rem(j, t.nq)
    last = lax.eq(j, pl.num_programs(2) - 1)

    @pl.when(lax.bitwise_and(lax.eq(ki, 0), lax.eq(j, 0)))
    def _init_dq():
        dqt_acc[...] = jnp.zeros_like(dqt_acc)

    @pl.when(lax.eq(j, 0))
    def _init_dkv():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        q = (q_ref[0].astype(jnp.float32) * scale).astype(jnp.bfloat16)
        do = do_ref[0]
        st = _dot(k_ref[0], q, _NT)                          # [keys, queries]
        if masked:
            st = t.mask(st, qi, ki)
        pt = jnp.exp(st - lse_ref[0])
        dv_acc[...] += _dot(pt, do, _NN)
        dpt = _dot(v_ref[0], do, _NT)
        dst = pt * (dpt - delta_ref[0])
        dk_acc[...] += _dot(dst, q, _NN)
        dqt_acc[j] += _dot(kt_ref[0], dst, _NN)              # dqᵀ [D, queries]

    t.when_needed(t.sees(qi, ki, by_k=False), qi, ki, step)

    @pl.when(last)
    def _finish_dkv():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when(lax.bitwise_and(last, lax.eq(ki, t.nk - 1)))
    def _finish_dq():
        for h in range(group):
            for i in range(t.nq):
                dq_ref[0, h, pl.ds(i * t.b, t.b), :] = jnp.transpose(
                    dqt_acc[h * t.nq + i] * scale).astype(dq_ref.dtype)


def _padded(s: int) -> int:
    """The sequence padded to whole blocks."""
    b = block_size(s, BLOCK)
    return -(-s // b) * b


def supported(q_shape, k_shape) -> bool:
    """Whether :func:`attention_bwd` takes these shapes: self-attention
    (``Sq == Sk``) whose query group's dq fits ``DQ_VMEM``."""
    _, hq, s, d = q_shape
    _, hkv, sk, _ = k_shape
    if sk != s or hq % hkv:
        return False
    return hq // hkv * _padded(s) * d * 4 <= DQ_VMEM


def attention_bwd(q, k, v, do, *, causal: bool = True, window: int = 0,
                  interpret: bool = False):
    """(dq, dk, dv) of self-attention ``attention(q, k, v)`` for the output
    cotangent ``do``.  q/do ``[B, Hq, S, D]``, k/v ``[B, Hkv, S, D]``,
    ``Hq % Hkv == 0``; static ``causal``/``window``.  A grid step takes
    ``BLOCK`` queries against ``BLOCK`` keys, or the whole sequence where it
    is shorter."""
    B, Hq, S, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    if not supported(q.shape, k.shape):
        raise ValueError(f"self-attention with grouped heads whose dq fits "
                         f"{DQ_VMEM} bytes only: q {q.shape}, k/v {v.shape}")
    group = Hq // Hkv
    scale = D ** -0.5
    b, sp = block_size(S, BLOCK), _padded(S)
    n = sp // b
    f32, bf16 = jnp.float32, jnp.bfloat16

    def flat(x):
        x = x.reshape(-1, S, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, sp - S), (0, 0))) if sp > S else x
    qf, kf, vf, dof = map(flat, (q, k, v, do))
    tiling = Tiling(causal=causal, window=window, sk=S, b=b, nq=n, nk=n)
    vmem = vmem_limit(b)

    def kv_block(bh, qi, ki):
        return (lax.div(bh, group), clamp(ki, *tiling.k_range(qi)), 0)

    def vt_block(bh, qi, ki):
        return (lax.div(bh, group), 0, clamp(ki, *tiling.k_range(qi)))
    q_block = lambda bh, qi, ki: (bh, qi, 0)
    q_row = lambda bh, qi, ki: (bh, 0, qi)
    lse, delta = pl.pallas_call(
        functools.partial(_stats_kernel, scale=scale, tiling=tiling),
        grid=(B * Hq, n, n),
        in_specs=[pl.BlockSpec((1, b, D), q_block),
                  pl.BlockSpec((1, b, D), kv_block),
                  pl.BlockSpec((1, Dv, b), vt_block),
                  pl.BlockSpec((1, b, Dv), q_block)],
        out_specs=[pl.BlockSpec((1, 1, b), q_row),
                   pl.BlockSpec((1, 1, b), q_row)],
        out_shape=[jax.ShapeDtypeStruct((B * Hq, 1, sp), f32),
                   jax.ShapeDtypeStruct((B * Hq, 1, sp), f32)],
        scratch_shapes=[pltpu.VMEM((b, D), bf16),       # q * scale
                        pltpu.VMEM((Dv, b), f32),       # Oᵀ, unnormalised
                        pltpu.VMEM((1, b), f32),        # running max
                        pltpu.VMEM((1, b), f32)],       # running sum
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=vmem),
        interpret=interpret,
        name="attention_bwd_stats",
    )(qf, kf, jnp.swapaxes(vf, 1, 2), dof)

    def qi_of(ki, j):
        return clamp(lax.rem(j, n), *tiling.q_range(ki))

    def bh_of(bkv, j):
        return lax.add(lax.mul(bkv, group), lax.div(j, n))

    q_of = lambda bkv, ki, j: (bh_of(bkv, j), qi_of(ki, j), 0)
    row_of = lambda bkv, ki, j: (bh_of(bkv, j), 0, qi_of(ki, j))
    k_block = lambda bkv, ki, j: (bkv, ki, 0)
    kt_block = lambda bkv, ki, j: (bkv, 0, ki)
    group_block = lambda bkv, ki, j: (bkv, 0, 0, 0)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_grads_kernel, scale=scale, tiling=tiling,
                          group=group),
        grid=(B * Hkv, n, group * n),
        in_specs=[pl.BlockSpec((1, b, D), q_of),
                  pl.BlockSpec((1, b, D), k_block),
                  pl.BlockSpec((1, D, b), kt_block),
                  pl.BlockSpec((1, b, Dv), k_block),
                  pl.BlockSpec((1, b, Dv), q_of),
                  pl.BlockSpec((1, 1, b), row_of),
                  pl.BlockSpec((1, 1, b), row_of)],
        out_specs=[pl.BlockSpec((1, group, sp, D), group_block),
                   pl.BlockSpec((1, b, D), k_block),
                   pl.BlockSpec((1, b, Dv), k_block)],
        out_shape=[jax.ShapeDtypeStruct((B * Hkv, group, sp, D), q.dtype),
                   jax.ShapeDtypeStruct(kf.shape, k.dtype),
                   jax.ShapeDtypeStruct(vf.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((group * n, D, b), f32),     # dqᵀ
                        pltpu.VMEM((b, D), f32),
                        pltpu.VMEM((b, Dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + 3 * group * sp * D * 4),
        interpret=interpret,
        name="attention_bwd_grads",
    )(qf, kf, jnp.swapaxes(kf, 1, 2), vf, dof, lse, delta)

    def unflat(x, h):
        return x.reshape(B, h, sp, x.shape[-1])[:, :, :S]
    return unflat(dq, Hq), unflat(dk, Hkv), unflat(dv, Hkv)

