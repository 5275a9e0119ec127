"""jit'd dispatch wrappers around the Pallas kernels.

Dispatch policy:
  * TPU backend -> Pallas kernels (compiled).
  * otherwise (CPU dry-run / smokes) -> the blocked pure-jnp implementations
    from :mod:`repro.kernels.ref`, which share the kernels' algorithmic
    structure (no [S, S] materialization) so the dry-run roofline reflects
    the same memory behaviour the TPU kernel has.

Tests that force the Pallas path off the TPU (monkeypatching
:func:`_use_pallas`) get the kernels in interpret mode; a TPU backend never
interprets.

The Pallas forwards are wrapped in ``jax.custom_vjp``.  Attention's
backward is Pallas too (:mod:`repro.kernels.attention_bwd`: lse and delta in
one kernel, dq/dk/dv in another, K/V never repeated over a GQA group), from
the residuals q, k, v alone.  It covers self-attention (``Sq == Sk``, no
``q_offset``) whose query group's dq fits its VMEM block
(``attention_bwd.supported``); anything else, chunked prefill among it (never
differentiated), takes the VJP of :func:`repro.kernels.ref.mha_blocked`,
which recomputes the forward in XLA.  The WKV-6 and RMSNorm backwards are
their references' VJPs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import attention_bwd, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.rwkv6 import wkv6_pallas


def _use_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


BATCH_AXES = ("pod", "data")
HEAD_AXIS = "tp"


def _per_device(fn, args, dims, out_dims):
    """Call a Pallas kernel on each device's shard.

    Mosaic kernels cannot be partitioned by the compiler, so under a mesh
    with automatic axes the call runs inside a ``shard_map``.  It names
    every mesh axis, the ones an enclosing ``shard_map`` (the pipeline's,
    over ``pipe``) already made manual too: the TPU lowering refuses a
    kernel whose context leaves any axis automatic.  ``dims`` (per
    argument) and ``out_dims`` (per output) name the ``(batch, heads)``
    dims, or ``None``: batch shards over (pod, data) and heads over tp
    where the sizes divide; everything else is replicated.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or not mesh.auto_axes:
        return fn(*args)
    auto = set(mesh.auto_axes)
    baxes = tuple(a for a in BATCH_AXES if a in auto)
    nb = 1
    for a in baxes:
        nb *= mesh.shape[a]
    nh = mesh.shape[HEAD_AXIS] if HEAD_AXIS in auto else 1
    bdims = [(a.shape[d[0]] if d and d[0] is not None else 0,
              a.shape[d[1]] if d and d[1] is not None else 0)
             for a, d in zip(args, dims)]
    shard_b = bool(baxes) and all(b % nb == 0 for b, _ in bdims if b)
    shard_h = nh > 1 and all(h % nh == 0 for _, h in bdims if h)

    def spec(ndim, d):
        out = [None] * ndim
        if d is not None:
            if d[0] is not None and shard_b:
                out[d[0]] = baxes
            if d[1] is not None and shard_h:
                out[d[1]] = HEAD_AXIS
        return P(*out)

    in_specs = tuple(spec(a.ndim, d) for a, d in zip(args, dims))
    out_proto = jax.eval_shape(fn, *args)
    if isinstance(out_proto, (tuple, list)):
        out_specs = type(out_proto)(spec(o.ndim, d)
                                    for o, d in zip(out_proto, out_dims))
    else:
        out_specs = spec(out_proto.ndim, out_dims)
    return jax.shard_map(fn, in_specs=in_specs, out_specs=out_specs,
                         axis_names=set(mesh.axis_names), check_vma=False)(*args)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention_pallas(q, k, v, causal, window, q_offset):
    fn = functools.partial(flash_attention, causal=causal, window=window,
                           q_offset=q_offset, interpret=_interpret())
    return _per_device(fn, (q, k, v), [(0, 1)] * 3, (0, 1))


def _attention_fwd(q, k, v, causal, window, q_offset):
    out = _attention_pallas(q, k, v, causal, window, q_offset)
    return out, (q, k, v)


def _attention_bwd(causal, window, q_offset, res, g):
    q, k, v = res
    if q_offset == 0 and attention_bwd.supported(q.shape, k.shape):
        fn = functools.partial(attention_bwd.attention_bwd, causal=causal,
                               window=window, interpret=_interpret())
        return _per_device(fn, (q, k, v, g), [(0, 1)] * 4, [(0, 1)] * 3)
    _, vjp = jax.vjp(
        lambda q_, k_, v_: ref.mha_blocked(q_, k_, v_, causal=causal,
                                           window=window, q_offset=q_offset),
        q, k, v)
    return vjp(g)


_attention_pallas.defvjp(_attention_fwd, _attention_bwd)


def attention(q, k, v, *, causal=True, window=None, q_offset: int = 0,
              kv_len=None):
    """GQA attention: q [B,Hq,S,D], k/v [B,Hkv,S,D] -> [B,Hq,S,D].

    ``causal``/``window``/``kv_len`` may be traced (mixed per-layer layouts);
    the Pallas kernel requires them static and handles the common uniform
    cases, the blocked-jnp path (same algorithm, blocked custom VJP) covers
    the rest."""
    static = (isinstance(causal, (bool, int))
              and (window is None or isinstance(window, int))
              and kv_len is None)
    if _use_pallas() and static:
        return _attention_pallas(q, k, v, bool(causal), int(window or 0),
                                 q_offset)
    return ref.mha_blocked(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, kv_len=kv_len)


# ---------------------------------------------------------------------------
# RWKV-6 WKV
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp)
def _wkv6_pallas_op(r, k, v, w, u, s0):
    fn = functools.partial(wkv6_pallas, interpret=_interpret())
    return _per_device(fn, (r, k, v, w, u, s0),
                       [(0, 1)] * 4 + [(None, 0), (0, 1)], [(0, 1), (0, 1)])


def _wkv6_fwd(r, k, v, w, u, s0):
    out = _wkv6_pallas_op(r, k, v, w, u, s0)
    return out, (r, k, v, w, u, s0)


def _wkv6_bwd(res, g):
    r, k, v, w, u, s0 = res
    _, vjp = jax.vjp(lambda *a: ref.wkv6(*a), r, k, v, w, u, s0)
    return vjp(g)


_wkv6_pallas_op.defvjp(_wkv6_fwd, _wkv6_bwd)


def wkv6(r, k, v, w, u, state0=None):
    """RWKV-6 recurrence. Returns (out [B,H,T,V], state [B,H,K,V])."""
    if state0 is None:
        B, H, _, K = r.shape
        state0 = jnp.zeros((B, H, K, v.shape[-1]), jnp.float32)
    if _use_pallas():
        return _wkv6_pallas_op(r, k, v, w, u, state0)
    return ref.wkv6(r, k, v, w, u, state0)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_pallas_op(x, scale, eps):
    fn = functools.partial(rmsnorm_pallas, eps=eps, interpret=_interpret())
    return _per_device(fn, (x, scale), [(0, None), None], (0, None))


def _rmsnorm_fwd(x, scale, eps):
    return _rmsnorm_pallas_op(x, scale, eps), (x, scale)


def _rmsnorm_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: ref.rmsnorm(x_, s_, eps), x, scale)
    return vjp(g)


_rmsnorm_pallas_op.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(x, scale, eps: float = 1e-6):
    if _use_pallas():
        return _rmsnorm_pallas_op(x, scale, eps)
    return ref.rmsnorm(x, scale, eps)


# Re-exported conveniences used by the model layers
decode_attend = ref.decode_attend
lse_combine = ref.lse_combine
