"""Model primitives: norms, RoPE, attention, MLPs, MoE dispatch, SSM scan.

Per-layer *constants* (identity-pad mask, sliding-window size, causal flag,
cross-attention flag, ...) arrive as traced arrays sliced from a stacked
``[n_stages, L_per_stage]`` buffer — the stage program is SPMD-uniform, so
anything that varies per layer must be data, not Python structure.  All
masking paths therefore accept traced scalars.

Sharding constraints use :func:`tpc` (tensor-parallel constraint): they apply
only when the surrounding mesh actually has the named axes, so the same code
runs on a 1-device CPU smoke test and a 512-chip production mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, AttentionConfig, MoEConfig, SSMConfig
from repro.core.pipeline import layout_hints_enabled
from repro.kernels import ops
from repro.scopes import ATTN, MLP, NORM, scoped

BATCH = ("pod", "data")
TP = "tp"


def constrain(x, spec: P):
    """with_sharding_constraint iff the current mesh has the spec's axes."""
    mesh = jax.sharding.get_abstract_mesh()
    if not layout_hints_enabled() or mesh.empty:
        return x
    names = set(mesh.axis_names)
    def ok(entry):
        if entry is None:
            return True
        if isinstance(entry, (tuple, list)):
            return all(e in names for e in entry)
        return entry in names
    if all(ok(e) for e in spec):
        return jax.lax.with_sharding_constraint(x, spec)
    return x


def act_bd(x):
    """Constrain [B, S, D]-like activations: batch over (pod, data)."""
    return constrain(x, P(BATCH, *([None] * (x.ndim - 1))))


def heads_tp(x):
    """Constrain [B, S, H, hd]: batch over (pod,data), heads over tp."""
    return constrain(x, P(BATCH, None, TP, None))


def ffn_tp(x):
    """Constrain [B, S, F]: hidden over tp."""
    return constrain(x, P(BATCH, None, TP))


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------

def dense_init(key, din: int, dout: int, dtype, scale: float = 1.0):
    std = scale * din ** -0.5
    return (jax.random.normal(key, (din, dout)) * std).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str, dtype):
    if kind == "rms":
        return {"scale": jnp.ones((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


@scoped(NORM)
def norm_apply(p, x, kind: str, eps: float = 1e-6):
    if kind == "rms":
        return ops.rmsnorm(x, p["scale"], eps)
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, pos, theta: float):
    """x: [B, S, H, hd]; pos: [S] or [B, S] (int)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if pos.ndim == 1:
        ang = pos.astype(jnp.float32)[:, None] * freq[None, :]      # [S, half]
        ang = ang[None, :, None, :]
    else:
        ang = pos.astype(jnp.float32)[..., None] * freq              # [B,S,half]
        ang = ang[:, :, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention (self / cross, train / decode)
# ---------------------------------------------------------------------------

def attn_init(key, d: int, a: AttentionConfig, dtype, *, out_scale=1.0):
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d, a.n_heads * a.head_dim, dtype),
        "wk": dense_init(ks[1], d, a.n_kv_heads * a.head_dim, dtype),
        "wv": dense_init(ks[2], d, a.n_kv_heads * a.head_dim, dtype),
        "wo": dense_init(ks[3], a.n_heads * a.head_dim, d, dtype, out_scale),
    }


def _qkv(p, x, kv_src, a: AttentionConfig):
    B, S, _ = x.shape
    Sk = kv_src.shape[1]
    q = heads_tp((x @ p["wq"]).reshape(B, S, a.n_heads, a.head_dim))
    k = heads_tp((kv_src @ p["wk"]).reshape(B, Sk, a.n_kv_heads, a.head_dim))
    v = heads_tp((kv_src @ p["wv"]).reshape(B, Sk, a.n_kv_heads, a.head_dim))
    return q, k, v


@scoped(ATTN)
def attn_apply(p, x, a: AttentionConfig, *, memory=None, window=None,
               causal=None, pos=None, kv_len=None):
    """Full-sequence attention (train / prefill).

    window / causal / kv_len may be traced scalars (per-layer constants):
      window: 0 => unlimited;  causal: {0,1};  kv_len: valid key prefix.
    """
    B, S, D = x.shape
    kv_src = memory if memory is not None else x
    q, k, v = _qkv(p, x, kv_src, a)
    if a.use_rope and memory is None:
        pq = jnp.arange(S) if pos is None else pos
        q = rope(q, pq, a.rope_theta)
        k = rope(k, pq, a.rope_theta)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    eff_causal = a.causal if causal is None else causal
    eff_window = window
    if eff_window is None and a.kind == "swa":
        eff_window = a.window
    out = ops.attention(qt, kt, vt, causal=eff_causal, window=eff_window,
                        kv_len=kv_len)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, a.n_heads * a.head_dim)
    return act_bd(out @ p["wo"])


def attn_decode(p, x, cache, a: AttentionConfig, *, window=None,
                cross: bool = False):
    """One-token decode against a ring cache.

    x: [B, 1, D]; cache: {"k","v": [B, slots, Hkv, hd], "len": scalar int32}.
    The cache is a ring over ``slots``; the new KV pair lands at
    ``len % slots``.  Validity is computed from ring *distance* so the same
    code serves full attention (slots >= seq), uniform SWA (slots == window)
    and mixed per-layer traced windows (slots >= window, older entries
    masked).  For cross-attention the cache holds precomputed memory K/V and
    is not updated (valid prefix = cache["len"]).
    Returns (out [B, 1, D], new_cache).
    """
    B = x.shape[0]
    q = (x @ p["wq"]).reshape(B, 1, a.n_heads, a.head_dim)
    ln = cache["len"]
    slots = cache["k"].shape[1]
    ki = jnp.arange(slots)
    if not cross:
        k1 = (x @ p["wk"]).reshape(B, 1, a.n_kv_heads, a.head_dim)
        v1 = (x @ p["wv"]).reshape(B, 1, a.n_kv_heads, a.head_dim)
        if a.use_rope:
            posv = jnp.full((B, 1), ln, jnp.int32)
            q = rope(q, posv, a.rope_theta)
            k1 = rope(k1, posv, a.rope_theta)
        slot = ln % slots
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k1.astype(cache["k"].dtype), slot, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v1.astype(cache["v"].dtype), slot, 1)
        new_cache = {"k": ck, "v": cv, "len": ln + 1}
        dist = (slot - ki) % slots          # 0 = newest, 1 = previous, ...
        w_eff = slots if window is None else jnp.minimum(
            jnp.asarray(window, jnp.int32), slots)
        valid = (dist < w_eff) & (dist <= ln)
    else:
        if a.use_rope:
            q = rope(q, jnp.full((B, 1), ln, jnp.int32), a.rope_theta)
        ck, cv = cache["k"], cache["v"]
        new_cache = cache
        valid = ki < ln
    from repro.kernels.ref import _expand_kv, NEG_INF
    qt = q.transpose(0, 2, 1, 3).astype(jnp.float32) * a.head_dim ** -0.5
    kt = _expand_kv(ck.transpose(0, 2, 1, 3), a.n_heads).astype(jnp.float32)
    vt = _expand_kv(cv.transpose(0, 2, 1, 3), a.n_heads).astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    pw = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", pw, vt).astype(x.dtype)
    out = out.transpose(0, 2, 1, 3).reshape(B, 1, a.n_heads * a.head_dim)
    return out @ p["wo"], new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d: int, f: int, act: str, dtype, *, out_scale=1.0):
    ks = jax.random.split(key, 3)
    if act in ("silu", "geglu"):
        return {"wg": dense_init(ks[0], d, f, dtype),
                "wu": dense_init(ks[1], d, f, dtype),
                "wd": dense_init(ks[2], f, d, dtype, out_scale)}
    return {"wu": dense_init(ks[0], d, f, dtype),
            "wd": dense_init(ks[1], f, d, dtype, out_scale)}


@scoped(MLP)
def mlp_apply(p, x, act: str):
    if act in ("silu", "geglu"):
        g = ffn_tp(x @ p["wg"])
        u = ffn_tp(x @ p["wu"])
        h = (jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)) * u
    else:
        h = jax.nn.gelu(ffn_tp(x @ p["wu"]))
    return act_bd(h @ p["wd"])


# ---------------------------------------------------------------------------
# MoE (top-k router + capacity dispatch; EP over the tp axis)
# ---------------------------------------------------------------------------

def moe_init(key, d: int, f: int, m: MoEConfig, dtype, *, out_scale=1.0):
    ks = jax.random.split(key, 4)
    E = m.n_experts
    std = d ** -0.5
    return {
        "router": dense_init(ks[0], d, E, jnp.float32),
        "wg": (jax.random.normal(ks[1], (E, d, f)) * std).astype(dtype),
        "wu": (jax.random.normal(ks[2], (E, d, f)) * std).astype(dtype),
        "wd": (jax.random.normal(ks[3], (E, f, d)) * std * out_scale).astype(dtype),
    }


def _expert_constrain(x):
    """[G, E, C, D]-like: experts over tp (EP), groups over (pod, data)."""
    return constrain(x, P(BATCH, TP, *([None] * (x.ndim - 2))))


def moe_apply(p, x, m: MoEConfig, *, group_size: int = 512):
    """Capacity-factor token dispatch (Mesh-TF/GSPMD style, activation
    stationary): tokens stay data-sharded, experts are EP-sharded over ``tp``,
    the combine einsum contracts the expert axis (GSPMD inserts the
    reduction).  Tokens over capacity are dropped (standard top-k routing)."""
    B, S, D = x.shape
    E, k = m.n_experts, m.top_k
    T = B * S
    g = max(1, min(group_size, T))
    while T % g:
        g -= 1
    G = T // g
    xt = x.reshape(G, g, D)
    cap = int(max(1, round(g * k * m.capacity_factor / E)))

    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    gates = jax.nn.softmax(logits, axis=-1)                    # [G, g, E]
    vals, idx = jax.lax.top_k(gates, k)                        # [G, g, k]
    vals = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)

    combine = jnp.zeros((G, g, E, cap), jnp.float32)
    counts = jnp.zeros((G, E), jnp.float32)
    for slot in range(k):
        e = idx[..., slot]
        oh = jax.nn.one_hot(e, E, dtype=jnp.float32)           # [G, g, E]
        pos_all = jnp.cumsum(oh, axis=1) - oh + counts[:, None, :]
        pos = jnp.sum(oh * pos_all, -1)                        # [G, g]
        keep = (pos < cap).astype(jnp.float32)
        counts = counts + jnp.sum(oh * keep[..., None], axis=1)
        ohc = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
        combine = combine + (vals[..., slot] * keep)[..., None, None] \
            * (oh[..., :, None] * ohc[..., None, :])
    dispatch = (combine > 0).astype(x.dtype)                   # [G, g, E, cap]

    ein = _expert_constrain(jnp.einsum("gsec,gsd->gecd", dispatch,
                                       xt.astype(x.dtype)))
    h_g = _expert_constrain(jnp.einsum("gecd,edf->gecf", ein, p["wg"]))
    h_u = _expert_constrain(jnp.einsum("gecd,edf->gecf", ein, p["wu"]))
    h = jax.nn.silu(h_g) * h_u
    eo = _expert_constrain(jnp.einsum("gecf,efd->gecd", h, p["wd"]))
    out = jnp.einsum("gsec,gecd->gsd", combine.astype(x.dtype), eo)
    return act_bd(out.reshape(B, S, D)), logits


def moe_aux_loss(logits, m: MoEConfig):
    """Switch-style load-balancing auxiliary loss."""
    gates = jax.nn.softmax(logits.astype(jnp.float32), -1)
    me = gates.mean(axis=tuple(range(gates.ndim - 1)))
    top1 = jnp.argmax(gates, -1)
    ce = jax.nn.one_hot(top1, m.n_experts).mean(
        axis=tuple(range(gates.ndim - 1)))
    return m.n_experts * jnp.sum(me * ce)


# ---------------------------------------------------------------------------
# Selective SSM (Mamba-style head group; hymba's SSM half)
# ---------------------------------------------------------------------------

def ssm_init(key, d: int, s: SSMConfig, dtype):
    H = s.n_heads or d // s.head_dim
    ks = jax.random.split(key, 5)
    return {
        "w_in": dense_init(ks[0], d, H * s.head_dim, dtype),
        "w_bc": dense_init(ks[1], d, H * 2 * s.state_dim, dtype),
        "w_dt": dense_init(ks[2], d, H, dtype),
        "a_log": jnp.zeros((H, s.state_dim), jnp.float32),
        "w_out": dense_init(ks[3], H * s.head_dim, d, dtype),
        "dskip": jnp.ones((H, 1), jnp.float32) * 0.1,
    }


def ssm_scan(p, x, s: SSMConfig, state0=None):
    """x: [B, S, D] -> (y [B, S, D], state [B, H, hd, N]).

    Linear recurrence h_t = exp(-softplus(dt_t) exp(a_log)) h_{t-1}
                           + dt_t * (x_t ⊗ B_t); y_t = (h_t · C_t) + D·x_t,
    evaluated with an associative scan over time (TPU-friendly log-depth)."""
    B, S, D = x.shape
    H = s.n_heads or D // s.head_dim
    hd, N = s.head_dim, s.state_dim
    xh = (x @ p["w_in"]).reshape(B, S, H, hd)
    bc = (x @ p["w_bc"]).reshape(B, S, H, 2 * N).astype(jnp.float32)
    Bm, Cm = bc[..., :N], bc[..., N:]
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32))    # [B,S,H]
    decay = jnp.exp(-dt[..., None] * jnp.exp(p["a_log"])[None, None])  # [B,S,H,N]
    inc = (dt[..., None, None] * xh.astype(jnp.float32)[..., :, None]
           * Bm[..., None, :])                                   # [B,S,H,hd,N]

    def combine(a, b):
        (da, ia), (db, ib) = a, b
        return da * db, ib + db * ia

    d_sc, i_sc = jax.lax.associative_scan(
        combine, (jnp.broadcast_to(decay[..., None, :], inc.shape), inc), axis=1)
    h = i_sc
    if state0 is not None:
        h = h + d_sc * state0[:, None]
    y = jnp.einsum("bshdn,bshn->bshd", h, Cm) \
        + xh.astype(jnp.float32) * p["dskip"][None, None]
    y = y.reshape(B, S, H * hd).astype(x.dtype)
    return act_bd(y @ p["w_out"]), h[:, -1]


def ssm_decode(p, x, state, s: SSMConfig):
    """One-step SSM decode. state: [B, H, hd, N]."""
    B = x.shape[0]
    H = s.n_heads or x.shape[-1] // s.head_dim
    hd, N = s.head_dim, s.state_dim
    xh = (x @ p["w_in"]).reshape(B, 1, H, hd)
    bc = (x @ p["w_bc"]).reshape(B, 1, H, 2 * N).astype(jnp.float32)
    Bm, Cm = bc[..., :N], bc[..., N:]
    dt = jax.nn.softplus((x @ p["w_dt"]).astype(jnp.float32))
    decay = jnp.exp(-dt[..., None] * jnp.exp(p["a_log"])[None, None])[:, 0]
    inc = (dt[..., None, None] * xh.astype(jnp.float32)[..., :, None]
           * Bm[..., None, :])[:, 0]
    state = decay[..., None, :] * state + inc
    y = jnp.einsum("bhdn,bhn->bhd", state, Cm[:, 0]) \
        + xh.astype(jnp.float32)[:, 0] * p["dskip"][None]
    y = y.reshape(B, 1, H * hd).astype(x.dtype)
    return y @ p["w_out"], state
