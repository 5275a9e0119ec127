"""Plain float32 reference of a Llama-style decoder trained with AdamW.

The configurations in this directory that name ``"reference": "llama"``
(SmolLM, DeepSeek LLM) are this architecture: token embedding, then per
layer ``h += Wo·attn(rope(Wq·rms(h)), rope(Wk·rms(h)), Wv·rms(h))`` with
causal grouped-query attention and ``h += Wd·(silu(Wg·rms(h)) * Wu·rms(h))``,
a final RMSNorm, a head (the embedding table transposed when tied), and the
mean next-token cross-entropy over every position of every row.  RoPE
rotates the two halves of each head (the Hugging Face ``rotate_half``
convention) with inverse frequencies ``theta ** (-i / (head_dim / 2))``.

It imports nothing of the program under test.  It makes its own weights
from the seed with :func:`init_weights` (the benchmark hands the same
values to the program), and it follows the program's optimizer as the
benchmark configures it: global-norm clipping, then AdamW with decoupled
weight decay on every parameter and a linear-warmup cosine schedule.

Matrix products take their numerics from a :class:`Numerics`: ``FP32``
(``Precision.HIGHEST``) is the reference, ``FP8`` (per-tensor scaled
e4m3 operands forward, e5m2 cotangents backward) is the control that a
comparison must refuse.  A step runs in blocks of rows, each layer
rematerialised, so the full-size reference fits on one chip.

The module also states what the benchmark needs to know of this family,
so that another family arrives as a module of its own: the model FLOPs of a
step (:func:`step_flops`), and, as plain names, where the program under
test keeps these sizes and parameters (``PROGRAM_*``).
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

NORMS = ("ln1", "ln2", "final_norm")
LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "ln2", "wg", "wu", "wd")


def leaf_shapes(c: Dict) -> Dict[str, tuple]:
    """Logical parameters; per-layer ones are stacked ``[layers, ...]``."""
    D, F = c["hidden_size"], c["intermediate_size"]
    V, L = c["vocab_size"], c["num_hidden_layers"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    s = {"embed": (V, D), "ln1": (L, D), "wq": (L, D, q), "wk": (L, D, kv),
         "wv": (L, D, kv), "wo": (L, q, D), "ln2": (L, D), "wg": (L, D, F),
         "wu": (L, D, F), "wd": (L, F, D), "final_norm": (D,)}
    if not c["tie_word_embeddings"]:
        s["head"] = (D, V)
    return s


def init_weights(key, c: Dict, dtype=jnp.bfloat16) -> Dict[str, jnp.ndarray]:
    """Seeded weights in ``dtype``: norms 1, matrices normal with std
    ``fan_in ** -0.5`` (output projections also ``(2 L) ** -0.5``, the
    embedding ``hidden ** -0.5``).  ``key`` is a raw ``uint32[2]`` key."""
    L = c["num_hidden_layers"]
    out = {}
    for i, (name, shape) in enumerate(sorted(leaf_shapes(c).items())):
        if name in NORMS:
            out[name] = jnp.ones(shape, dtype)
            continue
        std = (c["hidden_size"] if name == "embed" else shape[-2]) ** -0.5
        if name in ("wo", "wd"):
            std *= (2 * L) ** -0.5
        w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        out[name] = (w * std).astype(dtype)
    return out


def step_flops(c: Dict, batch: int, seq: int) -> float:
    """Model FLOPs of one training step: 6 per token per parameter of the
    matrix products (every layer's projections and MLP, the head once, tied
    or not; the embedding lookup is no product and is left out), plus causal
    attention: per layer, row and query head, 2 x 2 x head_dim per pair of
    positions for QK and PV in the forward, times 3 for forward and
    backward, over S (S + 1) / 2 pairs.  Recomputed work is not counted."""
    D, F, V = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    L = c["num_hidden_layers"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    matmul_params = L * (D * q + 2 * D * kv + q * D + 3 * D * F) + D * V
    pairs = seq * (seq + 1) / 2
    attention = L * batch * 3 * 4 * q * pairs
    return 6.0 * matmul_params * batch * seq + attention


# ---------------------------------------------------------------------------
# Where the program under test keeps this family (names only)
# ---------------------------------------------------------------------------

# Attributes of the program's ArchConfig that select this family.
PROGRAM_FAMILY = {"family": "dense", "norm": "rms", "act": "silu"}

# The configuration's keys and the ArchConfig attribute that holds each.
PROGRAM_KEYS = {
    "num_hidden_layers": ("n_layers",), "hidden_size": ("d_model",),
    "intermediate_size": ("d_ff",), "vocab_size": ("vocab",),
    "num_attention_heads": ("attn", "n_heads"),
    "num_key_value_heads": ("attn", "n_kv_heads"),
    "head_dim": ("attn", "head_dim"), "rope_theta": ("attn", "rope_theta"),
    "tie_word_embeddings": ("tie_embeddings",),
}

# The program's parameter paths and the parameter of this module each is.
PROGRAM_LEAVES = {
    ("embed", "tok"): "embed",
    ("stages", "ln1", "scale"): "ln1", ("stages", "ln2", "scale"): "ln2",
    ("stages", "attn", "wq"): "wq", ("stages", "attn", "wk"): "wk",
    ("stages", "attn", "wv"): "wv", ("stages", "attn", "wo"): "wo",
    ("stages", "mlp", "wg"): "wg", ("stages", "mlp", "wu"): "wu",
    ("stages", "mlp", "wd"): "wd",
    ("head", "norm", "scale"): "final_norm", ("head", "w"): "head",
}


# ---------------------------------------------------------------------------
# Numerics of the matrix products
# ---------------------------------------------------------------------------

class Numerics:
    """``dot(spec, a, b)``: an einsum of two float32 operands."""

    def dot(self, spec, a, b):
        raise NotImplementedError


class _FP32(Numerics):
    name = "fp32"

    def dot(self, spec, a, b):
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _scaled_cast(x, dtype, fmax):
    """Round ``x`` through ``dtype`` under one per-tensor scale."""
    amax = jax.lax.stop_gradient(jnp.max(jnp.abs(x)))
    s = jnp.where(amax > 0, amax / fmax, 1.0)
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_dot(spec, a, b):
    return _fp8_fwd(spec, a, b)[0]


def _fp8_fwd(spec, a, b):
    qa = _scaled_cast(a, jnp.float8_e4m3fn, 448.0)
    qb = _scaled_cast(b, jnp.float8_e4m3fn, 448.0)
    out = jnp.einsum(spec, qa, qb, precision=jax.lax.Precision.HIGHEST)
    return out, (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    qg = _scaled_cast(g, jnp.float8_e5m2, 57344.0)
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(
        spec, x, y, precision=jax.lax.Precision.HIGHEST), qa, qb)
    return vjp(qg)


_fp8_dot.defvjp(_fp8_fwd, _fp8_bwd)


class _FP8(Numerics):
    name = "fp8"

    def dot(self, spec, a, b):
        return _fp8_dot(spec, a, b)


FP32 = _FP32()
FP8 = _FP8()


# ---------------------------------------------------------------------------
# Forward and loss
# ---------------------------------------------------------------------------

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [b, S, H, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(c, num: Numerics, h, lp):
    b, S, _ = h.shape
    Hq, Hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps = c["rms_norm_eps"]
    x = _rms(h, lp["ln1"], eps)
    q = num.dot("bsd,de->bse", x, lp["wq"]).reshape(b, S, Hq, hd)
    k = num.dot("bsd,de->bse", x, lp["wk"]).reshape(b, S, Hkv, hd)
    v = num.dot("bsd,de->bse", x, lp["wv"]).reshape(b, S, Hkv, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    k = jnp.repeat(k, Hq // Hkv, axis=2)     # query head i reads kv head i // g
    v = jnp.repeat(v, Hq // Hkv, axis=2)
    s = num.dot("bqhd,bkhd->bhqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = num.dot("bhqk,bkhd->bqhd", p, v).reshape(b, S, Hq * hd)
    h = h + num.dot("bse,ed->bsd", o, lp["wo"])
    x = _rms(h, lp["ln2"], eps)
    g = num.dot("bsd,df->bsf", x, lp["wg"])
    u = num.dot("bsd,df->bsf", x, lp["wu"])
    return h + num.dot("bsf,fd->bsd", jax.nn.silu(g) * u, lp["wd"])


def loss_sum(c, num: Numerics, params, tokens, labels):
    """Summed next-token cross-entropy of a block of rows."""
    h = params["embed"][tokens]
    layers = {k: params[k] for k in LAYER_LEAVES}
    layer = jax.checkpoint(functools.partial(_layer, c, num))
    h, _ = jax.lax.scan(lambda h, lp: (layer(h, lp), None), h, layers)
    h = _rms(h, params["final_norm"], c["rms_norm_eps"])
    head = params["head"] if "head" in params else params["embed"].T
    logits = num.dot("bsd,dv->bsv", h, head)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)


# ---------------------------------------------------------------------------
# Optimizer and training step
# ---------------------------------------------------------------------------

def learning_rate(o: Dict, step):
    """Linear warmup then cosine decay to ``min_lr_ratio``; ``step`` counts
    updates from 1."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - o["warmup_steps"])
                    / max(o["total_steps"] - o["warmup_steps"], 1), 0, 1)
    cos = o["min_lr_ratio"] + (1 - o["min_lr_ratio"]) * 0.5 * (
        1 + jnp.cos(math.pi * prog))
    return o["lr"] * warm * cos


def leaf_norms(tree):
    """Norm of each parameter, per layer for the stacked ones."""
    return {k: (jnp.sqrt(jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim))))
                if k in LAYER_LEAVES else jnp.linalg.norm(v.reshape(-1)))
            for k, v in tree.items()}


def make_step(c: Dict, o: Dict, num: Numerics, row_block: int = 1):
    """``step(params, mu, nu, t, tokens, labels)`` -> ``(params, mu, nu,
    loss, raw_grad_norms, global_grad_norm)``; ``t`` is the number of
    updates made so far.  Jit it."""

    def step(params, mu, nu, t, tokens, labels):
        B, S = tokens.shape
        blocks = (tokens.reshape(B // row_block, row_block, S),
                  labels.reshape(B // row_block, row_block, S))
        block = jax.checkpoint(lambda p, tk, lb: loss_sum(c, num, p, tk, lb))
        lsum, gsum = jax.value_and_grad(lambda p: jax.lax.scan(
            lambda acc, xs: (acc + block(p, *xs), None),
            jnp.zeros((), jnp.float32), blocks)[0])(params)
        n = B * S
        loss = lsum / n
        grads = jax.tree.map(lambda g: g / n, gsum)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                          for g in jax.tree.leaves(grads)))
        clip = jnp.minimum(1.0, o["clip_norm"] / jnp.maximum(gn, 1e-12))
        t1 = t + 1
        lr = learning_rate(o, t1)
        b1, b2 = o["b1"], o["b2"]
        c1 = 1 - b1 ** t1.astype(jnp.float32)
        c2 = 1 - b2 ** t1.astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g * clip, mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * jnp.square(g * clip),
                          nu, grads)
        params = jax.tree.map(
            lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + o["eps"])
                                      + o["weight_decay"] * p),
            params, mu, nu)
        return params, mu, nu, loss, leaf_norms(grads), gn

    return step


def train(c: Dict, o: Dict, num: Numerics, key, batches, *, row_block=1):
    """Run ``len(batches)`` steps from :func:`init_weights` ``(key)``.

    Returns the loss of each step, the first step's raw gradient norms (per
    parameter and layer), its global gradient norm, and the norms of each
    parameter's change over all the steps."""
    step = jax.jit(make_step(c, o, num, row_block), donate_argnums=(0, 1, 2))
    f32 = lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t)
    params = jax.jit(lambda k: f32(init_weights(k, c)))(key)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, (tokens, labels) in enumerate(batches):
        params, mu, nu, loss, gnorms, gn = step(
            params, mu, nu, jnp.asarray(t, jnp.int32), tokens, labels)
        losses.append(float(loss))
        if first is None:
            first = (jax.device_get(gnorms), float(gn))
    del mu, nu
    change = jax.jit(lambda p, k: leaf_norms(jax.tree.map(
        jnp.subtract, p, f32(init_weights(k, c)))))(params, key)
    return {"losses": losses, "grad_norms": first[0], "grad_norm": first[1],
            "change_norms": jax.device_get(change)}
