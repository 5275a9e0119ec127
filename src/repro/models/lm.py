"""LM-family model assembly: params, stacked stages, embed/head, caches.

A model is a sequence of identical blocks (per family) plus an embedding
frontend and an LM head.  The blocks are stacked ``[n_stages, L_per_stage]``
for the pipeline (identity-padded per core.stage.pad_layout); embed and head
run *outside* the pipeline shard_map in plain GSPMD land (they are cheap
relative to the trunk and their parameters are FSDP/TP-sharded, replicated
over ``pipe``).

Encoder-decoder (whisper): encoder layers fill the leading stages, decoder
layers the trailing ones; the per-layer constant record carries
``causal``/``cross``/``dec_active`` flags and the encoder output reaches
every decoder stage through portal skip edges (paper §3.3.1) — the strongest
real use of portals among the assigned architectures.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ParallelConfig, ShapeConfig
from repro.core import stage as stage_lib
from repro.core.skip import SkipSpec
from repro.models import blocks as B
from repro.models import layers as L
from repro.scopes import EMBED, HEAD_LOSS, STAGE, scoped


def _embed_lookup(table, tokens, dtype):
    """Token-embedding gather, upcast to fp32 around the take.

    XLA CPU's AllReducePromotion pass crashes ("Invalid binary instruction
    opcode copy") when promoting the bf16 all-reduce that the partitioner
    emits for the gather's scatter-add gradient on a vocab-sharded table.
    Routing the gather (and hence its transpose) through fp32 sidesteps the
    pass with negligible cost and better embedding-grad accumulation.
    """
    return jnp.take(table.astype(jnp.float32), tokens, axis=0).astype(dtype)


def sinusoidal(positions, d: int, dtype=jnp.float32):
    half = d // 2
    freq = jnp.exp(-jnp.arange(half) / (half - 1) * np.log(10000.0))
    ang = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1).astype(dtype)


@dataclass
class LMModel:
    arch: ArchConfig
    pcfg: ParallelConfig
    dtype: Any = jnp.bfloat16

    def __post_init__(self):
        a = self.arch
        self.total_layers = a.n_layers + a.enc_layers
        # interleaved schedules cut the model into pipe * v GLOBAL stages;
        # rank r hosts the v chunks {r, r + pipe, ...} (Megatron layout)
        self.n_stages = self.pcfg.pipe * self.pcfg.virtual_stages
        # balance-partitioned (pcfg.partition) or legacy uniform ceil layout
        self.layout = stage_lib.partition_layout(
            self.total_layers, self.n_stages, self.pcfg.partition or None)
        self.L_per_stage = self.layout.L_per_stage
        self.layer_mask = self.layout.mask          # np [n_stages, L]
        fam = B.FAMILIES[a.family]
        (self.block_init, self.block_apply, self.block_decode,
         self.block_cache_proto, self.block_prefill) = fam
        # encoder/decoder stage split (whisper): encoder layers come first.
        if a.is_encdec:
            self.enc_last_stage = self.layout.stage_of(a.enc_layers - 1)
            self.dec_first_stage = self.layout.stage_of(a.enc_layers) \
                if a.enc_layers < self.total_layers else self.n_stages
        else:
            self.enc_last_stage = self.dec_first_stage = -1

    # ------------------------------------------------------------------ params
    def init(self, key):
        a = self.arch
        ks = jax.random.split(key, self.total_layers + 3)
        layer_ps = [self.block_init(ks[i], a, self.dtype)
                    for i in range(self.total_layers)]
        stages = stage_lib.stack_layer_params(layer_ps, self.n_stages,
                                              self.pcfg.partition or None)
        emb = {"tok": (jax.random.normal(ks[-1], (a.vocab, a.d_model))
                       * a.d_model ** -0.5).astype(self.dtype)}
        head = {"norm": L.norm_init(a.d_model, a.norm, self.dtype)}
        if not a.tie_embeddings:
            head["w"] = (jax.random.normal(ks[-2], (a.d_model, a.vocab))
                         * a.d_model ** -0.5).astype(self.dtype)
        return {"embed": emb, "stages": stages, "head": head}

    # ------------------------------------------------------------ layer consts
    def consts(self) -> Dict[str, jnp.ndarray]:
        """Stacked [n_stages, L_per_stage] per-layer constants.

        Built per GLOBAL layer then scattered onto the (possibly
        balance-partitioned) slot grid; padding slots take the identity
        defaults (mask 0, causal 1, dec_active 1) so padded layers stay
        exact identities under any partition.
        """
        a = self.arch
        tl = self.total_layers
        window = np.zeros(tl, np.int32)
        causal = np.ones(tl, np.int32)
        cross = np.zeros(tl, np.float32)
        dec_active = np.ones(tl, np.float32)
        if a.attn is not None:
            if a.attn.global_layers:
                window[:] = a.attn.window
                for g in a.attn.global_layers:
                    if g < tl:
                        window[g] = B.GLOBAL_WINDOW
            elif a.attn.kind == "swa":
                window[:] = a.attn.window
        is_enc_last = np.zeros(tl, np.float32)
        is_dec_first = np.zeros(tl, np.float32)
        if a.is_encdec:
            causal[:a.enc_layers] = 0
            cross[a.enc_layers:tl] = 1.0
            dec_active[:a.enc_layers] = 0.0
            is_enc_last[a.enc_layers - 1] = 1.0
            is_dec_first[a.enc_layers] = 1.0
        sc = self.layout.scatter
        c = {
            "mask": jnp.asarray(self.layer_mask, jnp.float32),
            "window": jnp.asarray(sc(window, 0)),
            "causal": jnp.asarray(sc(causal, 1)),
            "cross": jnp.asarray(sc(cross, 0.0)),
            "dec_active": jnp.asarray(sc(dec_active, 1.0)),
            "is_enc_last": jnp.asarray(sc(is_enc_last, 0.0)),
            "is_dec_first": jnp.asarray(sc(is_dec_first, 0.0)),
        }
        return c

    # ------------------------------------------------------------------ skips
    def skips(self) -> List[SkipSpec]:
        """Whisper: memory from the last encoder stage to every decoder
        stage, plus the decoder token embeddings from stage 0 to the first
        decoder stage.  Empty when the enc->dec boundary falls inside one
        stage (no cross-stage skip needed)."""
        if not self.arch.is_encdec:
            return []
        edges = []
        dec_stages = tuple(d for d in range(self.dec_first_stage, self.n_stages)
                           if d > self.enc_last_stage)
        if dec_stages:
            edges.append(SkipSpec("mem", self.enc_last_stage, dec_stages))
        if self.dec_first_stage > 0:
            edges.append(SkipSpec("dec_in", 0, (self.dec_first_stage,)))
        return edges

    def skip_protos(self, mb: int, S: int):
        if not self.arch.is_encdec:
            return {}
        proto = jax.ShapeDtypeStruct((mb, S, self.arch.d_model), self.dtype)
        return {"mem": proto, "dec_in": proto}

    # ------------------------------------------------------------------ embed
    @scoped(EMBED)
    def embed_inputs(self, emb, batch) -> Dict[str, jnp.ndarray]:
        """batch -> fresh stage-0 input pytree [B, ...]."""
        a = self.arch
        if a.is_encdec:
            h = batch["frames"].astype(self.dtype)           # stub frontend
            S = h.shape[1]
            h = h + sinusoidal(jnp.arange(S), a.d_model, self.dtype)[None]
            dec = jnp.take(emb["tok"], batch["dec_tokens"], axis=0)
            dec = dec + sinusoidal(jnp.arange(dec.shape[1]), a.d_model,
                                   self.dtype)[None]
            return {"h": L.act_bd(h), "dec_h": L.act_bd(dec)}
        h = _embed_lookup(emb["tok"], batch["tokens"], self.dtype)
        if a.name.startswith("gemma"):
            h = h * jnp.asarray(a.d_model ** 0.5, self.dtype)
        if a.frontend == "vision_stub" and "patches" in batch:
            p = batch["patches"].astype(self.dtype)
            np_ = min(p.shape[1], h.shape[1])    # patch tokens replace prefix
            h = jnp.concatenate([p[:, :np_], h[:, np_:]], axis=1)
        return {"h": L.act_bd(h)}

    def embed_decode(self, emb, tokens, pos):
        """Embed one decode token at absolute position ``pos``."""
        a = self.arch
        h = _embed_lookup(emb["tok"], tokens, self.dtype)
        if a.name.startswith("gemma"):
            h = h * jnp.asarray(a.d_model ** 0.5, self.dtype)
        if (a.is_encdec or not (a.attn and a.attn.use_rope)) \
                and a.family != "ssm":
            h = h + sinusoidal(jnp.asarray(pos)[None], a.d_model,
                               self.dtype)[None]
        return L.act_bd(h.astype(self.dtype))

    # ---------------------------------------------- stage fn (train / prefill)
    def make_stage_apply(self, consts, *, prefill: bool = False):
        """stage_apply for the pipeline runner.

        Encoder-decoder logic is uniform across all pipe/stage splits: the
        layer scan carries (h, mem, dec_emb); per-layer constants switch the
        carry from encoder hidden to decoder embeddings at ``is_dec_first``
        and latch the encoder output into ``mem`` at ``is_enc_last``.  Across
        stages, ``mem``/``dec_emb`` arrive through portal skip edges.
        """
        model = self
        a = model.arch

        def stage_apply(stage_params, carry, skips_in, resident, ctx):
            h = carry["h"]
            h = jnp.where(ctx.stage == 0, ctx.fresh["h"], h)
            if a.is_encdec:
                dec_emb = skips_in.get("dec_in", ctx.fresh.get("dec_h"))
                if dec_emb is None:
                    dec_emb = ctx.fresh["dec_h"]
                if "dec_in" in skips_in:
                    dec_emb = jnp.where(ctx.stage == 0, ctx.fresh["dec_h"],
                                        dec_emb)
                mem = skips_in.get("mem", jnp.zeros_like(h))
            else:
                dec_emb = None
                mem = None
            c_local = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, ctx.stage, 0, keepdims=False), consts)

            def body(carry_t, xs):
                h, mem, dec_emb = carry_t
                if prefill:
                    lp, c, cache = xs
                else:
                    lp, c = xs
                if a.is_encdec:
                    h = jnp.where(c["is_dec_first"] > 0, dec_emb, h)
                if prefill:
                    h2, cache = model.block_prefill(lp, h, c, a, cache,
                                                    memory=mem)
                else:
                    apply = model.block_apply
                    if model.pcfg.remat_layers:
                        apply = jax.checkpoint(
                            lambda lp_, h_, c_: model.block_apply(
                                lp_, h_, c_, a, memory=mem))
                        h2 = apply(lp, h, c)
                    else:
                        h2 = apply(lp, h, c, a, memory=mem)
                if a.is_encdec:
                    mem = jnp.where(c["is_enc_last"] > 0, h2, mem)
                out = (h2, mem, dec_emb)
                return out, (cache if prefill else None)

            with jax.named_scope(STAGE):
                if prefill:
                    cache_mb = jax.tree.map(
                        lambda x: jax.lax.dynamic_index_in_dim(
                            x, ctx.micro, 1, keepdims=False), resident)
                    (h, mem, _), caches_new = jax.lax.scan(
                        body, (h, mem, dec_emb),
                        (stage_params, c_local, cache_mb))
                    resident = jax.tree.map(
                        lambda full, new: jax.lax.dynamic_update_index_in_dim(
                            full, new.astype(full.dtype), ctx.micro, 1),
                        resident, caches_new)
                else:
                    (h, mem, _), _ = jax.lax.scan(
                        body, (h, mem, dec_emb), (stage_params, c_local))

            skips_out = {}
            if a.is_encdec:
                if mem is not None:
                    skips_out["mem"] = (mem if mem is not None else h).astype(model.dtype)
                skips_out["dec_in"] = ctx.fresh["dec_h"]
                skips_out = {k: v for k, v in skips_out.items()
                             if any(s.name == k for s in model.skips())}
            return {"h": h}, skips_out, resident

        return stage_apply

    # ------------------------------------------------------ stage fn (decode)
    def make_stage_apply_decode(self, consts):
        model = self

        def stage_apply(stage_params, carry, skips_in, resident, ctx):
            a = model.arch
            h = carry["h"]                       # [mb, 1, D]
            h = jnp.where(ctx.stage == 0, ctx.fresh["h"], h)
            c_all = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, ctx.stage, 0, keepdims=False), consts)
            # caches for this micro-batch slot
            cache_mb = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(
                    x, ctx.micro, 1, keepdims=False), resident)

            def body(hc, lp_c_cache):
                h = hc
                lp, c, cache = lp_c_cache
                h2, cache2 = model.block_decode(lp, h, c, a, cache)
                if a.is_encdec:
                    act = c["dec_active"]
                    h2 = jnp.where(act > 0, h2, h)
                    cache2 = jax.tree.map(
                        lambda new, old: jnp.where(act > 0, new, old),
                        cache2, cache)
                return h2, cache2

            h, caches_new = jax.lax.scan(
                lambda hh, xs: body(hh, xs),
                h, (stage_params, c_all, cache_mb))
            res_new = jax.tree.map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), ctx.micro, 1),
                resident, caches_new)
            return {"h": h}, {}, res_new

        return stage_apply

    # --------------------------------------------------------------- head/loss
    def head_logits(self, params, h):
        a = self.arch
        hn = L.norm_apply(params["head"]["norm"], h, a.norm)
        w = params["head"].get("w")
        if w is None:
            # tied embeddings: the table is d_model-sharded (gather-safe for
            # the embedding lookup); for the head matmul re-constrain it to
            # vocab-over-tp (replicated over data) so the logits contraction
            # is local per chunk.  One cheap table reshard per step.
            emb = L.constrain(params["embed"]["tok"],
                              jax.sharding.PartitionSpec(None, L.TP))
            w = emb.T
        return hn @ w

    @scoped(HEAD_LOSS)
    def head_loss(self, params, h, labels, *, chunk: int = 0):
        """Chunked softmax cross-entropy over the sequence (never
        materializes [B, S, V] for the full sequence).

        Chunking notes from the §Perf iterations: smaller chunks multiply
        the per-chunk fp32 dW all-reduce that the scan's gradient
        accumulator forces (64 chunks cost 107 GB/step at 100k vocab);
        unrolling the loop lets chunk logits coexist (101 GiB/device).
        chunk=512 with a scan is the measured sweet spot."""
        a = self.arch
        h = L.act_bd(h)
        Bsz, S, D = h.shape
        if chunk <= 0:
            chunk = 512
        c = min(chunk, S)
        while S % c:
            c -= 1
        nchunk = S // c
        hc = h.reshape(Bsz, nchunk, c, D).swapaxes(0, 1)
        lc = labels.reshape(Bsz, nchunk, c).swapaxes(0, 1)

        @jax.checkpoint
        def one(hx, lx):
            hx = L.constrain(hx, jax.sharding.PartitionSpec(
                L.BATCH, None, None))
            logits = self.head_logits(params, hx).astype(jnp.float32)
            logits = L.constrain(logits, jax.sharding.PartitionSpec(
                L.BATCH, None, L.TP))
            logz = jax.nn.logsumexp(logits, -1)
            gold = jnp.take_along_axis(logits, lx[..., None], -1)[..., 0]
            return (logz - gold).sum()

        def body(acc, xs):
            hx, lx = xs
            return acc + one(hx, lx), None

        tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc))
        return tot / (Bsz * S)

    # ----------------------------------------------------------------- caches
    def cache_protos(self, shape: ShapeConfig, n_micro: int):
        """Stacked resident cache protos [n_stages, L_per_stage, m, mb, ...]."""
        a = self.arch
        mb = shape.global_batch // n_micro
        slots_len = shape.seq_len + 64
        per_layer = self.block_cache_proto(a, mb, slots_len, self.dtype)

        def stack(p):
            return jax.ShapeDtypeStruct(
                (self.n_stages, self.L_per_stage, n_micro) + tuple(p.shape),
                p.dtype)
        return jax.tree.map(stack, per_layer)

    def init_cache(self, shape: ShapeConfig, n_micro: int, *, filled: bool):
        """Concrete zero caches; ``filled`` marks them as already holding
        ``seq_len`` tokens (the decode_* shapes' precondition)."""
        protos = self.cache_protos(shape, n_micro)

        def mk(p):
            z = jnp.zeros(tuple(p.shape), p.dtype)
            return z
        cache = jax.tree.map(mk, protos)
        if filled:
            cache = jax.tree.map(
                lambda x: (jnp.full_like(x, shape.seq_len)
                           if x.dtype == jnp.int32 and x.ndim == 3 else x),
                cache)
        return cache

    # ------------------------------------------------------------ input specs
    def input_specs(self, shape: ShapeConfig) -> Dict[str, jax.ShapeDtypeStruct]:
        a = self.arch
        Bsz, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        if shape.kind == "train":
            if a.is_encdec:
                return {"frames": jax.ShapeDtypeStruct((Bsz, S, a.d_model), jnp.bfloat16),
                        "dec_tokens": jax.ShapeDtypeStruct((Bsz, S), i32),
                        "labels": jax.ShapeDtypeStruct((Bsz, S), i32)}
                # frontend stub: precomputed frame embeddings per assignment
            spec = {"tokens": jax.ShapeDtypeStruct((Bsz, S), i32),
                    "labels": jax.ShapeDtypeStruct((Bsz, S), i32)}
            if a.frontend == "vision_stub":
                spec["patches"] = jax.ShapeDtypeStruct((Bsz, 256, a.d_model),
                                                       jnp.bfloat16)
            return spec
        if shape.kind == "prefill":
            if a.is_encdec:
                return {"frames": jax.ShapeDtypeStruct((Bsz, S, a.d_model), jnp.bfloat16),
                        "dec_tokens": jax.ShapeDtypeStruct((Bsz, S), i32)}
            spec = {"tokens": jax.ShapeDtypeStruct((Bsz, S), i32)}
            if a.frontend == "vision_stub":
                spec["patches"] = jax.ShapeDtypeStruct((Bsz, 256, a.d_model),
                                                       jnp.bfloat16)
            return spec
        # decode: one token per sequence + resident caches
        return {"tokens": jax.ShapeDtypeStruct((Bsz, 1), i32)}
