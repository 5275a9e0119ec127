"""Step builders: train / prefill / serve through the GPipe pipeline.

Each builder returns a pure function ready for ``jax.jit`` plus the sharding
specs the dry-run / drivers need.  All batch shapes are GLOBAL — GSPMD owns
the (pod, data, tp) axes; the pipeline shard_map owns ``pipe``.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ParallelConfig, ShapeConfig
from repro.core import balance
from repro.core.pipeline import (last_stage_output, microbatch, pipeline_call,
                                 pipeline_grad_call, unmicrobatch)
from repro.launch import sharding
from repro.models.lm import LMModel
from repro.optim import optimizers as optim
from repro.runtime.compression import EFCompressor
from repro.scopes import GRAD_REDUCE, scoped


def _carry_proto(model: LMModel, mbg: int, seq: int):
    return {"h": jax.ShapeDtypeStruct((mbg, seq, model.arch.d_model),
                                      model.dtype)}


@scoped(GRAD_REDUCE)
def _maybe_compress_grads(pcfg: ParallelConfig, grads, opt_state):
    """int8-EF the DP gradient reduce (grad_compression="int8_ef").

    The quantize/dequantize + residual update runs before the optimizer;
    under GSPMD the cross-replica mean is implicit in sharding propagation,
    so ``reduce_fn`` stays identity and the transform prices/ships the int8
    payload on the slow (cross-pod) link.  Returns the (possibly) rewritten
    grads plus the new EF residual pytree to store on the OptState.
    """
    if pcfg.grad_compression != "int8_ef":
        return grads, opt_state.ef
    if not jax.tree_util.tree_leaves(opt_state.ef):
        raise ValueError(
            "grad_compression='int8_ef' needs the error-feedback residual "
            "on the optimizer state: initialize it with "
            "optim.init(ocfg, params, with_ef=True)")
    return EFCompressor().compress_reduce(grads, opt_state.ef)


def _gate_ef(metrics: Dict, new_ef, old_ef):
    """Discard the EF-residual rewrite on a skipped (non-finite) step.

    The compressor updates its error-feedback residual BEFORE the
    optimizer sees the grads, so without this gate a NaN batch would
    poison the residual even though optim.apply discarded the step."""
    fin = metrics.get("finite")
    if fin is None or not jax.tree_util.tree_leaves(new_ef):
        return new_ef
    return jax.tree.map(lambda n, o: jnp.where(fin > 0, n, o),
                        new_ef, old_ef)


def stage_partition(arch: ArchConfig, pcfg: ParallelConfig, *,
                    by: str = "flops", seq_len: int = 0) -> Tuple[int, ...]:
    """Balanced layer -> stage cuts for ``pcfg`` (torchgpipe.balance, wired).

    Partitions the arch's layers over ``pipe * virtual_stages`` GLOBAL
    stages with the exact contiguous minimax partitioner, weighting layers
    by analytic per-layer flops (``by="flops"``; pass ``seq_len`` for the
    attention quadratic term) or parameter bytes (``by="size"``) from
    :func:`repro.core.balance.arch_layer_costs`.  Feed the result to
    ``pcfg.with_(partition=...)`` — the model assembly scatters layers and
    their constants accordingly.
    """
    if by not in ("flops", "size"):
        raise ValueError(f"unknown balance objective {by!r}; "
                         "want 'flops' or 'size'")
    n_stages = pcfg.pipe * pcfg.virtual_stages
    flops, pbytes = balance.arch_layer_costs(arch, seq_len)
    costs = flops if by == "flops" else pbytes
    return tuple(balance.block_partition(costs, n_stages))


# ---------------------------------------------------------------------------
# Train
# ---------------------------------------------------------------------------

def build_train_step(model: LMModel, pcfg: ParallelConfig, mesh: Mesh,
                     shape: ShapeConfig,
                     ocfg: Optional[optim.OptimizerConfig] = None,
                     resid_info: Optional[dict] = None):
    """Returns train_step(params, opt_state, batch) -> (params, opt, metrics).

    ``pcfg.schedule`` selects the execution order: the default ``"gpipe"``
    runs the forward clock-cycle and lets autodiff induce the reverse
    clock-cycle; ``"1f1b"`` / ``"gpipe_tasked"`` / ``"interleaved:v"`` /
    ``"zb"`` run the fused scheduler, where backward tasks execute inside
    the tick loop per the task table (see repro.core.plan) and the
    activation stash is sized structurally.  ``pcfg.residuals="reuse"``
    turns on ZB-H1 residual reuse for split-backward schedules; pass a
    dict as ``resid_info`` to receive the residual-stash geometry (leaf
    shapes, bytes per slot) when the step first traces.
    ``pcfg.executor`` selects the plan lowering: ``"spmd"`` (rank-uniform
    reference) or ``"mpmd"`` (per-rank specialized programs with the
    chain permute double-buffered one tick ahead — bitwise-identical
    results, see :func:`repro.core.pipeline.run_pipeline_tasks`).
    """
    ocfg = ocfg or optim.OptimizerConfig()
    # Gate known config smells at selection time: zb + recompute prices
    # Bx+Bw at 4 stage-forwards per micro (vs fused B's 3), which the
    # device model shows LOSING to 1f1b in low-bubble regimes; the
    # advisory recommends residuals="reuse" (true ZB-H1).
    for msg in pcfg.advisories():
        warnings.warn(msg, stacklevel=2)
    spec = pcfg.schedule_spec            # structured view of the knobs
    if spec.base in ("1f1b", "gpipe_tasked", "interleaved", "zb"):
        return _build_train_step_fused(model, pcfg, mesh, shape, ocfg,
                                       resid_info=resid_info)
    if spec.base != "gpipe":
        raise ValueError(f"unknown schedule {pcfg.schedule!r}; want 'gpipe', "
                         "'gpipe_tasked', '1f1b', 'interleaved:v', or 'zb'")
    consts = model.consts()
    stage_apply = model.make_stage_apply(consts)
    mbg = shape.global_batch // pcfg.n_micro
    pipe = pipeline_call(
        stage_apply, mesh=mesh, cfg=pcfg, skips=model.skips(),
        skip_protos=model.skip_protos(mbg, shape.seq_len),
        carry_proto=_carry_proto(model, mbg, shape.seq_len))

    def loss_fn(params, batch):
        fresh = model.embed_inputs(params["embed"], batch)
        inputs_mb = microbatch(fresh, pcfg.n_micro)
        stages = params["stages"]
        if pcfg.gather_weights_once:
            stages = sharding.gather_stage_weights(stages, mesh)
        outs, _ = pipe(stages, inputs_mb, None)
        h = unmicrobatch(last_stage_output(outs)["h"])
        return model.head_loss(params, h, batch["labels"])

    def train_step(params, opt_state, batch):
        if ocfg.dynamic_loss_scale:
            # Scale the loss before autodiff so reduced-precision grads
            # stay representable; optim.apply unscales after its overflow
            # check on the scaled grads.
            fn = lambda p, b: loss_fn(p, b) * opt_state.scale
        else:
            fn = loss_fn
        loss, grads = jax.value_and_grad(fn)(params, batch)
        grads, new_ef = _maybe_compress_grads(pcfg, grads, opt_state)
        params2, opt2, metrics = optim.apply(ocfg, opt_state, params,
                                             grads, loss=loss)
        opt2 = opt2._replace(ef=_gate_ef(metrics, new_ef, opt_state.ef))
        metrics["loss"] = (loss / opt_state.scale
                           if ocfg.dynamic_loss_scale else loss)
        return params2, opt2, metrics

    return train_step


def _build_train_step_fused(model: LMModel, pcfg: ParallelConfig, mesh: Mesh,
                            shape: ShapeConfig, ocfg: optim.OptimizerConfig,
                            resid_info: Optional[dict] = None):
    """Schedule-driven train step: the pipeline computes its own gradients.

    The fused executor returns stage grads, head grads, and per-micro input
    cotangents; only the (cheap, GSPMD-land) embedding VJP remains outside
    the pipeline.  Tied-embedding models route part of the table's gradient
    through the head loss — both contributions are summed here.  Skip edges
    (enc-dec portals) and streamed inputs lower into the same plan the
    executor runs, so every ``cfg.schedule`` covers every workload.
    """
    consts = model.consts()
    stage_apply = model.make_stage_apply(consts)
    mbg = shape.global_batch // pcfg.n_micro

    def micro_loss(head_ps, carry, largs):
        loss = model.head_loss(head_ps, carry["h"], largs["labels"])
        if ocfg.dynamic_loss_scale:
            # "_scale" rides along in head_ps so every micro's loss (and
            # therefore every cotangent the pipeline emits) is scaled;
            # its own cotangent is discarded below.
            loss = loss * head_ps["_scale"]
        return loss

    pipe_grad, _ = pipeline_grad_call(
        stage_apply, mesh=mesh, cfg=pcfg, loss_fn=micro_loss,
        skips=model.skips(),
        skip_protos=model.skip_protos(mbg, shape.seq_len),
        carry_proto=_carry_proto(model, mbg, shape.seq_len),
        resid_info=resid_info)

    def train_step(params, opt_state, batch):
        fresh, embed_vjp = jax.vjp(
            lambda emb: model.embed_inputs(emb, batch), params["embed"])
        inputs_mb = microbatch(fresh, pcfg.n_micro)
        labels_mb = microbatch({"labels": batch["labels"]}, pcfg.n_micro)
        head_ps = {"head": params["head"], "embed": params["embed"]}
        if ocfg.dynamic_loss_scale:
            head_ps["_scale"] = opt_state.scale
        loss, g_stage, g_head, ig = pipe_grad(params["stages"], head_ps,
                                              inputs_mb, labels_mb)
        (g_embed,) = embed_vjp(unmicrobatch(ig))
        g_embed = jax.tree.map(jnp.add, g_embed, g_head["embed"])
        grads = {"embed": g_embed, "stages": g_stage, "head": g_head["head"]}
        grads, new_ef = _maybe_compress_grads(pcfg, grads, opt_state)
        params2, opt2, metrics = optim.apply(ocfg, opt_state, params,
                                             grads, loss=loss)
        opt2 = opt2._replace(ef=_gate_ef(metrics, new_ef, opt_state.ef))
        metrics["loss"] = (loss / opt_state.scale
                           if ocfg.dynamic_loss_scale else loss)
        return params2, opt2, metrics

    return train_step


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def build_prefill_step(model: LMModel, pcfg: ParallelConfig, mesh: Mesh,
                       shape: ShapeConfig):
    """prefill_step(params, cache, batch) -> (last_token_logits, cache)."""
    consts = model.consts()
    stage_apply = model.make_stage_apply(consts, prefill=True)
    mbg = shape.global_batch // pcfg.n_micro
    pipe = pipeline_call(
        stage_apply, mesh=mesh, cfg=pcfg, skips=model.skips(),
        skip_protos=model.skip_protos(mbg, shape.seq_len),
        carry_proto=_carry_proto(model, mbg, shape.seq_len))

    def prefill_step(params, cache, batch):
        fresh = model.embed_inputs(params["embed"], batch)
        inputs_mb = microbatch(fresh, pcfg.n_micro)
        outs, cache = pipe(params["stages"], inputs_mb, cache)
        h = unmicrobatch(last_stage_output(outs)["h"])
        logits = model.head_logits(params, h[:, -1:, :])
        return logits, cache

    return prefill_step


# ---------------------------------------------------------------------------
# Decode / serve
# ---------------------------------------------------------------------------

def build_serve_step(model: LMModel, pcfg: ParallelConfig, mesh: Mesh,
                     shape: ShapeConfig):
    """serve_step(params, cache, tokens) -> (logits [B,1,V], cache).

    One decode tick: the request batch is micro-batched through the pipeline
    exactly like training (the paper's schedule reused for inference)."""
    consts = model.consts()
    stage_apply = model.make_stage_apply_decode(consts)
    mbg = shape.global_batch // pcfg.n_micro
    pipe = pipeline_call(stage_apply, mesh=mesh, cfg=pcfg,
                         carry_proto=_carry_proto(model, mbg, 1))

    def serve_step(params, cache, tokens):
        h = model.embed_decode(params["embed"], tokens, pos=shape.seq_len)
        inputs_mb = microbatch({"h": h}, pcfg.n_micro)
        outs, cache = pipe(params["stages"], inputs_mb, cache)
        h1 = unmicrobatch(last_stage_output(outs)["h"])
        return model.head_logits(params, h1), cache

    return serve_step


# ---------------------------------------------------------------------------
# Sharded jit assembly for a full cell (used by dryrun + drivers)
# ---------------------------------------------------------------------------

@dataclass
class CompiledCell:
    fn: Callable
    in_shardings: Tuple
    abstract_args: Tuple
    kind: str


def abstract_params(model: LMModel):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0))


def build_cell(model: LMModel, pcfg: ParallelConfig, mesh: Mesh,
               shape: ShapeConfig,
               ocfg: Optional[optim.OptimizerConfig] = None) -> CompiledCell:
    """Assemble the jit-able step + shardings + abstract args for one cell."""
    params_p = abstract_params(model)
    pspecs = sharding.param_specs(params_p, mesh)
    pshard = sharding.named(pspecs, mesh)
    batch_p = model.input_specs(shape)
    bshard = sharding.named(sharding.batch_specs(batch_p, mesh), mesh)

    if shape.kind == "train":
        step = build_train_step(model, pcfg, mesh, shape, ocfg)
        opt_p = jax.eval_shape(
            functools.partial(optim.init, ocfg or optim.OptimizerConfig(),
                              with_ef=pcfg.grad_compression == "int8_ef"),
            params_p)
        ospecs = sharding.opt_state_specs(pspecs, opt_p)
        oshard = sharding.named(ospecs, mesh)
        return CompiledCell(step, (pshard, oshard, bshard),
                            (params_p, opt_p, batch_p), "train")

    cache_p = model.cache_protos(shape, pcfg.n_micro)
    cshard = sharding.named(
        sharding.cache_specs(cache_p, mesh,
                             seq_shard=shape.global_batch <
                             mesh.shape.get("data", 1) *
                             mesh.shape.get("pod", 1)), mesh)
    if shape.kind == "prefill":
        step = build_prefill_step(model, pcfg, mesh, shape)
        return CompiledCell(step, (pshard, cshard, bshard),
                            (params_p, cache_p, batch_p), "prefill")

    step = build_serve_step(model, pcfg, mesh, shape)
    tok_p = batch_p["tokens"]
    tshard = sharding.named(sharding.batch_specs(tok_p, mesh), mesh)
    return CompiledCell(step, (pshard, cshard, tshard),
                        (params_p, cache_p, tok_p), "decode")
