"""Heterogeneous (switch-based) pipeline programs: U-Net / AmoebaNet.

LM stages are homogeneous (stacked params); conv nets change channel counts
and resolutions per stage, so each stage gets its own branch under
``lax.switch(stage_idx, ...)`` (core/stage.py rationale).  Stage boundaries
carry a flat fp32 activation buffer padded to the largest boundary.

Skip connections crossing stage boundaries follow paper §3.3:
  * portals=True  — each skip rides a dedicated single-pair
    collective-permute + destination ring (repro.core.skip);
  * portals=False — the skip is packed INTO the boundary buffer and hops
    through every intermediate stage (the symptomatic case; the buffer and
    hence every ``collective-permute`` gets wider, which the ablation
    benchmark measures).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ParallelConfig
from repro.core import stage as stage_lib
from repro.core.pipeline import (last_stage_output, microbatch,
                                 pipeline_call, pipeline_grad_call,
                                 unmicrobatch)
from repro.core.skip import SkipSpec


@dataclass
class HeteroProgram:
    stacked_params: Any             # [n_stages, max_flat] fp32
    stage_apply: Callable           # pipeline StageApplyFn
    carry_proto: Any                # {"buf": SDS([mb, max_elems])}
    skips: List[SkipSpec]
    skip_protos: Dict[str, Any]
    out_proto: Any                  # final stage output pytree proto


def _buffer_proto(protos: Sequence[Any], mb: int) -> int:
    return max(stage_lib.buffer_elems(p) for p in protos)


def build_hetero_program(model, params, mb: int, pcfg: ParallelConfig,
                         example_input) -> HeteroProgram:
    """Compile a layer-list model (UNetModel/AmoebaNetModel API) into a
    switch-based pipeline program.

    model must expose: layers, bounds, n_stages, layer_apply(i, p, x, skips),
    and (for skip routing) optional .skip_edges().
    """
    n = model.n_stages
    bounds = model.bounds

    # one abstract pass: boundary activation shapes, skip tensor shapes,
    # and which stage produces/consumes each skip
    stage_of = np.zeros(len(model.layers), int)
    for s in range(n):
        stage_of[bounds[s]:bounds[s + 1]] = s
    produced_at: Dict[str, int] = {}
    consumed_at: Dict[str, int] = {}
    skip_shapes: Dict[str, Any] = {}
    boundary_x: List[Any] = [jax.eval_shape(lambda v: v, example_input)]
    x = boundary_x[0]
    store: Dict[str, Any] = {}
    for i, l in enumerate(model.layers):
        def step(v, st, _i=i):
            st = dict(st)
            out = model.layer_apply(_i, params[_i], v, st)
            return out, st
        x, store = jax.eval_shape(step, x, store)
        store = dict(store)
        skip_shapes.update(store)
        if getattr(l, "skip_out", None):
            produced_at[l.skip_out] = int(stage_of[i])
        if getattr(l, "skip_in", None):
            consumed_at[l.skip_in] = int(stage_of[i])
        if i + 1 in bounds[1:]:
            boundary_x.append(x)
    out_proto = x

    # skips crossing stage boundaries
    crossing = {k: (produced_at[k], consumed_at[k])
                for k in produced_at
                if k in consumed_at and consumed_at[k] > produced_at[k]}
    use_portals = pcfg.portals
    portal_edges = [SkipSpec(k, int(s), (int(d),))
                    for k, (s, d) in crossing.items()] if use_portals else []

    # per-stage boundary protos: x plus (threaded mode) live crossing skips
    def live_at(s):
        return {k: None for k, (src, dst) in crossing.items()
                if src < s <= dst} if not use_portals else {}

    in_protos, out_protos = [], []
    for s in range(n):
        xin = {"x": boundary_x[s],
               **{k: skip_shapes[k] for k in live_at(s)}}
        xout = {"x": boundary_x[s + 1],
                **{k: skip_shapes[k] for k in live_at(s + 1)}}
        in_protos.append(xin)
        out_protos.append(xout)
    max_elems = _buffer_proto(in_protos + out_protos, mb)

    # flat-pack the per-stage params
    flats, treedefs, shapess = [], [], []
    for s in range(n):
        f, td, sh = stage_lib.flatten_params(params[bounds[s]:bounds[s + 1]])
        flats.append(f)
        treedefs.append(td)
        shapess.append(sh)
    size = max(f.shape[0] for f in flats)
    stacked = jnp.stack([jnp.pad(f, (0, size - f.shape[0])) for f in flats])

    skip_protos = {e.name: skip_shapes[e.name] for e in portal_edges}

    def make_branch(s: int):
        def branch(flat_params, buf, skips_in):
            p_list = stage_lib.unflatten_params(flat_params, treedefs[s],
                                                shapess[s])
            xin = stage_lib.unpack_buffer(buf, in_protos[s])
            x = xin.pop("x")
            store = dict(xin)
            for e in portal_edges:
                if e.dsts[0] == s:
                    store[e.name] = skips_in[e.name]
            outs = {}
            for li in range(bounds[s], bounds[s + 1]):
                x = model.layer_apply(li, p_list[li - bounds[s]], x, store)
            # zero skips take the runtime batch (x.shape[0]): switch
            # branches must agree on their output shapes.
            skips_out = {e.name: (store[e.name] if e.name in store
                                  else jnp.zeros(
                                      (x.shape[0],)
                                      + tuple(skip_protos[e.name].shape[1:]),
                                      skip_protos[e.name].dtype))
                         for e in portal_edges}
            pack = {"x": x}
            for k in live_at(s + 1):
                pack[k] = store[k]
            return stage_lib.pack_buffer(pack, max_elems), skips_out
        return branch

    branches = [make_branch(s) for s in range(n)]

    def stage_apply(stage_params, carry, skips_in, resident, ctx):
        buf_in = jnp.where(ctx.stage == 0, ctx.fresh["buf"], carry["buf"])
        sidx = jnp.clip(ctx.stage, 0, n - 1)
        buf, skips_out = jax.lax.switch(sidx, branches, stage_params,
                                        buf_in, skips_in)
        return {"buf": buf}, skips_out, resident

    carry_proto = {"buf": jax.ShapeDtypeStruct((mb, max_elems), jnp.float32)}
    return HeteroProgram(stacked, stage_apply, carry_proto, portal_edges,
                         skip_protos, out_proto)


def hetero_forward(program: HeteroProgram, mesh, pcfg: ParallelConfig,
                   x_batch):
    """Full pipelined forward: x [B, ...] -> y [B, ...] (last stage out)."""
    pipe = pipeline_call(program.stage_apply, mesh=mesh, cfg=pcfg,
                         skips=program.skips,
                         skip_protos=program.skip_protos,
                         carry_proto=program.carry_proto)
    B = x_batch.shape[0]
    mb = B // pcfg.n_micro
    max_elems = program.carry_proto["buf"].shape[1]
    bufs = stage_lib.pack_buffer({"x": x_batch}, max_elems)
    inputs_mb = microbatch({"buf": bufs}, pcfg.n_micro)
    outs, _ = pipe(program.stacked_params, inputs_mb, None)
    buf = unmicrobatch(last_stage_output(outs))["buf"]
    out_shape = jax.ShapeDtypeStruct((B,) + tuple(program.out_proto.shape[1:]),
                                     program.out_proto.dtype)
    return stage_lib.unpack_buffer(buf, {"x": out_shape})["x"]


def hetero_grad_call(program: HeteroProgram, mesh, pcfg: ParallelConfig,
                     resid_info: Optional[dict] = None):
    """Fused schedule-driven training call for a hetero (switch) program.

    The portal skip edges lower into the unified executor's plan, so the
    U-Net / AmoebaNet pipelines train under any ``pcfg.schedule`` (GPipe or
    1F1B) with the same bitwise-stable gradients as the LM path — including
    ``"zb"`` with ``pcfg.residuals="reuse"`` (pass a dict as ``resid_info``
    to receive the residual-stash geometry at trace time).  Returns
    ``call(stacked_params, x [B, ...], y [B, ...]) -> (loss, grads)``:
    loss is the mean-squared error of the final stage output against ``y``
    and grads mirror ``stacked_params``.
    """
    max_elems = program.carry_proto["buf"].shape[1]
    out_elems = int(np.prod(program.out_proto.shape[1:]))

    def micro_loss(head_ps, carry, largs):
        y = carry["buf"][:, :out_elems]
        return jnp.mean((y - largs["y"]) ** 2)

    pipe_grad, _ = pipeline_grad_call(
        program.stage_apply, mesh=mesh, cfg=pcfg, loss_fn=micro_loss,
        skips=program.skips, skip_protos=program.skip_protos,
        carry_proto=program.carry_proto, resid_info=resid_info)

    def call(stacked_params, x_batch, y_batch):
        bufs = stage_lib.pack_buffer({"x": x_batch}, max_elems)
        inputs_mb = microbatch({"buf": bufs}, pcfg.n_micro)
        y_flat = y_batch.reshape(y_batch.shape[0], -1).astype(jnp.float32)
        labels_mb = microbatch({"y": y_flat}, pcfg.n_micro)
        loss, g_stage, _, _ = pipe_grad(stacked_params, {}, inputs_mb,
                                        labels_mb)
        return loss, g_stage

    return call
