"""GPipe micro-batch pipeline parallelism as a JAX transform (paper §2–3).

The pipeline runs inside a :func:`jax.shard_map` that is *manual* over the
``pipe`` mesh axis and *auto* (GSPMD) over every other axis (``pod``,
``data``, ``tp``): stage ``j``'s parameters live on pipe-rank ``j`` (the
leading axis of the stacked stage parameters is sharded over ``pipe``), while
FSDP/TP/DP sharding inside a stage is delegated to the compiler via
``with_sharding_constraint`` — the paper's "device j holds partition j"
placement, generalized to a 512-chip mesh.

There is ONE execution engine: :func:`run_pipeline_tasks`, a scan over the
static event plan lowered by :mod:`repro.core.plan` from a validated
schedule task table (:mod:`repro.core.schedules`).  The plan is cut into
*segments* — maximal runs of ticks sharing a branch set — and each segment
runs its own scan with the ``lax.switch`` pruned to exactly the branches
that segment uses and the bookkeeping (grad writes, chain permutes, stream
rotation) elided when the segment provably never needs it.
``ParallelConfig.executor`` selects the segment lowering: the ``"spmd"``
reference traces the union branch set with dynamic rank indexing and
eager end-of-tick chain sends, while ``"mpmd"`` dispatches one
*specialized* tick body per rank (static columns, per-rank pruned
branches — ``plan.specialize``'s projection) under a top-level
rank-indexed switch and double-buffers the chain ``ppermute`` one tick
ahead so the hop overlaps the next stage compute; the two are
bitwise-identical.  Each tick, rank
``r`` runs at most one task — NOP (bubble), F, fused B, or the
split-backward pair Bx / Bw — boundary activations move with a
``collective-permute`` ring shift directly into plan-allocated *park* slots
(arrival buffer == activation stash, by donation), skip tensors move on
plan-lowered portal/threaded routes (paper §3.3), resident state (KV
caches) is read and updated on F ticks, and streamed inputs rotate towards
stage 0 on plan-flagged ticks.

Plan families select the backward story:

* **forward-only plans** (``gpipe_fwd``, paper Algorithm 1): the executor
  runs just the forward wavefront and ``jax.grad`` through it yields the
  reverse clock-cycle with rematerialization scheduled immediately before
  each stage backward — the paper's fork/join + Checkpoint/Recompute
  pairing, obtained structurally (DESIGN.md §2).  :func:`run_pipeline` /
  :func:`pipeline_call` are thin wrappers that lower this plan.

* **F+B plans** (``gpipe_tasked`` / ``1f1b`` / ``interleaved:v`` / ``zb``):
  backward tasks execute *inside* the same loop — a backward tick re-reads
  the parked boundary activation (and parked skip operands), recomputes
  the stage forward inside ``jax.vjp``, and ships input / skip cotangents
  down the reverse routes.  That is what lets 1F1B drain backwards early
  and bound the activation stash at ``min(n - j, m)`` instead of ``m``;
  see :func:`pipeline_grad_call`.  With interleaved virtual stages
  (``tplan.n_chunks > 1``) rank ``r`` holds a ``[v, ...]`` parameter block
  and each tick dynamically selects the chunk its task touches; the ring
  shift becomes a full rotation so chunk boundaries (rank n-1 -> rank 0)
  ride the same collective.  Split-backward plans run Bx (input cotangent
  only — the half other stages wait for) on the critical path and fill
  bubble ticks with Bw (weight gradient), re-reading the parked operands
  and the parked output cotangent.
"""
from __future__ import annotations

import contextlib
import functools
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ParallelConfig
from repro.core import checkpointing
from repro.core import plan as plan_lib
from repro.core.plan import BWD, BWD_W, BWD_X, FWD, NOP, pipe_ring_perm
from repro.core.skip import SkipSpec
from repro.runtime.compression import _dequantize_block, _quantize_block
from repro.scopes import (GRAD_REDUCE, PIPE, PIPE_B, PIPE_BW, PIPE_BX, PIPE_F,
                           PIPE_HOP, scoped)

PIPE_AXIS = "pipe"


@dataclass
class TickCtx:
    """Per-tick context handed to the stage function."""
    stage: jax.Array          # GLOBAL stage index (chunk * n_ranks + rank)
    micro: jax.Array          # micro-batch index of this rank's task
    valid: jax.Array          # bool: is this a real (scheduled) task?
    t: Any                    # tick counter (traced in scan mode, int if unrolled)
    fresh: Any                # stage-0 input pytree slice for this tick
    n_stages: int             # GLOBAL stage count (n_ranks * n_chunks)
    n_micro: int


# StageApplyFn signature:
#   stage_apply(stage_params, carry, skips_in: dict, resident, ctx: TickCtx)
#       -> (carry_out, skips_out: dict, resident_out)
StageApplyFn = Callable[..., Tuple[Any, Dict[str, Any], Any]]


def _select(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


@scoped(PIPE_HOP)
def _shift_chain(value, n: int, axis: str, *, ring: bool = False):
    """Main pipeline hop: rank j -> j+1.  ``ring`` adds the wraparound pair
    (n-1 -> 0) that interleaved chunk boundaries ride; without it rank 0
    receives zeros."""
    if n == 1:
        # single rank: the wraparound hop (chunk c -> c+1) is an identity
        return value if ring else jax.tree.map(jnp.zeros_like, value)
    perm = pipe_ring_perm(n, ring=ring)
    return jax.tree.map(lambda v: jax.lax.ppermute(v, axis, perm), value)


@scoped(PIPE_HOP)
def _shift_chain_rev(value, n: int, axis: str, *, ring: bool = False):
    """Backward (cotangent) hop: rank j -> j-1 (+ wraparound 0 -> n-1)."""
    if n == 1:
        return value if ring else jax.tree.map(jnp.zeros_like, value)
    perm = pipe_ring_perm(n, reverse=True, ring=ring)
    return jax.tree.map(lambda v: jax.lax.ppermute(v, axis, perm), value)


@scoped(PIPE_HOP)
def _route_hop(value, perm, axis: str):
    """One skip-route hop: a static (src, dst) pair list ppermute.  An empty
    perm means src and dst share a rank — the hop is an identity hold."""
    if not perm:
        return value
    return jax.tree.map(
        lambda v: jax.lax.ppermute(v, axis, list(perm)), value)


BATCH_AXES = ("pod", "data")

_HINTS = threading.local()


@contextlib.contextmanager
def no_layout_hints():
    """Elide sharding constraints while tracing the body.

    XLA requires every branch of a conditional to agree on its output
    sharding.  A constraint that ends one branch, or that ends its transpose
    (a constraint at the top of a branch becomes the last op of its
    backward), breaks that against a branch returning plain zeros.
    ``layers.constrain`` and :func:`_constrain_batch0` honour this flag."""
    prev = getattr(_HINTS, "off", False)
    _HINTS.off = True
    try:
        yield
    finally:
        _HINTS.off = prev


def layout_hints_enabled() -> bool:
    return not getattr(_HINTS, "off", False)


def _constrain_batch0(tree, *, lead: int = 0):
    """Constrain pytree leaves: batch dim = ``lead`` over (pod, data).

    GSPMD does not reliably propagate the data sharding of the mini-batch
    into the clock-loop carries (state, outputs, per-tick slices) that start
    from jnp.zeros — without these constraints every carry is replicated
    over the data axis and per-device memory blows up by |data|x.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if (not layout_hints_enabled() or mesh.empty
            or not set(BATCH_AXES) <= set(mesh.axis_names)):
        return tree

    nshard = 1
    for ax in BATCH_AXES:
        nshard *= mesh.shape[ax]

    def one(a):
        if a.ndim <= lead or a.shape[lead] % nshard:
            return a
        spec = [None] * a.ndim
        spec[lead] = BATCH_AXES
        return jax.lax.with_sharding_constraint(a, P(*spec))
    return jax.tree.map(one, tree)


def _barrier(*trees):
    """Ablation hook (overlap=False): serialize comm against compute, the
    analogue of torchgpipe's default-stream (no copy-stream) baseline."""
    flat, tds = zip(*[jax.tree_util.tree_flatten(t) for t in trees])
    leaves = [l for f in flat for l in f]
    if not leaves:
        return trees
    out = jax.lax.optimization_barrier(tuple(leaves))
    res, k = [], 0
    for f, td in zip(flat, tds):
        res.append(jax.tree_util.tree_unflatten(td, out[k:k + len(f)]))
        k += len(f)
    return tuple(res)


def _dyn_read(buf_tree, slot):
    s = jnp.maximum(slot, 0)
    return jax.tree.map(
        lambda b: jax.lax.dynamic_index_in_dim(b, s, 0, keepdims=False),
        buf_tree)


def _masked_write(buf_tree, val_tree, slot, pred):
    s = jnp.maximum(slot, 0)

    def upd(b, v):
        cur = jax.lax.dynamic_index_in_dim(b, s, 0, keepdims=False)
        new = jnp.where(pred, v.astype(b.dtype), cur)
        return jax.lax.dynamic_update_index_in_dim(b, new, s, 0)
    return jax.tree.map(upd, buf_tree, val_tree)


def _masked_accum(buf_tree, val_tree, slot, pred):
    """Add ``val`` into row ``slot`` under ``pred`` (chunked grad rows:
    each chunk's backward deposits into its own disjoint sub-row)."""
    s = jnp.maximum(slot, 0)

    def upd(b, v):
        cur = jax.lax.dynamic_index_in_dim(b, s, 0, keepdims=False)
        new = jnp.where(pred, cur + v.astype(b.dtype), cur)
        return jax.lax.dynamic_update_index_in_dim(b, new, s, 0)
    return jax.tree.map(upd, buf_tree, val_tree)


def _zeros_of(proto):
    return jax.tree.map(
        lambda p: jnp.zeros(tuple(p.shape), jnp.dtype(p.dtype)), proto)


def _buf(depth, proto):
    return jax.tree.map(
        lambda c: jnp.zeros((depth,) + c.shape, c.dtype), proto)


def _vjp_split(fn, args, also_live=()):
    """vjp over all of ``args``, with the pullback flattened into leaves.

    ``jax.vjp``'s pullback is a :class:`jax.tree_util.Partial` pytree whose
    leaves are its residuals.  Leaves that are (by tracer identity) the
    live inputs themselves — the primal args, or ``also_live`` values the
    caller can rederive on a later tick (parked activations, labels of the
    same micro, resident state) — need not cross ticks; everything else is
    what the Bx tick must stash for residual reuse.  Returns
    ``(out, vjp_fn, leaves, treedef, stash_mask)`` where ``stash_mask[i]``
    is True for leaves that must be stashed.
    """
    out, vjp_fn = jax.vjp(fn, *args)
    leaves, treedef = jax.tree_util.tree_flatten(vjp_fn)
    live = set(map(id, jax.tree_util.tree_leaves((args, also_live))))
    mask = tuple(id(leaf) not in live for leaf in leaves)
    return out, vjp_fn, leaves, treedef, mask


# ---------------------------------------------------------------------------
# On-the-wire codec (plan.TaskPlan.wire): encode at latch, decode at arrival
# ---------------------------------------------------------------------------

def _float_leaf(p) -> bool:
    return jnp.issubdtype(jnp.dtype(p.dtype), jnp.floating)


class _Codec:
    """One payload class's wire codec, applied leaf-wise over carry trees.

    ``zeros(proto)`` builds the wire-format register/in-flight value the
    scan state holds; ``enc(value, ef, pred)`` encodes at the latch (or the
    SPMD eager send) and — for the stateful ``int8-ef`` codec — folds the
    quantization residual into the error-feedback state only when ``pred``
    says the send is real, keeping the EF sequence identical across
    executors; ``dec(wire, proto)`` reverses it at the arrival tick.
    Non-float leaves (token ids riding a forward-only carry) always pass
    through untouched, so every codec is exact on them.  ``fp32`` is a
    strict identity — wire trees equal value trees bitwise, which is what
    keeps the default mode bit-for-bit against the pre-codec executor.
    """

    def __init__(self, codec: str, block: int):
        self.codec = codec
        self.block = block
        self.stateful = codec == "int8-ef"

    def _q_shapes(self, p):
        n = 1
        for d in p.shape:
            n *= int(d)
        nb = max(-(-n // self.block), 1)
        return n, nb

    def zeros(self, proto):
        if self.codec == "fp32":
            return _zeros_of(proto)
        leaves, td = jax.tree_util.tree_flatten(proto)

        def one(p):
            if not _float_leaf(p):
                return jnp.zeros(tuple(p.shape), jnp.dtype(p.dtype))
            if self.codec == "bf16":
                return jnp.zeros(tuple(p.shape), jnp.bfloat16)
            n, nb = self._q_shapes(p)
            return {"q": jnp.zeros((nb, self.block), jnp.int8),
                    "s": jnp.zeros((nb, 1), jnp.float32)}
        return jax.tree_util.tree_unflatten(td, [one(p) for p in leaves])

    def ef_zeros(self, proto):
        """Error-feedback residual per float leaf (empty where exact)."""
        leaves, td = jax.tree_util.tree_flatten(proto)
        return jax.tree_util.tree_unflatten(
            td, [jnp.zeros(tuple(p.shape), jnp.float32)
                 if self.stateful and _float_leaf(p) else ()
                 for p in leaves])

    def enc(self, value, ef=(), pred=None):
        """value tree -> (wire tree, new ef tree)."""
        if self.codec == "fp32":
            return value, ef
        if self.codec == "bf16":
            return jax.tree.map(
                lambda v: v.astype(jnp.bfloat16) if _float_leaf(v) else v,
                value), ef
        leaves, td = jax.tree_util.tree_flatten(value)
        efs = td.flatten_up_to(ef) if self.stateful else [()] * len(leaves)

        def one(v, e):
            if not _float_leaf(v):
                return v, e
            y = v.astype(jnp.float32) + e
            flat = y.reshape(-1)
            q, s = _quantize_block(flat, self.block)
            deq = _dequantize_block(q, s, flat.shape[0]).reshape(v.shape)
            resid = y - deq
            new_e = jnp.where(pred, resid, e) if pred is not None else resid
            return {"q": q, "s": s}, new_e
        pairs = [one(v, e) for v, e in zip(leaves, efs)]
        wire = jax.tree_util.tree_unflatten(td, [w for w, _ in pairs])
        new_ef = jax.tree_util.tree_unflatten(td, [e for _, e in pairs])
        return wire, new_ef

    def dec(self, wire, proto):
        """wire tree -> value tree (dtype/shape of ``proto``)."""
        if self.codec == "fp32":
            return wire
        leaves_p, td = jax.tree_util.tree_flatten(proto)
        leaves_w = td.flatten_up_to(wire)

        def one(w, p):
            if not _float_leaf(p):
                return w
            if self.codec == "bf16":
                return w.astype(jnp.dtype(p.dtype))
            n, _ = self._q_shapes(p)
            flat = _dequantize_block(w["q"], w["s"], n)
            return flat.reshape(tuple(p.shape)).astype(jnp.dtype(p.dtype))
        return jax.tree_util.tree_unflatten(
            td, [one(w, p) for w, p in zip(leaves_w, leaves_p)])


# ---------------------------------------------------------------------------
# THE schedule executor — the repo's single tick loop
# ---------------------------------------------------------------------------

def run_pipeline_tasks(stage_apply: StageApplyFn,
                       stage_params,
                       inputs_mb,
                       cfg: ParallelConfig,
                       *,
                       tplan: plan_lib.TaskPlan,
                       head_params=None,
                       loss_args_mb=None,
                       loss_fn=None,
                       skip_protos: Optional[Dict[str, Any]] = None,
                       resident=None,
                       carry_proto=None,
                       axis: str = PIPE_AXIS,
                       rank=None,
                       resid_info: Optional[Dict[str, Any]] = None):
    """Execute one event plan (forward-only, or fused F+B) for a mini-batch.

    Forward-only plans (``tplan.has_backward == False``) return
    ``(outputs, resident)``: outputs is the ``[m, ...carry]`` collection at
    the last rank (autodiff through this call induces the reverse
    clock-cycle).  F+B plans return ``(loss_sum, stage_grads, head_grads,
    input_grads_mb, resident)``: a backward tick re-reads the parked
    boundary activation and skip operands, recomputes the stage forward
    inside ``jax.vjp`` (the paper's Checkpoint/Recompute pairing, now
    structural), and ships carry / skip cotangents down the reverse
    routes.  Fused B ticks produce input and weight cotangents together;
    split plans run Bx (inputs only) on the critical path and Bw (weights
    only) in former bubble ticks, re-seeding the weight VJP from the
    still-parked output cotangent.

    Split plans lowered with ``residuals="reuse"`` (true ZB-H1) change the
    Bw story: the Bx tick vjp's the remat-policy-wrapped stage over ALL
    arguments, ships the input cotangents, and *stashes* the pullback's
    residual leaves (minus the ones rederivable from live state — parked
    inputs, params, labels) into the plan-allocated residual slot; the Bw
    tick rebuilds the pullback around the stashed leaves, so its local
    forward recompute is dead code XLA eliminates — Bw costs one forward
    of work (the weight-grad half) instead of two.  ``cfg.remat`` decides
    what the pullback saves and hence what is stashed
    (:mod:`repro.core.checkpointing`).  Pass a dict as ``resid_info`` to
    receive the stash geometry (leaf shapes, bytes per slot) observed at
    trace time.

    With interleaved plans (``tplan.n_chunks > 1``), ``stage_params``
    leaves carry a leading ``[n_chunks]`` axis — rank ``r`` holds global
    stages ``{r, r + R, ...}`` — and each task dynamically selects its
    chunk; returned ``stage_grads`` mirror the ``[n_chunks, ...]`` block.

    The plan's segments drive one scan each: a GPipe fill runs a pure-F
    loop with no gradient bookkeeping at all, the 1F1B steady state runs
    the mixed F/B loop, and a ZB drain runs Bw-only ticks — the
    ``lax.switch`` in each segment contains exactly the branches that
    segment uses.

    ``cfg.executor`` picks the lowering of each segment:

    * ``"spmd"`` (reference): every rank traces the segment's UNION
      branch set, gathers its plan columns with a dynamic ``[axis_index]``
      read, and ships its boundary output eagerly at the end of each tick
      (compute -> send serialized).
    * ``"mpmd"``: a top-level rank-indexed ``lax.switch`` dispatches one
      specialized tick body per rank — static column reads, branch sets
      pruned to exactly the kinds that rank's column contains in the
      segment (``plan.specialize``'s projection; a rank that is all-F in
      a window runs branch-free code), buffer writes elided where that
      rank's columns prove them dead — and the chain ``ppermute`` is
      double-buffered: a tick's boundary output latches into a send
      register (``plan.send_slot``) and ships at the TOP of the next
      tick, so the hop has no data dependency on that tick's compute and
      overlaps it (``optimization_barrier`` pins the grouping).  The
      collective skeleton stays rank-uniform outside the switch —
      collectives inside per-rank branches would deadlock a real device
      group — and one SPMD executable still allocates ring-max buffers;
      the per-rank programs *declare* their true footprint
      (``plan.specialize(tplan, r).buffer_slots()``), which bench/dryrun
      report.  Identical values flow on identical ticks, so both
      executors are bitwise-identical in loss and gradients.

    Losses accumulate in ascending micro order on the last stage
    (identical in every schedule) and parameter cotangents are collected
    per-micro and reduced in a fixed order (``cfg.grad_reduce ==
    "ordered"``), so any two schedules of the same computation produce
    bitwise-identical losses and gradients.  ``grad_reduce == "running"``
    instead folds cotangents in schedule order — O(1) extra memory, but
    bit-exact only against itself.
    """
    R, m = cfg.pipe, cfg.n_micro
    assert tplan.n_ranks == R and tplan.n_micro == m
    v = tplan.n_chunks
    chunked = v > 1
    fb = tplan.has_backward
    if rank is not None:
        idx = rank
    else:
        idx = jax.lax.axis_index(axis) if R > 1 else jnp.zeros((), jnp.int32)
    skip_protos = skip_protos or {}
    resident = {} if resident is None else resident
    routes = tplan.routes
    skip_names = tuple(dict.fromkeys(rt.name for rt in routes))
    for name in skip_names:
        if name not in skip_protos:
            raise ValueError(f"skip edge {name!r} has no proto")
    streaming = cfg.stream_inputs and R > 1
    k_stream = m // R if streaming else 0
    mpmd = cfg.executor == "mpmd"

    # on-the-wire codec per payload class (plan.TaskPlan.wire): chain
    # carries, portal/skip route values, and backward cotangents (chain +
    # mirrored route cotangents) each pick fp32 | bf16 | int8-ef.
    wire_spec = tplan.wire
    cdc_id = _Codec("fp32", wire_spec.block)
    cdc_chain = _Codec(wire_spec.chain, wire_spec.block)
    cdc_portal = _Codec(wire_spec.portal, wire_spec.block)
    cdc_cot = _Codec(wire_spec.cotangent, wire_spec.block)
    if R == 1:
        # single-rank pipelines have no chain wire: the "hop" is an
        # identity hold, never lossified
        cdc_chain = cdc_cot = cdc_id
    # route payloads: the codec applies only where the hop actually
    # crosses a wire (non-empty permute); same-rank holds stay exact
    rt_vc = {rt.key: (cdc_portal if rt.fwd_perm else cdc_id)
             for rt in routes}
    rt_gc = {rt.key: (cdc_cot if rt.bwd_perm else cdc_id)
             for rt in routes}
    wire_stateful = (cdc_chain.stateful or cdc_cot.stateful
                     or any(c.stateful for c in rt_vc.values())
                     or any(c.stateful for c in rt_gc.values()))

    if fb:
        if loss_fn is None:
            raise ValueError("F+B plans need a loss_fn")
        if cfg.grad_reduce not in ("ordered", "running"):
            raise ValueError(f"unknown grad_reduce {cfg.grad_reduce!r}; "
                             "want 'ordered' or 'running'")
        ordered = cfg.grad_reduce == "ordered"
        seed = jnp.asarray(1.0 / m, jnp.float32)

    if carry_proto is None:
        carry0 = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype),
                              inputs_mb)
    else:
        carry0 = _zeros_of(carry_proto)
    fresh0 = jax.tree.map(lambda a: jnp.zeros(a.shape[1:], a.dtype),
                          inputs_mb)
    is_last_rank = idx == R - 1

    def chunk_params(p_all, c):
        if not chunked:
            return p_all
        return jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, c, 0, keepdims=False),
            p_all)

    # ---- scan state (identical pytree across all segment scans) -----------
    # Chain registers and route registers/in-flight values live in WIRE
    # format (the fp32 codec's wire format IS the value format): MPMD route
    # payloads latch into "snd"/"gsnd" registers shipped at the top of the
    # next tick (double-buffered like the chain carry); SPMD keeps the
    # eager end-of-tick "fly"/"gfly" in-flight slots.
    route_reg = "snd" if mpmd else "fly"
    g_route_reg = "gsnd" if mpmd else "gfly"
    st = {
        "f_chain": cdc_chain.zeros(carry0),
        "park": _buf(max(tplan.park_depth, 1), carry0),
        "resident": resident,
        "routes": {rt.key: {"buf": _buf(rt.depth, skip_protos[rt.name]),
                            route_reg: rt_vc[rt.key].zeros(
                                skip_protos[rt.name])}
                   for rt in routes},
    }
    if streaming:
        st["stream"] = inputs_mb
    if fb:
        st["b_chain"] = cdc_cot.zeros(carry0)
        st["b_inbox"] = _buf(tplan.b_inbox_depth, carry0)
        st["loss"] = jnp.zeros((), jnp.float32)
        st["g_stage"] = (_buf(m, stage_params) if ordered
                         else jax.tree.map(jnp.zeros_like, stage_params))
        st["g_head"] = (_buf(m, head_params) if ordered
                        else jax.tree.map(jnp.zeros_like, head_params))
        st["igbuf"] = _buf(m, fresh0)
        if streaming:
            st["fs"] = _buf(tplan.fs_depth, fresh0)
        for rt in routes:
            st["routes"][rt.key]["gbuf"] = _buf(rt.g_depth,
                                                skip_protos[rt.name])
            st["routes"][rt.key][g_route_reg] = rt_gc[rt.key].zeros(
                skip_protos[rt.name])
    if wire_stateful:
        # per-(rank, stream) error-feedback state for int8-ef classes; the
        # residual of each real send folds into the next payload of the
        # same stream (chain, backward chain, each route's value /
        # cotangent flow)
        wef: Dict[str, Any] = {}
        if cdc_chain.stateful:
            wef["f"] = cdc_chain.ef_zeros(carry0)
        if fb and cdc_cot.stateful:
            wef["b"] = cdc_cot.ef_zeros(carry0)
        for rt in routes:
            if rt_vc[rt.key].stateful:
                wef["r:" + rt.key] = rt_vc[rt.key].ef_zeros(
                    skip_protos[rt.name])
            if fb and rt_gc[rt.key].stateful:
                wef["g:" + rt.key] = rt_gc[rt.key].ef_zeros(
                    skip_protos[rt.name])
        st["wef"] = wef
    if not fb:
        st["outputs"] = _buf(m, carry0)
        # the stream shard's batch dim is also at 1 ([k, mb, ...]), so one
        # constraint covers both input modes before slicing / rotating.
        inputs_mb = _constrain_batch0(inputs_mb, lead=1)
        if streaming:
            st["stream"] = inputs_mb

    def normalize_skips(skips_out):
        """Stage skips_out -> exactly the declared names (protos' dtypes)."""
        out = {}
        for name in skip_names:
            proto = skip_protos[name]
            if skips_out and name in skips_out:
                out[name] = jax.tree.map(
                    lambda v, p: v.astype(p.dtype), skips_out[name], proto)
            else:
                out[name] = _zeros_of(proto)
        return out

    def zeros_skips():
        return {name: _zeros_of(skip_protos[name]) for name in skip_names}

    # ---- residual reuse (ZB-H1): probe the stash geometry ----------------
    reuse = fb and tplan.residuals == "reuse"
    stash_mask: Tuple[bool, ...] = ()
    stash_protos: list = []
    if resid_info is not None and not reuse:
        resid_info.update(residuals="recompute", resid_depth=0,
                          per_stage_resid=[], resid_leaves=[],
                          resid_bytes_per_slot=0)

    def stage_core(p_all, c, si, fr, ph,
                   micro_t, chunk_t, t, is_last_stage, resident_t, largs_t):
        """THE stage+loss body every F+B tick runs — forward ticks, fused
        backwards, and both split-backward halves differentiate exactly
        this one definition (``apply_full`` and ``make_full_f`` are thin
        adapters), so the reuse path can never drift from the forward."""
        p = chunk_params(p_all, chunk_t)
        gstage = chunk_t * R + idx if chunked else idx
        ctx = TickCtx(stage=gstage, micro=micro_t,
                      valid=jnp.asarray(True), t=t, fresh=fr,
                      n_stages=tplan.n_stages, n_micro=m)
        carry_out, skips_out, res_new = stage_apply(p, c, si, resident_t, ctx)
        if not cfg.overlap:
            (carry_out,), = (_barrier(carry_out),)
        with no_layout_hints():
            loss_i = jax.lax.cond(
                is_last_stage,
                lambda: loss_fn(ph, carry_out, largs_t).astype(jnp.float32),
                lambda: jnp.zeros((), jnp.float32))
        return carry_out, normalize_skips(skips_out), loss_i, res_new

    def make_full_f(micro_t, chunk_t, t, is_last_stage, resident_t, largs_t):
        """The function split-backward ticks differentiate: identical
        structure for the Bx tick (input half + residual stash), the Bw
        tick (weight half from stashed residuals), and the setup probe
        below — all three traces must produce the same pullback leaf
        list, which the in-branch ``stash_mask`` asserts.
        """
        def f(p_all, c, si, fr, ph):
            carry_out, skips, loss_i, _ = stage_core(
                p_all, c, si, fr, ph,
                micro_t, chunk_t, t, is_last_stage, resident_t, largs_t)
            return carry_out, skips, loss_i
        return checkpointing.wrap_for_residuals(
            f, cfg.remat, "reuse" if reuse else "recompute")

    if reuse:
        largs_proto = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape[1:]), a.dtype),
            loss_args_mb)
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        bool_ = jax.ShapeDtypeStruct((), jnp.bool_)
        probe_out = {}

        def probe(p_all, c, si, fr, ph, res_t, la, mi, ch, tt, last):
            f = make_full_f(mi, ch, tt, last, res_t, la)
            _, _, leaves, _, mask = _vjp_split(
                f, (p_all, c, si, fr, ph),
                also_live=(res_t, la, mi, ch, tt, last, idx))
            probe_out["mask"] = mask
            return [l for l, keep in zip(leaves, mask) if keep]

        stash_protos = list(jax.eval_shape(
            probe, stage_params, carry0, zeros_skips(), fresh0, head_params,
            resident, largs_proto, i32, i32, i32, bool_))
        stash_mask = probe_out["mask"]
        if resid_info is not None:
            resid_info.update(
                residuals="reuse", remat=cfg.remat,
                resid_depth=tplan.resid_depth,
                per_stage_resid=list(tplan.per_stage_resid),
                resid_leaves=[(tuple(p.shape), str(jnp.dtype(p.dtype)))
                              for p in stash_protos],
                resid_bytes_per_slot=sum(
                    int(np.prod(p.shape)) * jnp.dtype(p.dtype).itemsize
                    for p in stash_protos))
        if stash_protos:
            st["resid"] = [
                jnp.zeros((max(tplan.resid_depth, 1),) + tuple(p.shape),
                          jnp.dtype(p.dtype)) for p in stash_protos]
    has_stash = bool(stash_protos)

    # ---- per-segment scan bodies -----------------------------------------
    # Both executors share one tick body (`rank_tick`): the SPMD reference
    # path calls it once with dynamic rank indexing and the segment's UNION
    # branch set; the MPMD path dispatches R specialized instances — static
    # column reads, per-rank pruned branch sets and buffer-write elision —
    # under a single top-level rank-indexed lax.switch.  Collectives (chain
    # permutes, route hops, stream rotation) always stay in the rank-uniform
    # skeleton OUTSIDE that switch: a collective inside a per-rank branch
    # would deadlock a real device group.
    # global ship mask: tick t's skeleton permute carries the latches
    # written at t-1 (MPMD double buffering, see plan.py)
    ship_f_tick = np.zeros(tplan.n_ticks, bool)
    ship_b_tick = np.zeros(tplan.n_ticks, bool)
    ship_f_tick[1:] = (tplan.send_slot[:-1] >= 0).any(axis=1)
    ship_b_tick[1:] = (tplan.b_send_slot[:-1] >= 0).any(axis=1)
    route_name_of = {rt.key: rt.name for rt in routes}

    def make_segment(seg: plan_lib.Segment):
        sl = slice(seg.start, seg.stop)
        kinds = seg.kinds
        has_f = FWD in kinds
        has_bi = any(k in kinds for k in plan_lib.BWD_INPUT_KINDS)
        has_bw = any(k in kinds for k in plan_lib.BWD_WEIGHT_KINDS)
        need_park = bool((tplan.park_recv[sl] >= 0).any())
        need_bseed = fb and bool((tplan.b_read[sl] >= 0).any())
        need_brecv = fb and bool((tplan.b_recv[sl] >= 0).any())
        need_rot = streaming and bool(tplan.stream_rot[sl].any())
        need_x = bool((tplan.park_read[sl] >= 0).any())
        has_rx = reuse and has_stash and BWD_X in kinds
        need_rw = has_rx and bool((tplan.resid_write[sl] >= 0).any())
        need_rd = reuse and has_stash \
            and bool((tplan.resid_read[sl] >= 0).any())
        # MPMD: does any tick of this segment ship a latched chain value?
        # (an arrival implies a ship one tick earlier, so need_park /
        # need_brecv can never outrun these)
        need_ship_f = mpmd and bool(ship_f_tick[sl].any())
        need_ship_b = mpmd and fb and bool(ship_b_tick[sl].any())
        # per-route ship masks (MPMD latched routes) and arrival flags —
        # a route arrival in a segment implies a ship tick in the same
        # segment (the latch is always exactly one tick earlier)
        rship = {rt.key: mpmd and bool(rt.ship[sl].any()) for rt in routes}
        rgship = {rt.key: mpmd and fb and bool(rt.g_ship[sl].any())
                  for rt in routes}
        seg_recv = {rt.key: bool((rt.recv[sl] >= 0).any()) for rt in routes}
        seg_grecv = {rt.key: fb and bool((rt.g_recv[sl] >= 0).any())
                     for rt in routes}
        if mpmd:
            assert not need_park or need_ship_f
            assert not need_brecv or need_ship_b
            for rt in routes:
                assert not seg_recv[rt.key] or rship[rt.key], \
                    f"route {rt.key}: arrival without a same-segment ship"
                assert not seg_grecv[rt.key] or rgship[rt.key], \
                    f"route {rt.key}: g arrival without a same-segment ship"

        # per-rank specialization tables (MPMD): rank r's branch set over
        # this segment is EXACTLY the kinds its column contains here
        if mpmd:
            rank_kinds = tuple(
                tuple(sorted(set(int(k) for k in tplan.kind[sl, r])))
                for r in range(R))
        else:
            rank_kinds = (kinds,) * R

        # branch-index remap: plan kind id -> position in the executing
        # branch set (per rank under MPMD, the union set under SPMD)
        sel = tplan.kind[sl].copy()
        for r in range(R):
            remap_r = {k: i for i, k in enumerate(rank_kinds[r])}
            for k, i in remap_r.items():
                sel[tplan.kind[sl, r] == k, r] = i

        xs = {
            "t": jnp.arange(seg.start, seg.stop),
            "sel": jnp.asarray(sel),
            "micro": jnp.asarray(tplan.micro[sl]),
            "chunk": jnp.asarray(tplan.chunk[sl]),
            "prd": jnp.asarray(tplan.park_read[sl]),
        }
        if need_park:
            xs["prs"] = jnp.asarray(tplan.park_recv[sl])
        if need_bseed:
            xs["brd"] = jnp.asarray(tplan.b_read[sl])
        if need_brecv:
            xs["brs"] = jnp.asarray(tplan.b_recv[sl])
        if need_rw:
            xs["rw"] = jnp.asarray(tplan.resid_write[sl])
        if need_rd:
            xs["rd"] = jnp.asarray(tplan.resid_read[sl])
        # "snd"/"bsnd" drive the MPMD latches — and, under a stateful
        # chain/cotangent codec, the SPMD eager sends' EF gating (the EF
        # update must key on the same real-send predicate in both
        # executors to keep them bitwise-identical in lossy modes)
        if (mpmd or cdc_chain.stateful) and has_f:
            xs["snd"] = jnp.asarray(tplan.send_slot[sl])
        if (mpmd or cdc_cot.stateful) and fb and has_bi:
            xs["bsnd"] = jnp.asarray(tplan.b_send_slot[sl])
        if streaming:
            xs["ssl"] = jnp.asarray(tplan.stream_slot[sl])
            xs["rot"] = jnp.asarray(tplan.stream_rot[sl])
            if fb:
                xs["fsl"] = jnp.asarray(tplan.fs_slot[sl])
        rxs = {}
        for rt in routes:
            e = {}
            for nm, arr in (("send", rt.send), ("recv", rt.recv),
                            ("read", rt.read)):
                if (arr[sl] >= 0).any() or (nm == "send"
                                            and (arr[sl] != -1).any()):
                    e[nm] = jnp.asarray(arr[sl])
            if fb:
                for nm, arr in (("g_send", rt.g_send), ("g_recv", rt.g_recv),
                                ("g_read", rt.g_read)):
                    if (arr[sl] >= 0).any() or (nm == "g_send"
                                                and (arr[sl] != -1).any()):
                        e[nm] = jnp.asarray(arr[sl])
            rxs[rt.key] = e
        if rxs and any(rxs.values()):
            xs["routes"] = rxs

        def rank_tick(r, st, xt, arr_f, arr_b, arr_rt, arr_grt):
            """One rank's tick: arrivals -> operands -> task -> commit.

            ``r is None`` is the SPMD reference instance: dynamic
            ``[idx]`` column reads and the segment's union branch set.  A
            static ``r`` is rank r's MPMD specialization: static column
            reads, branch set pruned to exactly the kinds rank r runs in
            this segment (a single kind dispatches with no switch at
            all), and buffer writes elided when rank r's columns prove
            them dead.  ``arr_f`` / ``arr_b`` are this tick's chain
            arrivals (SPMD: the value permuted at the end of last tick;
            MPMD: the latch register shipped at the top of this one);
            ``arr_rt`` / ``arr_grt`` are the route value / cotangent
            arrivals keyed by route, already wire-decoded by the
            skeleton.  Returns ``(out_state, extras)`` with ``extras``
            rank-uniform.
            """
            static = r is not None

            def col(a):
                return a[r] if static else a[idx]

            if static:
                kinds_r = rank_kinds[r]
                csl = (sl, r)
                r_park = need_park and bool(
                    (tplan.park_recv[csl] >= 0).any())
                r_bseed = need_bseed and bool((tplan.b_read[csl] >= 0).any())
                r_brecv = need_brecv and bool((tplan.b_recv[csl] >= 0).any())
                r_x = need_x and bool((tplan.park_read[csl] >= 0).any())
                r_rx = reuse and has_stash and BWD_X in kinds_r
                r_rw = need_rw and bool((tplan.resid_write[csl] >= 0).any())
                r_rd = need_rd and bool((tplan.resid_read[csl] >= 0).any())
                r_latch_f = has_f and bool((tplan.send_slot[csl] >= 0).any())
                r_latch_b = fb and has_bi and bool(
                    (tplan.b_send_slot[csl] >= 0).any())
            else:
                kinds_r = kinds
                r_park, r_bseed, r_brecv = need_park, need_bseed, need_brecv
                r_x, r_rx, r_rw, r_rd = need_x, has_rx, need_rw, need_rd
                r_latch_f = r_latch_b = False
            r_f = FWD in kinds_r
            r_bi = any(k in kinds_r for k in plan_lib.BWD_INPUT_KINDS)
            r_bw = any(k in kinds_r for k in plan_lib.BWD_WEIGHT_KINDS)
            r_b = any(k in kinds_r for k in plan_lib.BWD_KINDS)
            remap = {k: i for i, k in enumerate(kinds_r)}

            t = xt["t"]
            sel_t = col(xt["sel"])
            micro_t = col(xt["micro"])
            chunk_t = col(xt["chunk"])
            prd = col(xt["prd"])
            is_last_stage = (is_last_rank & (chunk_t == v - 1) if chunked
                             else is_last_rank)

            # 1. park ring / route arrivals in their plan-assigned slots
            park = st["park"]
            if r_park:
                prs = col(xt["prs"])
                park = _masked_write(park, arr_f, prs, prs >= 0)
            rst = {}
            for rt in routes:
                rx = xt.get("routes", {}).get(rt.key, {})
                rs = st["routes"][rt.key]
                entry = {"buf": rs["buf"], route_reg: rs[route_reg]}
                if "recv" in rx:
                    rc = col(rx["recv"])
                    entry["buf"] = _masked_write(rs["buf"],
                                                 arr_rt[rt.key], rc,
                                                 rc >= 0)
                if fb:
                    entry["gbuf"] = rs["gbuf"]
                    entry[g_route_reg] = rs[g_route_reg]
                    if "g_recv" in rx:
                        grc = col(rx["g_recv"])
                        entry["gbuf"] = _masked_write(rs["gbuf"],
                                                      arr_grt[rt.key],
                                                      grc, grc >= 0)
                rst[rt.key] = entry
            b_inbox = st.get("b_inbox")
            if r_brecv:
                brs = col(xt["brs"])
                b_inbox = _masked_write(b_inbox, arr_b, brs, brs >= 0)

            # 2. gather this tick's operands
            if r_x:
                x_f = _select(prd >= 0, _dyn_read(park, prd),
                              _zeros_of(carry0))
            else:
                x_f = _zeros_of(carry0)
            if not fb:
                x_f = _constrain_batch0(x_f)
            skips_in = zeros_skips()
            for rt in routes:
                rx = xt.get("routes", {}).get(rt.key, {})
                if "read" in rx:
                    rd = col(rx["read"])
                    skips_in[rt.name] = _select(
                        rd >= 0, _dyn_read(rst[rt.key]["buf"], rd),
                        skips_in[rt.name])
            if streaming:
                ssl = jnp.clip(xt["ssl"], 0, max(k_stream - 1, 0))
                fresh_f = _dyn_read(st["stream"], ssl)
            else:
                fresh_f = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, micro_t, 0, keepdims=False), inputs_mb)
                if not fb:
                    fresh_f = _constrain_batch0(fresh_f)
            resident = st["resident"]

            if fb:
                if r_bseed:
                    brd = col(xt["brd"])
                    bseed = _select(brd >= 0, _dyn_read(b_inbox, brd),
                                    _zeros_of(carry0))
                else:
                    bseed = _zeros_of(carry0)
                if streaming and r_b:
                    fsl = col(xt["fsl"])
                    fresh_b = _dyn_read(st["fs"], fsl)
                else:
                    fresh_b = fresh_f
                largs = jax.tree.map(
                    lambda a: jax.lax.dynamic_index_in_dim(
                        a, micro_t, 0, keepdims=False), loss_args_mb)
                skip_seeds = zeros_skips()
                for rt in routes:
                    rx = xt.get("routes", {}).get(rt.key, {})
                    if "g_read" in rx:
                        gr = col(rx["g_read"])
                        add = _select(gr >= 0,
                                      _dyn_read(rst[rt.key]["gbuf"], gr),
                                      _zeros_of(skip_protos[rt.name]))
                        skip_seeds[rt.name] = jax.tree.map(
                            jnp.add, skip_seeds[rt.name], add)
                if r_rd:
                    rd = col(xt["rd"])
                    resid_in = [
                        _select(rd >= 0, _dyn_read(bufl, rd),
                                jnp.zeros(bufl.shape[1:], bufl.dtype))
                        for bufl in st["resid"]]
                else:
                    # a coalesced segment may carry the BWD_W branch without
                    # any Bw tick in its slice: the branch still traces, so
                    # feed it (dead) zeros of the stash leaves
                    resid_in = [jnp.zeros(tuple(p.shape), jnp.dtype(p.dtype))
                                for p in stash_protos]

            # 3. run exactly one task (XLA conditional: no masked work)
            if fb:
                def apply_full(p_all, c, si, fr, ph):
                    return stage_core(p_all, c, si, fr, ph,
                                      micro_t, chunk_t, t, is_last_stage,
                                      resident, largs)

                def out_zeros():
                    o = {"res": resident}
                    if r_f:
                        o["carry"] = _zeros_of(carry0)
                        o["skips"] = zeros_skips()
                        o["loss"] = jnp.zeros((), jnp.float32)
                    if r_bi:
                        o["b"] = _zeros_of(carry0)
                        o["gskips"] = zeros_skips()
                        o["g_fr"] = _zeros_of(fresh0)
                    if r_bw:
                        o["g_p"] = jax.tree.map(jnp.zeros_like, stage_params)
                        o["g_ph"] = jax.tree.map(jnp.zeros_like, head_params)
                    if r_rx:
                        o["resid"] = [jnp.zeros(tuple(p.shape),
                                                jnp.dtype(p.dtype))
                                      for p in stash_protos]
                    return o

                def seeds_tuple():
                    loss_bar = jnp.where(is_last_stage, seed,
                                         0.0).astype(jnp.float32)
                    return bseed, skip_seeds, loss_bar

                def nop_branch():
                    return out_zeros()

                @scoped(PIPE_F)
                def f_branch():
                    carry_out, skip_vals, loss_i, res_new = apply_full(
                        stage_params, x_f, skips_in, fresh_f, head_params)
                    o = out_zeros()
                    o.update(carry=carry_out, skips=skip_vals, loss=loss_i,
                             res=res_new)
                    return o

                @scoped(PIPE_B)
                def b_branch():
                    def f(p, c, si, fr, ph):
                        carry_out, skip_vals, loss_i, _ = apply_full(
                            p, c, si, fr, ph)
                        return carry_out, skip_vals, loss_i
                    # jax.vjp recomputes the stage forward from the parked
                    # boundary input + parked skip operands and applies the
                    # cotangents immediately — remat-before-backward with no
                    # residuals carried across ticks.
                    _, vjp = jax.vjp(f, stage_params, x_f, skips_in, fresh_b,
                                     head_params)
                    g_p, g_c, g_si, g_fr, g_ph = vjp(seeds_tuple())
                    o = out_zeros()
                    o.update(b=g_c, gskips=g_si, g_fr=g_fr, g_p=g_p,
                             g_ph=g_ph)
                    return o

                @scoped(PIPE_BX)
                def bx_branch():
                    def f(c, si, fr):
                        carry_out, skip_vals, loss_i, _ = apply_full(
                            stage_params, c, si, fr, head_params)
                        return carry_out, skip_vals, loss_i
                    # input-cotangent half only: weight-gradient chains are
                    # dead code here and XLA eliminates them.
                    _, vjp = jax.vjp(f, x_f, skips_in, fresh_b)
                    g_c, g_si, g_fr = vjp(seeds_tuple())
                    o = out_zeros()
                    o.update(b=g_c, gskips=g_si, g_fr=g_fr)
                    return o

                @scoped(PIPE_BW)
                def bw_branch():
                    def f(p, ph):
                        carry_out, skip_vals, loss_i, _ = apply_full(
                            p, x_f, skips_in, fresh_b, ph)
                        return carry_out, skip_vals, loss_i
                    # weight-gradient half, re-seeded from the parked output
                    # cotangent; input chains are dead code.
                    _, vjp = jax.vjp(f, stage_params, head_params)
                    g_p, g_ph = vjp(seeds_tuple())
                    o = out_zeros()
                    o.update(g_p=g_p, g_ph=g_ph)
                    return o

                if reuse:
                    # True ZB-H1 residual reuse: Bx vjp's the policy-wrapped
                    # stage over ALL args, ships the input cotangents and
                    # stashes the pullback's non-rederivable leaves; Bw
                    # rebuilds the pullback around the stashed leaves, so
                    # its own recompute is dead code XLA eliminates.
                    full_args = (stage_params, x_f, skips_in, fresh_b,
                                 head_params)

                    # rederivable-at-Bw values (same micro, same rank, the
                    # tick scalars, labels, resident state) are excluded
                    # from the stash: the Bw tick substitutes its own live
                    # copies, exactly as recompute-mode semantics would.
                    rederivable = (resident, largs, micro_t, chunk_t, t,
                                   is_last_stage, idx)

                    @scoped(PIPE_BX)
                    def bx_branch():
                        f = make_full_f(micro_t, chunk_t, t, is_last_stage,
                                        resident, largs)
                        _, vjp_fn, leaves, _, mask = _vjp_split(
                            f, full_args, also_live=rederivable)
                        assert mask == stash_mask, \
                            "Bx residual structure diverged from the probe"
                        _, g_c, g_si, g_fr, _ = vjp_fn(seeds_tuple())
                        o = out_zeros()
                        o.update(b=g_c, gskips=g_si, g_fr=g_fr)
                        if r_rx:
                            o["resid"] = [l for l, keep in zip(leaves, mask)
                                          if keep]
                        return o

                    @scoped(PIPE_BW)
                    def bw_branch():
                        f = make_full_f(micro_t, chunk_t, t, is_last_stage,
                                        resident, largs)
                        _, _, leaves, treedef, mask = _vjp_split(
                            f, full_args, also_live=rederivable)
                        assert mask == stash_mask, \
                            "Bw residual structure diverged from the probe"
                        it = iter(resid_in)
                        merged = [next(it) if keep else leaf
                                  for leaf, keep in zip(leaves, mask)]
                        vjp2 = jax.tree_util.tree_unflatten(treedef, merged)
                        g_p, _, _, _, g_ph = vjp2(seeds_tuple())
                        o = out_zeros()
                        o.update(g_p=g_p, g_ph=g_ph)
                        return o

                branch_of = {NOP: nop_branch, FWD: f_branch, BWD: b_branch,
                             BWD_X: bx_branch, BWD_W: bw_branch}
                branches = tuple(branch_of[k] for k in kinds_r)
                if len(branches) == 1 and kinds_r[0] in plan_lib.BWD_KINDS:
                    # A lone backward task still runs as a conditional arm,
                    # behind an index XLA cannot constant-fold: inlined, its
                    # body fuses differently and its gradients change in the
                    # last bits, breaking the bitwise equality of schedules
                    # whose segments cut differently.
                    res = jax.lax.switch(
                        jax.lax.optimization_barrier(sel_t) + 1,
                        (nop_branch,) + branches)
                else:
                    res = (branches[0]() if len(branches) == 1
                           else jax.lax.switch(sel_t, branches))
            else:
                ctx = TickCtx(stage=idx, micro=micro_t, valid=sel_t
                              == remap.get(FWD, -1), t=t, fresh=fresh_f,
                              n_stages=tplan.n_stages, n_micro=m)
                wrapped = checkpointing.wrap_stage(
                    lambda p, c, si, rr: stage_apply(p, c, si, rr, ctx),
                    cfg.remat)

                def nop_branch():
                    return {"carry": _zeros_of(carry0),
                            "skips": zeros_skips(), "res": resident}

                def f_branch():
                    carry_out, skips_out, res_new = wrapped(
                        stage_params, x_f, skips_in, resident)
                    if not cfg.overlap:
                        (carry_out,), = (_barrier(carry_out),)
                    return {"carry": carry_out,
                            "skips": normalize_skips(skips_out),
                            "res": res_new}

                branch_of = {NOP: nop_branch, FWD: f_branch}
                branches = tuple(branch_of[k] for k in kinds_r)
                res = (branches[0]() if len(branches) == 1
                       else jax.lax.switch(sel_t, branches))
                # constrained after the switch: XLA requires every branch
                # of a conditional to agree on its output sharding
                res["carry"] = _constrain_batch0(res["carry"])

            # 4. commit state
            out = dict(st)
            out["park"] = park
            out["resident"] = res["res"]
            wef = dict(st["wef"]) if wire_stateful else None
            is_f = sel_t == remap.get(FWD, -1) if r_f else None
            if fb:
                if r_f:
                    out["loss"] = st["loss"] + res["loss"]
                    if streaming:
                        fsl = col(xt["fsl"])
                        out["fs"] = _masked_write(st["fs"], fresh_f, fsl,
                                                  is_f & (fsl >= 0))
                if r_bw:
                    w_sels = [remap[k] for k in plan_lib.BWD_WEIGHT_KINDS
                              if k in remap]
                    is_w = functools.reduce(
                        jnp.logical_or, [sel_t == s for s in w_sels])
                    if ordered:
                        wr = _masked_accum if chunked else _masked_write
                        out["g_stage"] = wr(st["g_stage"], res["g_p"],
                                            micro_t, is_w)
                        head_pred = is_w & is_last_stage
                        out["g_head"] = _masked_write(st["g_head"],
                                                      res["g_ph"], micro_t,
                                                      head_pred)
                    else:
                        out["g_stage"] = jax.tree.map(jnp.add, st["g_stage"],
                                                      res["g_p"])
                        out["g_head"] = jax.tree.map(jnp.add, st["g_head"],
                                                     res["g_ph"])
                if r_rw:
                    rw = col(xt["rw"])
                    is_x = sel_t == remap[BWD_X]
                    out["resid"] = _masked_write(st["resid"], res["resid"],
                                                 rw, is_x & (rw >= 0))
                if r_bi:
                    bi_sels = [remap[k] for k in plan_lib.BWD_INPUT_KINDS
                               if k in remap]
                    is_bi = functools.reduce(
                        jnp.logical_or, [sel_t == s for s in bi_sels])
                    ig_pred = is_bi & (idx == 0)
                    if chunked:
                        ig_pred = ig_pred & (chunk_t == 0)
                    out["igbuf"] = _masked_write(st["igbuf"], res["g_fr"],
                                                 micro_t, ig_pred)
                    out["b_inbox"] = b_inbox
                    if r_latch_b:
                        # MPMD: encode + latch the input cotangent into the
                        # send register; the NEXT tick's skeleton ships it.
                        bsnd = col(xt["bsnd"])
                        wire_b, ef2 = cdc_cot.enc(
                            res["b"],
                            wef["b"] if cdc_cot.stateful else (),
                            bsnd >= 0)
                        out["b_chain"] = _select(bsnd >= 0, wire_b,
                                                 st["b_chain"])
                        if cdc_cot.stateful:
                            wef["b"] = ef2
                elif r_brecv:
                    out["b_inbox"] = b_inbox
            else:
                if r_f:
                    out["outputs"] = _constrain_batch0(
                        _masked_write(st["outputs"], res["carry"], micro_t,
                                      is_f & is_last_rank), lead=1)
            if r_latch_f:
                # MPMD: encode + latch this tick's boundary output for the
                # next tick's overlapped ship (see plan.TaskPlan.send_slot)
                snd = col(xt["snd"])
                wire_f, ef2 = cdc_chain.enc(
                    res["carry"],
                    wef["f"] if cdc_chain.stateful else (),
                    snd >= 0)
                out["f_chain"] = _select(snd >= 0, wire_f, st["f_chain"])
                if cdc_chain.stateful:
                    wef["f"] = ef2
            if routes and mpmd:
                # MPMD route latch: encode + park outgoing route payloads in
                # the per-route send registers at the bottom of the tick; the
                # next tick's skeleton ships them overlapped with compute —
                # no route hop ever serializes after its producing task.
                for rt in routes:
                    rx = xt.get("routes", {}).get(rt.key, {})
                    entry = rst[rt.key]
                    proto = skip_protos[rt.name]
                    vc, gc = rt_vc[rt.key], rt_gc[rt.key]
                    if "send" in rx and (
                            not static
                            or bool((rt.send[sl, r] != -1).any())):
                        sv = col(rx["send"])
                        fresh = (res["skips"][rt.name]
                                 if (not fb or r_f) else _zeros_of(proto))
                        raw = _select(sv == plan_lib.SEND_STAGE, fresh,
                                      _dyn_read(entry["buf"], sv))
                        ef = wef["r:" + rt.key] if vc.stateful else ()
                        wire_v, ef2 = vc.enc(raw, ef, sv != -1)
                        entry["snd"] = _select(
                            sv != -1, wire_v, st["routes"][rt.key]["snd"])
                        if vc.stateful:
                            wef["r:" + rt.key] = ef2
                    if fb and "g_send" in rx and (
                            not static
                            or bool((rt.g_send[sl, r] != -1).any())):
                        gv = col(rx["g_send"])
                        gfresh = (res["gskips"][rt.name]
                                  if r_bi else _zeros_of(proto))
                        graw = _select(gv == plan_lib.SEND_STAGE, gfresh,
                                       _dyn_read(entry["gbuf"], gv))
                        gef = wef["g:" + rt.key] if gc.stateful else ()
                        wire_g, gef2 = gc.enc(graw, gef, gv != -1)
                        entry["gsnd"] = _select(
                            gv != -1, wire_g, st["routes"][rt.key]["gsnd"])
                        if gc.stateful:
                            wef["g:" + rt.key] = gef2
            if routes:
                # fresh dict: never mutate st (the MPMD branches all close
                # over the same state dict)
                out["routes"] = {rt.key: rst[rt.key] for rt in routes}
            if wef is not None:
                out["wef"] = wef

            extras = {}
            if routes and not mpmd:
                extras["skips"] = (res["skips"] if r_f and has_f
                                   else zeros_skips())
                if fb and has_bi:
                    extras["gskips"] = (res["gskips"] if r_bi
                                        else zeros_skips())
            if not mpmd:
                if has_f:
                    extras["carry"] = res["carry"]
                if fb and has_bi:
                    extras["b"] = res["b"]
            return out, extras

        def tick_body(st, xt):
            # --- rank-uniform comm skeleton, part 1: chain arrivals -------
            if mpmd:
                # double-buffered ship: the permute reads the latch
                # registers written LAST tick, so it carries no data
                # dependency on this tick's compute — XLA's scheduler can
                # overlap the hop with the stage work below.
                arr_f = (_shift_chain(st["f_chain"], R, axis, ring=chunked)
                         if need_ship_f else cdc_chain.zeros(carry0))
                arr_b = None
                if fb:
                    arr_b = (_shift_chain_rev(st["b_chain"], R, axis,
                                              ring=chunked)
                             if need_ship_b else cdc_cot.zeros(carry0))
                # latched route hops: ship last tick's send registers at
                # the top of this tick, same double-buffer story as the
                # chain carry — no route hop serializes after its producer.
                arr_rt = {rt.key: _route_hop(st["routes"][rt.key]["snd"],
                                             rt.fwd_perm, axis)
                          for rt in routes if rship[rt.key]}
                arr_grt = {rt.key: _route_hop(st["routes"][rt.key]["gsnd"],
                                              rt.bwd_perm, axis)
                           for rt in routes if rgship[rt.key]} if fb else {}
                if cfg.overlap and (need_ship_f or need_ship_b
                                    or arr_rt or arr_grt):
                    # pin the overlap: group the in-flight arrivals into
                    # one scheduling unit issued ahead of the compute, so
                    # the compiler cannot sink the send back behind it
                    # (the serialized story cfg.overlap=False ablates to).
                    if fb:
                        arr_f, arr_b, arr_rt, arr_grt = _barrier(
                            arr_f, arr_b, arr_rt, arr_grt)
                    else:
                        arr_f, arr_rt = _barrier(arr_f, arr_rt)
                # decode at arrival (identity for fp32 wire)
                arr_f = cdc_chain.dec(arr_f, carry0)
                if fb:
                    arr_b = cdc_cot.dec(arr_b, carry0)
                arr_rt = {k: rt_vc[k].dec(v, skip_protos[route_name_of[k]])
                          for k, v in arr_rt.items()}
                arr_grt = {k: rt_gc[k].dec(v,
                                           skip_protos[route_name_of[k]])
                           for k, v in arr_grt.items()}
            else:
                arr_f = cdc_chain.dec(st["f_chain"], carry0)
                arr_b = cdc_cot.dec(st["b_chain"], carry0) if fb else None
                arr_rt = {rt.key: rt_vc[rt.key].dec(
                    st["routes"][rt.key]["fly"], skip_protos[rt.name])
                    for rt in routes if seg_recv[rt.key]}
                arr_grt = {rt.key: rt_gc[rt.key].dec(
                    st["routes"][rt.key]["gfly"], skip_protos[rt.name])
                    for rt in routes if seg_grecv[rt.key]}

            # --- per-rank specialized tick ---------------------------------
            if mpmd and R > 1:
                out, extras = jax.lax.switch(
                    idx, tuple(functools.partial(rank_tick, r)
                               for r in range(R)), st, xt, arr_f, arr_b,
                    arr_rt, arr_grt)
            else:
                out, extras = rank_tick(0 if mpmd else None, st, xt,
                                        arr_f, arr_b, arr_rt, arr_grt)

            # --- rank-uniform comm skeleton, part 2 ------------------------
            # SPMD reference: eager chain sends (this tick's outputs enter
            # the wire immediately, serialized after the compute).
            if not mpmd:
                if fb and has_bi:
                    if cdc_cot.stateful:
                        bsnd = xt["bsnd"][idx]
                        wire_b, ef2 = cdc_cot.enc(extras["b"],
                                                  out["wef"]["b"],
                                                  bsnd >= 0)
                        out["wef"] = dict(out["wef"], b=ef2)
                    else:
                        wire_b, _ = cdc_cot.enc(extras["b"], (), None)
                    out["b_chain"] = _shift_chain_rev(wire_b, R, axis,
                                                      ring=chunked)
                if has_f:
                    if cdc_chain.stateful:
                        snd = xt["snd"][idx]
                        wire_f, ef2 = cdc_chain.enc(extras["carry"],
                                                    out["wef"]["f"],
                                                    snd >= 0)
                        out["wef"] = dict(out["wef"], f=ef2)
                    else:
                        wire_f, _ = cdc_chain.enc(extras["carry"], (), None)
                    out["f_chain"] = _shift_chain(wire_f, R, axis,
                                                  ring=chunked)

            # skip-route hops (static single-pair / chain permutes) — SPMD
            # eager reference: this tick's payload enters the wire
            # immediately, serialized after the compute.  (MPMD latches
            # instead; see the commit section + part-1 skeleton.)
            for rt in (() if mpmd else routes):
                rx = xt.get("routes", {}).get(rt.key, {})
                entry = dict(out["routes"][rt.key])
                if "send" in rx and has_f:
                    sv = rx["send"][idx]
                    val = _select(sv == plan_lib.SEND_STAGE,
                                  extras["skips"][rt.name],
                                  _dyn_read(entry["buf"], sv))
                    vc = rt_vc[rt.key]
                    ef = out["wef"]["r:" + rt.key] if vc.stateful else ()
                    wire_v, ef2 = vc.enc(val, ef, sv != -1)
                    if vc.stateful:
                        out["wef"] = dict(out["wef"],
                                          **{"r:" + rt.key: ef2})
                    entry["fly"] = _route_hop(wire_v, rt.fwd_perm, axis)
                else:
                    entry["fly"] = st["routes"][rt.key]["fly"]
                if fb:
                    if "g_send" in rx and has_bi:
                        gv = rx["g_send"][idx]
                        gval = _select(gv == plan_lib.SEND_STAGE,
                                       extras["gskips"][rt.name],
                                       _dyn_read(entry["gbuf"], gv))
                        gc = rt_gc[rt.key]
                        gef = (out["wef"]["g:" + rt.key]
                               if gc.stateful else ())
                        wire_g, gef2 = gc.enc(gval, gef, gv != -1)
                        if gc.stateful:
                            out["wef"] = dict(out["wef"],
                                              **{"g:" + rt.key: gef2})
                        entry["gfly"] = _route_hop(wire_g, rt.bwd_perm,
                                                   axis)
                    else:
                        entry["gfly"] = st["routes"][rt.key]["gfly"]
                out["routes"][rt.key] = entry

            # rotate the input stream one rank towards stage 0 on the
            # plan-flagged ticks (keeps rotation count == injected micros)
            if need_rot:
                rot = [(i, (i - 1) % R) for i in range(R)]
                with jax.named_scope(PIPE_HOP):
                    spun = jax.tree.map(
                        lambda a: jax.lax.ppermute(a, axis, rot),
                        st["stream"])
                out["stream"] = _select(xt["rot"], spun, st["stream"])
            return out, None

        return xs, tick_body

    state = st
    with jax.named_scope(PIPE):
        for seg in tplan.segments:
            xs, body = make_segment(seg)
            if cfg.unroll_ticks:
                for t in range(seg.stop - seg.start):
                    state, _ = body(state, jax.tree.map(
                        lambda a, _t=t: a[_t], xs))
            else:
                state, _ = jax.lax.scan(body, state, xs)

    if not fb:
        return state["outputs"], state["resident"]
    loss_acc = state["loss"]
    g_stage, g_head, igbuf = state["g_stage"], state["g_head"], state["igbuf"]
    if ordered:
        # fixed-order reduction over the micro axis: the sum is identical
        # for every schedule, making gradients schedule-bitwise-stable.
        with jax.named_scope(GRAD_REDUCE):
            g_stage = jax.tree.map(lambda a: jnp.sum(a, axis=0), g_stage)
            g_head = jax.tree.map(lambda a: jnp.sum(a, axis=0), g_head)
    return loss_acc, g_stage, g_head, igbuf, state["resident"]


def run_pipeline(stage_apply: StageApplyFn,
                 stage_params,
                 inputs_mb,
                 cfg: ParallelConfig,
                 *,
                 skips: Sequence[SkipSpec] = (),
                 skip_protos: Optional[Dict[str, Any]] = None,
                 resident=None,
                 carry_proto=None,
                 axis: str = PIPE_AXIS,
                 rank=None):
    """Forward-only wrapper: lower the GPipe clock-cycle plan and run it.

    ``jax.grad`` through this call induces the reverse clock-cycle with
    recompute-before-backward (the legacy semantics); the loop itself is
    :func:`run_pipeline_tasks` on a ``gpipe_fwd`` plan — there is no
    separate forward tick loop any more.

    Returns ``(outputs [m, ...carry], resident)`` — outputs valid on the
    last rank.
    """
    tplan = plan_lib.plan_for("gpipe_fwd", cfg.n_micro, cfg.pipe,
                              skips=skips, portals=cfg.portals,
                              wire=cfg.wire)
    return run_pipeline_tasks(stage_apply, stage_params, inputs_mb, cfg,
                              tplan=tplan, skip_protos=skip_protos,
                              resident=resident, carry_proto=carry_proto,
                              axis=axis, rank=rank)


# ---------------------------------------------------------------------------
# Fused-schedule training entry point (F+B plans)
# ---------------------------------------------------------------------------

def pipeline_grad_call(stage_apply: StageApplyFn,
                       *,
                       mesh: Mesh,
                       cfg: ParallelConfig,
                       loss_fn,
                       carry_proto=None,
                       skips: Sequence[SkipSpec] = (),
                       skip_protos: Optional[Dict[str, Any]] = None,
                       axis: str = PIPE_AXIS,
                       resid_info: Optional[Dict[str, Any]] = None):
    """Build the fused schedule-driven training call.

    Returns ``call(stage_params, head_params, inputs_mb, loss_args_mb,
    resident=None) -> (loss, stage_grads, head_grads, input_grads_mb)``
    where:

    * ``loss`` is the mean per-micro loss (matches ``head_loss`` over the
      full batch up to micro-chunked summation order),
    * ``stage_grads`` mirrors ``stage_params`` ([n_stages, ...], sharded
      over ``pipe``; for interleaved schedules ``n_stages = pipe * v``
      global stages stacked in stage order),
    * ``head_grads`` mirrors ``head_params`` (valid on the last rank),
    * ``input_grads_mb`` mirrors ``inputs_mb`` ([m, ...], valid on rank 0)
      — feed it to the embed VJP outside the pipeline.  Skip cotangents a
      stage-0 producer routes into its fresh input (e.g. the enc-dec
      ``dec_in`` portal) are folded in here as well.

    The schedule comes from ``cfg.schedule``: ``"1f1b"``,
    ``"gpipe"``/``"gpipe_tasked"``, ``"interleaved:v"`` (v virtual stages
    per rank, Megatron-style) or ``"zb"`` (ZB-H1 split backward) — all
    lowered by :func:`repro.core.plan.plan_for` from the validated task
    tables in :mod:`repro.core.schedules`.  Skip edges lower to
    portal/threaded routes per ``cfg.portals``; ``cfg.stream_inputs``
    (with ``m % n == 0``) shards the micro-batches over pipe and injects
    them on plan ticks.  For split-backward schedules,
    ``cfg.residuals="reuse"`` lowers the Bx->Bw residual-stash events
    (true ZB-H1: Bw re-reads what Bx materialized instead of recomputing);
    pass a dict as ``resid_info`` to receive the stash geometry at trace
    time.  ``cfg.executor`` picks the SPMD reference lowering or the MPMD
    per-rank specialization (bitwise-identical; see
    :func:`run_pipeline_tasks`).
    """
    n, m = cfg.pipe, cfg.n_micro
    v = cfg.virtual_stages
    streaming = cfg.stream_inputs and n > 1
    if streaming and m % n:
        # don't silently drop a memory knob: streaming shards the
        # micro-batches over pipe, which needs m % n == 0
        raise ValueError(f"stream_inputs needs n_micro ({m}) divisible by "
                         f"pipe ({n})")
    cfg = cfg.with_(stream_inputs=streaming)
    tplan = plan_lib.plan_for(cfg.schedule, m, n, skips=skips,
                              portals=cfg.portals,
                              residuals=cfg.residuals,
                              wire=cfg.wire)

    def inner(rank_arr, params, head_params, inputs_mb, loss_args_mb):
        params = jax.tree.map(lambda a: a[0], params)
        if streaming:
            inputs_mb = jax.tree.map(lambda a: a[0], inputs_mb)
        loss_sum, g_stage, g_head, ig, _ = run_pipeline_tasks(
            stage_apply, params, inputs_mb, cfg,
            tplan=tplan, head_params=head_params,
            loss_args_mb=loss_args_mb, loss_fn=loss_fn,
            skip_protos=dict(skip_protos or {}),
            carry_proto=carry_proto, axis=axis,
            rank=rank_arr[0], resid_info=resid_info)
        loss = (loss_sum * (1.0 / m))[None]
        g_stage = jax.tree.map(lambda a: a[None], g_stage)
        g_head = jax.tree.map(lambda a: a[None], g_head)
        ig = jax.tree.map(lambda a: a[None], ig)
        return loss, g_stage, g_head, ig

    def call(stage_params, head_params, inputs_mb, loss_args_mb):
        rank_arr = jnp.arange(n, dtype=jnp.int32)
        if v > 1:
            # stage-major [n*v, ...] -> rank-major [n, v, ...]: rank r
            # hosts global stages {r, r + n, ...} (Megatron chunk layout)
            stage_params = jax.tree.map(
                lambda a: a.reshape((v, n) + a.shape[1:]).swapaxes(0, 1),
                stage_params)
        if streaming:
            k = m // n
            inputs_mb = jax.tree.map(
                lambda a: a.reshape((k, n) + a.shape[1:]).swapaxes(0, 1),
                inputs_mb)
        if cfg.pipe > 1:
            in_spec_x = P(axis) if streaming else P()
            fn = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(P(axis), P(axis), P(), in_spec_x, P()),
                out_specs=(P(axis), P(axis), P(axis), P(axis)),
                axis_names={axis}, check_vma=False)
        else:
            fn = inner
        loss, g_stage, g_head, ig = fn(rank_arr, stage_params, head_params,
                                       inputs_mb, loss_args_mb)
        loss = loss[-1]
        g_head = jax.tree.map(lambda a: a[-1], g_head)
        ig = jax.tree.map(lambda a: a[0], ig)
        if v > 1:
            # rank-major grads [n, v, ...] -> stage-major [n*v, ...]
            g_stage = jax.tree.map(
                lambda a: a.swapaxes(0, 1).reshape((n * v,) + a.shape[2:]),
                g_stage)
        return loss, g_stage, g_head, ig

    return call, tplan


# ---------------------------------------------------------------------------
# shard_map wrapper: the public forward entry point
# ---------------------------------------------------------------------------

def pipeline_call(stage_apply: StageApplyFn,
                  *,
                  mesh: Mesh,
                  cfg: ParallelConfig,
                  skips: Sequence[SkipSpec] = (),
                  skip_protos: Optional[Dict[str, Any]] = None,
                  carry_proto=None,
                  axis: str = PIPE_AXIS):
    """Build ``(stage_params, inputs_mb, resident) -> (outputs, resident)``.

    ``stage_params``/``resident`` leaves carry a leading ``n_stages`` axis
    sharded over ``pipe``; ``inputs_mb`` is replicated over ``pipe`` (its
    batch-ish dims may be sharded over the auto axes).  ``outputs`` gains a
    leading ``pipe``-sharded axis: index ``[-1]`` for the last stage's
    results (:func:`last_stage_output`).

    Forward-only execution always runs the GPipe clock-cycle plan
    (interleaving is a fused-training lever; inference has no backward
    bubble to shrink).
    """
    if cfg.virtual_stages > 1:
        raise ValueError("interleaved schedules are train-only (use "
                         "pipeline_grad_call); forward execution runs the "
                         "clock-cycle plan")
    # Input modes across the shard_map boundary:
    #  * replicated (default): the transpose of the pipe-replicated in_spec
    #    is a psum over the *manual* axis — this both dominates collective
    #    bytes for embedding-fed models AND crashes XLA-CPU's
    #    AllReducePromotion in bf16, so the inputs cross in fp32.
    #  * streaming (cfg.stream_inputs, m % n == 0): micro-batches are
    #    SHARDED over pipe (micro-batch i at rank i%n, slot i//n) and
    #    rotated one hop per plan tick; the transpose is a reverse rotation
    #    (no psum), memory drops by n, and bf16 is safe.
    def inner(rank_arr, params, inputs_mb, resident, in_dtypes, cfg_run):
        params = jax.tree.map(lambda a: a[0], params)
        resident = jax.tree.map(lambda a: a[0], resident)
        if cfg_run.stream_inputs:
            inputs_mb = jax.tree.map(lambda a: a[0], inputs_mb)
        inputs_mb = jax.tree.map(lambda a, d: a.astype(d), inputs_mb,
                                 in_dtypes)
        outs, res = run_pipeline(stage_apply, params, inputs_mb, cfg_run,
                                 skips=skips,
                                 skip_protos=dict(skip_protos or {}),
                                 resident=resident, carry_proto=carry_proto,
                                 axis=axis, rank=rank_arr[0])
        outs = jax.tree.map(lambda a: a[None], outs)
        res = jax.tree.map(lambda a: a[None], res)
        return outs, res

    def call(stage_params, inputs_mb, resident=None):
        resident = {} if resident is None else resident
        n, m = cfg.pipe, cfg.n_micro
        streaming = cfg.stream_inputs and n > 1 and m % n == 0
        cfg_run = cfg.with_(stream_inputs=streaming)
        in_dtypes = jax.tree.map(lambda a: a.dtype, inputs_mb)
        if streaming:
            k = m // n
            inputs_mb = jax.tree.map(
                lambda a: a.reshape((k, n) + a.shape[1:]).swapaxes(0, 1),
                inputs_mb)
            in_spec_x = P(axis)
            up = inputs_mb
        else:
            in_spec_x = P()
            up = jax.tree.map(
                lambda a: a.astype(jnp.float32)
                if a.dtype == jnp.bfloat16 else a, inputs_mb)
        rank_arr = jnp.arange(n, dtype=jnp.int32)
        if cfg.pipe > 1:
            fn = jax.shard_map(
                functools.partial(inner, in_dtypes=in_dtypes,
                                  cfg_run=cfg_run), mesh=mesh,
                in_specs=(P(axis), P(axis), in_spec_x, P(axis)),
                out_specs=(P(axis), P(axis)),
                axis_names={axis}, check_vma=False)
        else:
            # Degenerate single-stage pipeline: plain sequential execution,
            # no manual axis (avoids size-1 manual subgroups).
            fn = functools.partial(inner, in_dtypes=in_dtypes,
                                   cfg_run=cfg_run.with_(stream_inputs=False))
        return fn(rank_arr, stage_params, up, resident)

    return call


def last_stage_output(outputs):
    """Extract the last pipe rank's collected outputs: [m, ...] pytree."""
    return jax.tree.map(lambda a: a[-1], outputs)


def microbatch(tree, n_micro: int):
    """Split leading batch dim B -> [n_micro, B // n_micro, ...]."""
    def f(a):
        b = a.shape[0]
        if b % n_micro:
            raise ValueError(f"batch {b} not divisible by n_micro {n_micro}")
        return a.reshape((n_micro, b // n_micro) + a.shape[1:])
    return jax.tree.map(f, tree)


def unmicrobatch(tree):
    return jax.tree.map(
        lambda a: a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:]), tree)
