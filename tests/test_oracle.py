"""Independent single-device correctness oracle for the fused runtime.

The schedule-equivalence suite (tests/test_schedule_exec.py) is
self-referential: gpipe_tasked / 1f1b / zb / interleaved are compared
bitwise against *each other*, so a bug shared by the fused executor's vjp
path would pass every test.  This module checks every fused schedule —
including zb with residual REUSE and RECOMPUTE — against a from-scratch
single-device reference: no pipeline, no shard_map, no task plan, just the
model's stage functions chained sequentially per micro-batch and
``jax.grad`` through the whole thing.

Three model families cover the runtime surface: the plain LM path, the
whisper encoder-decoder (skip portals), and the U-Net heterogeneous
(switch-based) program via ``UNetModel.apply_sequential``.  The LM test
additionally checks loss-curve agreement over 5 optimizer steps.
"""
from conftest import run_subprocess

# Per-dtype allclose tolerances: the oracle and the pipeline evaluate the
# same math on different graphs (fused remat + buffered operands vs one
# autodiff pass), so sums reassociate.
#
# The whole suite honours REPRO_EXECUTOR ("spmd" default / "mpmd"): the CI
# executor-matrix leg reruns every oracle comparison with the fused side
# lowered to per-rank specialized programs, so the MPMD path is checked
# against the independent single-device reference, not just against SPMD.
COMMON = """
import os
import numpy as np
import jax, jax.numpy as jnp

EXECUTOR = os.environ.get("REPRO_EXECUTOR", "spmd")
print("oracle executor:", EXECUTOR)

TOL = {"float32": dict(rtol=5e-4, atol=5e-5),
       "bfloat16": dict(rtol=2e-2, atol=2e-2)}

def assert_close(oracle, got, tag):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(oracle)[0],
                            jax.tree_util.tree_leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            **TOL[str(np.asarray(a).dtype)], err_msg=f"{tag} {path}")

def assert_bitwise(ga, gb, tag):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        assert np.array_equal(a, b), (tag, path)
"""

LM_ORACLE = COMMON + """
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.core.pipeline import (TickCtx, pipeline_grad_call, microbatch,
                                 unmicrobatch)

ARCH = __ARCH__
arch = configs.smoke_arch(ARCH)
key = jax.random.PRNGKey(0)
shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")

def make_batch(model):
    batch = {}
    for k, v in model.input_specs(shape).items():
        kk = jax.random.fold_in(key, len(k))
        batch[k] = (jax.random.randint(kk, v.shape, 0, arch.vocab)
                    if v.dtype == jnp.int32
                    else jax.random.normal(kk, v.shape, v.dtype) * 0.1)
    return batch

def oracle_loss_fn(model, m):
    # Sequential single-device reference: stage chain per micro-batch,
    # skips held in a plain dict, mean of per-micro losses — mirrors the
    # fused loss contract with zero pipeline machinery.
    sk = model.skips()
    stage_apply = model.make_stage_apply(model.consts())

    def loss_fn(params, batch):
        fresh = model.embed_inputs(params["embed"], batch)
        fresh_mb = jax.tree.map(
            lambda a: a.reshape((m, a.shape[0] // m) + a.shape[1:]), fresh)
        labels_mb = batch["labels"].reshape(
            (m, batch["labels"].shape[0] // m) + batch["labels"].shape[1:])
        hp = {"head": params["head"], "embed": params["embed"]}
        total = jnp.zeros((), jnp.float32)
        for i in range(m):
            fresh_i = jax.tree.map(lambda a: a[i], fresh_mb)
            carry = {"h": jnp.zeros_like(fresh_i["h"])}
            store = {}
            for s in range(model.n_stages):
                skips_in = {e.name: store[e.name] for e in sk
                            if s in e.dsts and e.name in store}
                ctx = TickCtx(stage=jnp.int32(s), micro=jnp.int32(i),
                              valid=jnp.asarray(True), t=jnp.int32(0),
                              fresh=fresh_i, n_stages=model.n_stages,
                              n_micro=m)
                p_s = jax.tree.map(lambda a: a[s], params["stages"])
                carry, skips_out, _ = stage_apply(p_s, carry, skips_in,
                                                  {}, ctx)
                for e in sk:
                    if e.src_stage == s:
                        store[e.name] = skips_out[e.name].astype(model.dtype)
            total = total + model.head_loss(
                hp, carry["h"], labels_mb[i]).astype(jnp.float32)
        return total / m
    return loss_fn

def fused_lg(schedule, m, residuals, remat, remat_last_micro=False):
    pcfg = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=m,
                          remat=remat, schedule=schedule,
                          residuals=residuals, executor=EXECUTOR,
                          remat_last_micro=remat_last_micro)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    batch = make_batch(model)
    mbg = shape.global_batch // m
    cp = {"h": jax.ShapeDtypeStruct((mbg, 16, arch.d_model), jnp.float32)}
    info = {}
    with set_mesh(mesh):
        pg, _ = pipeline_grad_call(
            model.make_stage_apply(model.consts()), mesh=mesh, cfg=pcfg,
            loss_fn=lambda hpp, c, la: model.head_loss(hpp, c["h"],
                                                       la["labels"]),
            skips=model.skips(),
            skip_protos=model.skip_protos(mbg, 16),
            carry_proto=cp, resid_info=info)
        @jax.jit
        def fused(p, b):
            fresh, evjp = jax.vjp(
                lambda e: model.embed_inputs(e, b), p["embed"])
            hpp = {"head": p["head"], "embed": p["embed"]}
            loss, gs, gh, ig = pg(p["stages"], hpp, microbatch(fresh, m),
                                  microbatch({"labels": b["labels"]}, m))
            (ge,) = evjp(unmicrobatch(ig))
            ge = jax.tree.map(jnp.add, ge, gh["embed"])
            return loss, {"embed": ge, "stages": gs, "head": gh["head"]}
        loss, grads = fused(params, batch)
    return (np.asarray(loss), jax.tree.map(np.asarray, grads),
            model, params, batch, info)

m = 4
results = {}
MATRIX = [("gpipe_tasked", "recompute", "full"),
          ("1f1b", "recompute", "full"),
          ("interleaved:2", "recompute", "full"),
          ("zb", "recompute", "full"),
          ("zb", "reuse", "dots"),
          ("zb", "reuse", "none")]
for schedule, residuals, remat in MATRIX:
    loss, grads, model, params, batch, info = fused_lg(
        schedule, m, residuals, remat)
    if residuals == "reuse" and remat != "full":
        assert info["resid_bytes_per_slot"] > 0, info  # machinery engaged
    o_loss, o_grads = jax.jit(jax.value_and_grad(
        oracle_loss_fn(model, m)))(params, batch)
    np.testing.assert_allclose(np.asarray(o_loss), loss, rtol=2e-5)
    assert_close(o_grads, grads, (ARCH, schedule, residuals, remat))
    results[(schedule, residuals, remat)] = (loss, grads)
    print("oracle OK", ARCH, schedule, residuals, remat)

# acceptance: zb reuse (dots policy) is BITWISE against zb recompute
l_rec, g_rec = results[("zb", "recompute", "full")]
l_reu, g_reu = results[("zb", "reuse", "dots")]
assert np.array_equal(l_rec, l_reu)
assert_bitwise(g_rec, g_reu, "zb-reuse-vs-recompute")

# remat_last_micro is an unrolled-legacy knob: it must not perturb the
# fused reuse path (edge-case satellite)
l_rl, g_rl, *_ = fused_lg("zb", m, "reuse", "dots", remat_last_micro=True)
assert np.array_equal(l_reu, l_rl)
assert_bitwise(g_reu, g_rl, "remat_last_micro-x-reuse")
print("bitwise OK")
print("LM ORACLE OK")
"""

LM_TRAIN_CURVE = COMMON + """
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib, steps
from repro.models.lm import LMModel
from repro.core.pipeline import TickCtx
from repro.optim import optimizers as optim

arch = configs.smoke_arch("smollm-360m")
key = jax.random.PRNGKey(0)
shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")
m = 4
pcfg = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=m,
                      schedule="zb", residuals="reuse", remat="dots",
                      executor=EXECUTOR)
mesh = mesh_lib.make_smoke_mesh(pcfg)
model = LMModel(arch, pcfg, dtype=jnp.float32)
params = model.init(key)
batch = {k: jax.random.randint(jax.random.fold_in(key, len(k)), v.shape, 0,
                               arch.vocab)
         for k, v in model.input_specs(shape).items()}
ocfg = optim.OptimizerConfig(lr=2e-3, warmup_steps=2, total_steps=20)

# pipeline side: the production train step (fused zb + residual reuse)
with set_mesh(mesh):
    step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape, ocfg))
    p_pipe, o_pipe = params, optim.init(ocfg, params)
    pipe_losses = []
    for _ in range(5):
        p_pipe, o_pipe, metrics = step(p_pipe, o_pipe, batch)
        pipe_losses.append(float(metrics["loss"]))

# oracle side: sequential stage chain + jax.grad + the same optimizer
stage_apply = model.make_stage_apply(model.consts())
def oracle_loss(p, b):
    fresh = model.embed_inputs(p["embed"], b)
    fresh_mb = jax.tree.map(
        lambda a: a.reshape((m, a.shape[0] // m) + a.shape[1:]), fresh)
    labels_mb = b["labels"].reshape(
        (m, b["labels"].shape[0] // m) + b["labels"].shape[1:])
    hp = {"head": p["head"], "embed": p["embed"]}
    total = jnp.zeros((), jnp.float32)
    for i in range(m):
        fresh_i = jax.tree.map(lambda a: a[i], fresh_mb)
        carry = {"h": jnp.zeros_like(fresh_i["h"])}
        for s in range(model.n_stages):
            ctx = TickCtx(stage=jnp.int32(s), micro=jnp.int32(i),
                          valid=jnp.asarray(True), t=jnp.int32(0),
                          fresh=fresh_i, n_stages=model.n_stages, n_micro=m)
            p_s = jax.tree.map(lambda a: a[s], p["stages"])
            carry, _, _ = stage_apply(p_s, carry, {}, {}, ctx)
        total = total + model.head_loss(hp, carry["h"],
                                        labels_mb[i]).astype(jnp.float32)
    return total / m

@jax.jit
def oracle_step(p, o, b):
    loss, grads = jax.value_and_grad(oracle_loss)(p, b)
    p2, o2, _ = optim.apply(ocfg, o, p, grads)
    return p2, o2, loss

p_o, o_o = params, optim.init(ocfg, params)
oracle_losses = []
for _ in range(5):
    p_o, o_o, loss = oracle_step(p_o, o_o, batch)
    oracle_losses.append(float(loss))

print("pipe  :", pipe_losses)
print("oracle:", oracle_losses)
np.testing.assert_allclose(pipe_losses, oracle_losses, rtol=2e-3, atol=1e-5)
assert pipe_losses[-1] < pipe_losses[0], "training must make progress"
print("TRAIN CURVE OK")
"""

UNET_ORACLE = COMMON + """
from jax import set_mesh
from repro.configs.base import ParallelConfig
from repro.core import stage as stage_lib
from repro.launch import mesh as mesh_lib
from repro.models import pipeline_hetero as PH
from repro.models.unet import UNetConfig, UNetModel

key = jax.random.PRNGKey(0)
ucfg = UNetConfig(B=1, C=8, levels=3, img=16)
UB, pipe, m = 8, 2, 4
mb = UB // m
x = jax.random.normal(jax.random.fold_in(key, 1), (UB, ucfg.img, ucfg.img, 3))

MATRIX = [("gpipe_tasked", "recompute", "full"),
          ("1f1b", "recompute", "full"),
          ("interleaved:2", "recompute", "full"),
          ("zb", "recompute", "full"),
          ("zb", "reuse", "dots")]
results = {}
for schedule, residuals, remat in MATRIX:
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=1, pod=1, n_micro=m,
                          portals=True, remat=remat, schedule=schedule,
                          residuals=residuals, executor=EXECUTOR)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    umodel = UNetModel(ucfg, pipe * pcfg.virtual_stages)
    uparams = umodel.init(jax.random.PRNGKey(0))
    prog = PH.build_hetero_program(umodel, uparams, mb, pcfg, x[:2])
    tgt = jnp.zeros((UB,) + tuple(prog.out_proto.shape[1:]), jnp.float32)
    with set_mesh(mesh):
        call = jax.jit(PH.hetero_grad_call(prog, mesh, pcfg))
        loss, g_stage = call(prog.stacked_params, x, tgt)
    loss, g_stage = np.asarray(loss), np.asarray(g_stage)
    results[(schedule, residuals)] = (loss, g_stage)

    # oracle: direct layer chain (UNetModel.apply_sequential), jax.grad
    def oracle_loss(params_list):
        total = jnp.zeros((), jnp.float32)
        for i in range(m):
            xi = x[i * mb:(i + 1) * mb]
            yi = tgt[i * mb:(i + 1) * mb].reshape(mb, -1)
            out = umodel.apply_sequential(params_list, xi)
            total = total + jnp.mean((out.reshape(mb, -1) - yi) ** 2)
        return total / m
    o_loss, o_grads = jax.jit(jax.value_and_grad(oracle_loss))(uparams)
    np.testing.assert_allclose(np.asarray(o_loss), loss, rtol=2e-5)
    # fused grads are flat-packed per stage: flatten the oracle's the same
    # way and compare (the padding tail must be exactly zero)
    for s in range(umodel.n_stages):
        lo, hi = umodel.bounds[s], umodel.bounds[s + 1]
        flat, _, _ = stage_lib.flatten_params(
            jax.tree.map(np.asarray, o_grads[lo:hi]))
        got = g_stage[s]
        np.testing.assert_allclose(np.asarray(flat), got[:flat.shape[0]],
                                   rtol=5e-4, atol=5e-5,
                                   err_msg=f"{schedule} stage {s}")
        assert not got[flat.shape[0]:].any(), f"{schedule} stage {s} padding"
    print("unet oracle OK", schedule, residuals)

l_rec, g_rec = results[("zb", "recompute")]
l_reu, g_reu = results[("zb", "reuse")]
assert np.array_equal(l_rec, l_reu) and np.array_equal(g_rec, g_reu)
print("UNET ORACLE OK")
"""


def test_oracle_lm():
    """Every fused schedule (incl. zb residual reuse and recompute) matches
    a from-scratch single-device jax.grad reference on the LM model, and
    zb-reuse is bitwise against zb-recompute."""
    out = run_subprocess(LM_ORACLE.replace("__ARCH__", repr("smollm-360m")),
                         n_devices=8, timeout=2400)
    assert "LM ORACLE OK" in out


def test_oracle_whisper_portal():
    """The encoder-decoder portal model (skip routes through the plan)
    matches the sequential oracle under every fused schedule."""
    out = run_subprocess(LM_ORACLE.replace("__ARCH__", repr("whisper-tiny")),
                         n_devices=8, timeout=2400)
    assert "LM ORACLE OK" in out


def test_oracle_unet_hetero():
    """The heterogeneous (switch-program) U-Net matches jax.grad over
    UNetModel.apply_sequential under every fused schedule."""
    out = run_subprocess(UNET_ORACLE, n_devices=8, timeout=2400)
    assert "UNET ORACLE OK" in out


def test_oracle_train_curve():
    """5 optimizer steps of the fused zb+reuse train step track the oracle
    train loop's loss curve."""
    out = run_subprocess(LM_TRAIN_CURVE, n_devices=8, timeout=1800)
    assert "TRAIN CURVE OK" in out
