"""Fused-schedule training through the full LM on 8 host devices: 1F1B and
GPipe give bitwise-identical losses and gradients, and every fused schedule
trains.  Each test runs its own subprocess; the file stands apart from
``test_schedule_exec.py`` so that parallel test workers share the load."""
from conftest import run_subprocess


EXEC_GRID = """
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.core.pipeline import (pipeline_call, pipeline_grad_call,
                                 microbatch, last_stage_output, unmicrobatch)

arch = configs.smoke_arch("smollm-360m")
key = jax.random.PRNGKey(0)

def loss_and_grads(schedule, pipe, m, data):
    shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=data, pod=1, n_micro=m,
                          remat="full", schedule=schedule)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    batch = {k: jax.random.randint(jax.random.fold_in(key, len(k)),
                                   v.shape, 0, arch.vocab)
             for k, v in model.input_specs(shape).items()}
    consts = model.consts()
    mbg = shape.global_batch // m
    cp = {"h": jax.ShapeDtypeStruct((mbg, 16, arch.d_model), jnp.float32)}
    with set_mesh(mesh):
        if schedule == "gpipe":      # legacy autodiff path (reference)
            pipe_fn = pipeline_call(model.make_stage_apply(consts),
                                    mesh=mesh, cfg=pcfg, carry_proto=cp)
            def loss_fn(p, b):
                fresh = model.embed_inputs(p["embed"], b)
                outs, _ = pipe_fn(p["stages"], microbatch(fresh, m), None)
                h = unmicrobatch(last_stage_output(outs)["h"])
                return model.head_loss(p, h, b["labels"])
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
            return np.asarray(loss), jax.tree.map(np.asarray, grads)
        pg, tplan = pipeline_grad_call(
            model.make_stage_apply(consts), mesh=mesh, cfg=pcfg,
            loss_fn=lambda hp, c, la: model.head_loss(hp, c["h"],
                                                      la["labels"]),
            carry_proto=cp)
        # structural memory bound: the park buffer depth is decided by the
        # plan, before any tracing
        expect = ([min(pipe - j, m) for j in range(pipe)]
                  if schedule == "1f1b" else [m] * pipe)
        assert list(tplan.per_stage_stash) == expect, tplan.per_stage_stash
        @jax.jit
        def fused(p, b):
            fresh, evjp = jax.vjp(
                lambda e: model.embed_inputs(e, b), p["embed"])
            head_ps = {"head": p["head"], "embed": p["embed"]}
            loss, gs, gh, ig = pg(p["stages"], head_ps, microbatch(fresh, m),
                                  microbatch({"labels": b["labels"]}, m))
            (ge,) = evjp(unmicrobatch(ig))
            ge = jax.tree.map(jnp.add, ge, gh["embed"])
            return loss, {"embed": ge, "stages": gs, "head": gh["head"]}
        loss, grads = fused(params, batch)
        return np.asarray(loss), jax.tree.map(np.asarray, grads)

for pipe, m, data in [(1, 4, 1), (2, 4, 1), (2, 8, 2), (4, 4, 1), (4, 8, 2)]:
    l_t, g_t = loss_and_grads("gpipe_tasked", pipe, m, data)
    l_f, g_f = loss_and_grads("1f1b", pipe, m, data)
    # 1F1B vs GPipe through the fused scheduler: bitwise identical
    assert np.array_equal(l_t, l_f), (pipe, m, data, l_t, l_f)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_t)[0],
                            jax.tree_util.tree_leaves(g_f)):
        assert np.array_equal(a, b), (pipe, m, data, path)
    # fused gpipe vs legacy autodiff gpipe: same math, different graph
    l_r, g_r = loss_and_grads("gpipe", pipe, m, data)
    np.testing.assert_allclose(l_t, l_r, rtol=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_r)[0],
                            jax.tree_util.tree_leaves(g_t)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                                   err_msg=f"{(pipe, m, data)} {path}")
    print("grid point OK", pipe, m, data)
print("SCHEDULE EXEC EQUIV OK")
"""


def test_1f1b_equals_gpipe_bitwise_and_legacy_close():
    out = run_subprocess(EXEC_GRID, n_devices=8, timeout=1800)
    assert "SCHEDULE EXEC EQUIV OK" in out


TRAIN_1F1B = """
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro import configs
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib, steps
from repro.models.lm import LMModel
from repro.optim import optimizers as optim

arch = configs.smoke_arch("smollm-360m")
for schedule in ("1f1b", "zb", "interleaved:2"):
    pcfg = ParallelConfig(pipe=4, tp=1, data=2, pod=1, n_micro=4,
                          schedule=schedule)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")
    params = model.init(jax.random.PRNGKey(0))
    ocfg = optim.OptimizerConfig(lr=2e-3, warmup_steps=2, total_steps=20)
    opt = optim.init(ocfg, params)
    with set_mesh(mesh):
        step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape,
                                              ocfg))
        batch = {k: jax.random.randint(jax.random.PRNGKey(1), v.shape, 0,
                                       arch.vocab)
                 for k, v in model.input_specs(shape).items()}
        losses = []
        for _ in range(6):
            params, opt, metrics = step(params, opt, batch)
            losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses)), (schedule, losses)
    assert losses[-1] < losses[0] * 0.9, (schedule, losses)
    print("TRAIN OK", schedule, losses[0], "->", losses[-1])
print("ALL TRAIN OK")
"""


def test_fused_train_loops_converge():
    """End-to-end: schedule="1f1b" / "zb" / "interleaved:2" through
    build_train_step memorize a fixed batch on an 8-device mesh
    (pipeline + DP + AdamW)."""
    out = run_subprocess(TRAIN_1F1B, n_devices=8, timeout=1500)
    assert "ALL TRAIN OK" in out
