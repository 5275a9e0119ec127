"""The framework's core guarantee: the pipelined computation (any pipe/m/
data split, with checkpointing and portals) computes EXACTLY the same loss
and gradients as plain sequential execution.

These run in subprocesses with 8 XLA host devices (the main test process
must keep seeing 1 device per the assignment).
"""
import pytest

from conftest import run_subprocess

EQUIV_TEMPLATE = """
import zlib, dataclasses
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro import configs
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.core.pipeline import (pipeline_call, microbatch,
                                 last_stage_output, unmicrobatch)

name = {name!r}
arch = configs.smoke_arch(name)
shape = ShapeConfig("t", seq_len=16, global_batch=8, kind="train")
key = jax.random.PRNGKey(0)

def run(pcfg):
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    with set_mesh(mesh):
        consts = model.consts()
        mbg = shape.global_batch // pcfg.n_micro
        pipe = pipeline_call(
            model.make_stage_apply(consts), mesh=mesh, cfg=pcfg,
            skips=model.skips(),
            skip_protos=model.skip_protos(mbg, shape.seq_len),
            carry_proto={{"h": jax.ShapeDtypeStruct(
                (mbg, shape.seq_len, arch.d_model), jnp.float32)}})
        def loss_fn(p, batch):
            fresh = model.embed_inputs(p["embed"], batch)
            outs, _ = pipe(p["stages"], microbatch(fresh, pcfg.n_micro), None)
            h = unmicrobatch(last_stage_output(outs)["h"])
            return model.head_loss(p, h, batch["labels"])
        batch = {{}}
        for k, v in model.input_specs(shape).items():
            kk = jax.random.fold_in(key, zlib.crc32(k.encode()) % 1000)
            batch[k] = (jax.random.randint(kk, v.shape, 0, arch.vocab)
                        if v.dtype == jnp.int32
                        else jax.random.normal(kk, v.shape, v.dtype) * 0.1)
        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        return np.asarray(loss), jax.tree.map(np.asarray, grads)

l_ref, g_ref = run(ParallelConfig(pipe=1, tp=1, data=1, pod=1, n_micro=1,
                                  remat="none", portals={portals}))
l_pp, g_pp = run(ParallelConfig(pipe=4, tp=1, data=2, pod=1, n_micro=4,
                                remat={remat!r}, portals={portals},
                                overlap={overlap}))
np.testing.assert_allclose(l_ref, l_pp, rtol=2e-5)
ref_leaves = jax.tree_util.tree_flatten_with_path(g_ref)[0]
pp_leaves = jax.tree_util.tree_leaves(g_pp)
for (path, a), b in zip(ref_leaves, pp_leaves):
    if a.ndim >= 2 and a.shape[:2] != b.shape[:2]:
        a = a.reshape((-1,) + a.shape[2:])
        b = b.reshape((-1,) + b.shape[2:])
        nmin = min(a.shape[0], b.shape[0])
        if b.shape[0] > nmin:
            assert np.abs(b[nmin:]).max() == 0.0, \\
                f"identity-pad layers must get zero grads: {{path}}"
        a, b = a[:nmin], b[:nmin]
    np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                               err_msg=str(path))
print("EQUIV OK", name)
"""


@pytest.mark.parametrize("name,remat,portals,overlap", [
    ("smollm-360m", "full", True, True),     # dense + remat
    ("smollm-360m", "none", True, True),     # no checkpointing
    ("smollm-360m", "dots", True, False),    # policy remat + no-overlap path
    ("whisper-tiny", "full", True, True),    # enc-dec through PORTALS
    ("whisper-tiny", "full", False, True),   # enc-dec THREADED (paper §3.3)
    ("mixtral-8x7b", "full", True, True),    # MoE + SWA
    ("rwkv6-1.6b", "full", True, True),      # attention-free recurrence
    ("hymba-1.5b", "full", True, True),      # hybrid attn+SSM, mixed windows
])
def test_pipeline_equals_sequential(name, remat, portals, overlap):
    run_subprocess(EQUIV_TEMPLATE.format(name=name, remat=remat,
                                         portals=portals, overlap=overlap),
                   n_devices=8, timeout=900)


TRAIN_LOOP = """
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro import configs
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib, steps, sharding
from repro.models.lm import LMModel
from repro.optim import optimizers as optim

arch = configs.smoke_arch("deepseek-7b")
pcfg = ParallelConfig(pipe=2, tp=2, data=2, pod=1, n_micro=2)
mesh = mesh_lib.make_smoke_mesh(pcfg)
model = LMModel(arch, pcfg, dtype=jnp.float32)
shape = ShapeConfig("t", seq_len=16, global_batch=8, kind="train")
params = model.init(jax.random.PRNGKey(0))
ocfg = optim.OptimizerConfig(lr=2e-3, warmup_steps=2, total_steps=20)
opt = optim.init(ocfg, params)
with set_mesh(mesh):
    step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape, ocfg))
    batch = {}
    key = jax.random.PRNGKey(1)
    for k, v in model.input_specs(shape).items():
        batch[k] = jax.random.randint(key, v.shape, 0, arch.vocab)
    losses = []
    for _ in range(6):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
assert all(np.isfinite(losses)), losses
assert losses[-1] < losses[0] * 0.9, losses
print("SHARDED TRAIN OK", losses[0], "->", losses[-1])
"""


def test_sharded_train_loop_converges():
    """Full train step (pipeline + FSDP + TP + DP + AdamW) on an 8-device
    mesh memorizes a fixed batch."""
    run_subprocess(TRAIN_LOOP, n_devices=8, timeout=900)
