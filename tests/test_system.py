"""End-to-end system behaviour: the full stack (data pipeline -> pipelined
train step -> optimizer -> async checkpoint -> preemption -> restart)
integrated, on a reduced model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import set_mesh
from repro import configs
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib, steps
from repro.models.lm import LMModel
from repro.optim import optimizers as optim
from repro.runtime.fault_tolerance import FaultInjector, Supervisor


def _build(name="smollm-360m"):
    arch = configs.smoke_arch(name)
    pcfg = configs.smoke_parallel(name)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
    ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=40)
    data = SyntheticLM(DataConfig(seed=11, vocab=arch.vocab, seq_len=16,
                                  global_batch=4))
    with set_mesh(mesh):
        step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape, ocfg))
    return model, ocfg, data, step, mesh


def test_train_ckpt_preempt_restart_is_exact(tmp_path):
    """A run preempted twice must reach the SAME final params as a clean
    run: batches are pure functions of step, checkpoints commit atomically,
    and the supervisor resumes at the right step."""
    model, ocfg, data, step, mesh = _build()

    def make_runner(ckpt_dir, faults):
        mgr = CheckpointManager(str(ckpt_dir), async_write=False)

        def make_state(restored):
            if restored is not None:
                return restored
            params = model.init(jax.random.PRNGKey(0))
            return {"params": params, "opt": optim.init(ocfg, params)}

        def step_fn(state, i):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            with set_mesh(mesh):
                p, o, m = step(state["params"], state["opt"], batch)
            return {"params": p, "opt": o}, {"loss": float(m["loss"])}

        return Supervisor(ckpt=mgr, make_state=make_state, step_fn=step_fn,
                          ckpt_every=3,
                          injector=FaultInjector(fail_at_steps=faults))

    clean = make_runner(tmp_path / "clean", ()).run(10)
    faulty = make_runner(tmp_path / "faulty", (4, 8)).run(10)
    assert faulty["restarts"] == 2
    for a, b in zip(jax.tree.leaves(clean["state"]["params"]),
                    jax.tree.leaves(faulty["state"]["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)
    # all steps executed; replayed steps (since last commit) are expected
    steps_seen = [h["step"] for h in faulty["history"]]
    assert set(steps_seen) == set(range(10))
    assert steps_seen[-1] == 9


def test_loss_decreases_over_fixed_batch():
    model, ocfg, data, step, mesh = _build("gemma-2b")
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.init(ocfg, params)
    losses = []
    with set_mesh(mesh):
        for i in range(8):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_elastic_restack_preserves_function():
    """Re-partition trained stage weights to a different pipe degree (lost
    devices); the model function must be identical (same loss).  Runs in a
    subprocess with 8 host devices (the shrunken mesh needs >1 device)."""
    from conftest import run_subprocess
    run_subprocess("""
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.configs.base import ShapeConfig
from jax import set_mesh
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.runtime import elastic
from repro.core.pipeline import (pipeline_call, microbatch,
                                 last_stage_output, unmicrobatch)

name = "deepseek-7b"
arch = configs.smoke_arch(name)
shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
data = SyntheticLM(DataConfig(seed=5, vocab=arch.vocab, seq_len=16,
                              global_batch=4))
batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}

def loss_with(pcfg, params):
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    with set_mesh(mesh):
        consts = model.consts()
        mbg = shape.global_batch // pcfg.n_micro
        pipe = pipeline_call(
            model.make_stage_apply(consts), mesh=mesh, cfg=pcfg,
            carry_proto={"h": jax.ShapeDtypeStruct(
                (mbg, 16, arch.d_model), jnp.float32)})
        @jax.jit
        def loss(params, batch):
            fresh = model.embed_inputs(params["embed"], batch)
            outs, _ = pipe(params["stages"],
                           microbatch(fresh, pcfg.n_micro), None)
            h = unmicrobatch(last_stage_output(outs)["h"])
            return model.head_loss(params, h, batch["labels"])
        return float(loss(params, batch))

# train-time layout: 4 stages; "failure" shrinks the pool to 2 stages
p1 = configs.smoke_parallel(name).with_(pipe=4, n_micro=2)
model1 = LMModel(arch, p1, dtype=jnp.float32)
params = model1.init(jax.random.PRNGKey(0))
l1 = loss_with(p1, params)
new_layout = elastic.choose_layout(2, p1)
assert new_layout.pipe == 2
restacked, _ = elastic.restack_stages(params["stages"], model1.layer_mask,
                                      new_layout.pipe)
l2 = loss_with(new_layout.with_(n_micro=2), dict(params, stages=restacked))
np.testing.assert_allclose(l1, l2, rtol=2e-5)
print("ELASTIC OK", l1, l2)
""", n_devices=8, timeout=600)


CACHE_PROBE = """
import os
if {env!r}:
    os.environ["JAX_COMPILATION_CACHE_DIR"] = {env!r}
else:
    os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax, jax.numpy as jnp
from repro.launch.cache import enable_compile_cache
path = enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
x = jnp.ones(3)
before = set(os.listdir(path)) if os.path.isdir(path) else set()
# a compile no earlier run can have cached: the constant is new
jax.jit(lambda x: jnp.sin(x) * len({salt!r}) + hash({salt!r}) % 97)(x)
print("CACHE", path, bool(set(os.listdir(path)) - before))
"""


@pytest.mark.parametrize("where", ["env", "checkout"])
def test_compile_cache_location(tmp_path, where):
    """Entry points cache compiles in $JAX_COMPILATION_CACHE_DIR when set,
    else in the checkout's fixed, git-ignored ``.jax_cache/``."""
    import os
    from conftest import run_subprocess
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    env = str(tmp_path / "cc") if where == "env" else ""
    out = run_subprocess(CACHE_PROBE.format(env=env, salt=str(tmp_path)),
                         n_devices=1, timeout=300)
    want = env or os.path.join(root, ".jax_cache")
    assert f"CACHE {want} True" in out, out
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
