"""End-to-end driver example: ~100M-param model, multi-stage pipeline, a few
hundred steps with checkpoint/restart (deliverable (b): the train driver).

Runs a REAL 4-stage x 2-way-data pipeline on 8 XLA host devices — the same
execution path as the production mesh, scaled to this container.

    PYTHONPATH=src python examples/train_pipeline_parallel.py [--steps 200]
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import argparse

import jax
import jax.numpy as jnp
from jax import set_mesh

from repro import configs
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import ArchConfig, AttentionConfig, ParallelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib, steps
from repro.launch.cache import enable_compile_cache
from repro.models.lm import LMModel
from repro.optim import optimizers as optim
from repro.planner import HardwareSpec
from repro.runtime.fault_tolerance import Supervisor, StepWatchdog

# ~100M params: a 12-layer, d=512 llama-style decoder with a 32k vocab
ARCH = ArchConfig(
    name="demo-100m", family="dense",
    n_layers=12, d_model=512, d_ff=2048, vocab=32000,
    attn=AttentionConfig(n_heads=8, n_kv_heads=4, head_dim=64),
    act="silu", norm="rms")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_demo_ckpt")
    args = ap.parse_args()

    shape = ShapeConfig("train", args.seq_len, args.batch, "train")
    # the planner picks schedule / n_micro / residuals / executor /
    # partition against the hardware description; remaining knobs (data
    # parallelism, remat policy, portals) pass through as overrides.
    # executors=("spmd",): on emulated host-CPU devices the mpmd leg's
    # per-rank specialized compilation is not worth it
    pcfg = ParallelConfig.auto(
        ARCH, shape,
        HardwareSpec(name="demo-4", ranks=4, memory_bytes=4.0 * 2**30),
        executors=("spmd",), data=2, remat="full", portals=True)
    print(f"planned: schedule={pcfg.schedule} m={pcfg.n_micro} "
          f"residuals={pcfg.residuals} executor={pcfg.executor}")
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(ARCH, pcfg, dtype=jnp.float32)
    ocfg = optim.OptimizerConfig(lr=3e-4, warmup_steps=20,
                                 total_steps=args.steps)
    data = SyntheticLM(DataConfig(vocab=ARCH.vocab, seq_len=args.seq_len,
                                  global_batch=args.batch))
    print(f"model: {ARCH.total_params()/1e6:.0f}M params over "
          f"{pcfg.pipe} pipeline stages x {pcfg.data}-way data parallel, "
          f"m={pcfg.n_micro} micro-batches")

    with set_mesh(mesh):
        jstep = jax.jit(steps.build_train_step(model, pcfg, mesh, shape, ocfg))

    def make_state(restored):
        if restored is not None:
            return restored
        p = model.init(jax.random.PRNGKey(0))
        return {"params": p, "opt": optim.init(ocfg, p)}

    def step_fn(state, i):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        with set_mesh(mesh):
            p, o, m = jstep(state["params"], state["opt"], batch)
        if i % 10 == 0:
            print(f"step {i:4d}  loss {float(m['loss']):.4f}  "
                  f"lr {float(m['lr']):.2e}")
        return {"params": p, "opt": o}, {"loss": float(m["loss"])}

    sup = Supervisor(ckpt=CheckpointManager(args.ckpt_dir, keep=2),
                     make_state=make_state, step_fn=step_fn,
                     ckpt_every=50, watchdog=StepWatchdog())
    out = sup.run(args.steps)
    hist = [h["loss"] for h in out["history"]]
    print(f"done: loss {hist[0]:.4f} -> {hist[-1]:.4f}")


if __name__ == "__main__":
    main()
