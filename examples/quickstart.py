"""Quickstart: GPipe micro-batch pipeline parallelism in ~40 lines.

Builds a small llama-style LM, asks the automatic planner for the
pipeline config (`ParallelConfig.auto` — schedule, microbatch count,
executor, and partition all chosen by the device model against the
hardware description), and trains a few steps on synthetic data.

    PYTHONPATH=src python examples/quickstart.py
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax
import jax.numpy as jnp
from jax import set_mesh

from repro import configs
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib, steps
from repro.launch.cache import enable_compile_cache
from repro.models.lm import LMModel
from repro.optim import optimizers as optim
from repro.planner import HardwareSpec


def main():
    enable_compile_cache()
    arch = configs.smoke_arch("smollm-360m")   # reduced dims, same family
    shape = ShapeConfig("train", seq_len=32, global_batch=8, kind="train")
    # one planner call replaces the manual five-knob dance (schedule,
    # n_micro, residuals, executor, partition); hardware.yaml in the repo
    # root shows the full schema for real slices
    hw = HardwareSpec(name="quickstart", ranks=len(jax.devices()),
                      memory_bytes=2.0 * 2**30)
    pcfg = ParallelConfig.auto(arch, shape, hw)
    print(f"planned: pipe={pcfg.pipe} schedule={pcfg.schedule} "
          f"m={pcfg.n_micro} executor={pcfg.executor}")
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)

    params = model.init(jax.random.PRNGKey(0))
    ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=5, total_steps=30)
    opt = optim.init(ocfg, params)
    data = SyntheticLM(DataConfig(vocab=arch.vocab, seq_len=32,
                                  global_batch=8))

    with set_mesh(mesh):
        train_step = jax.jit(
            steps.build_train_step(model, pcfg, mesh, shape, ocfg),
            donate_argnums=(0, 1))
        for i in range(10):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
            params, opt, metrics = train_step(params, opt, batch)
            print(f"step {i}: loss {float(metrics['loss']):.4f}")


if __name__ == "__main__":
    main()
