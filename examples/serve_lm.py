"""Serving example: batched prefill + pipelined greedy decode with KV caches.

    PYTHONPATH=src python examples/serve_lm.py [--arch mixtral-8x7b]

Uses the reduced config of the chosen architecture (MoE routing, SWA ring
caches, RWKV state, hybrid SSM state — whatever the family needs — all flow
through the same pipeline serve path).
"""
import argparse
import time

import jax
import jax.numpy as jnp
from jax import set_mesh
import numpy as np

from repro import configs
from repro.configs.base import ShapeConfig
from repro.launch import mesh as mesh_lib, steps
from repro.launch.cache import enable_compile_cache
from repro.models.lm import LMModel


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x7b",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--gen", type=int, default=12)
    args = ap.parse_args()

    arch = configs.smoke_arch(args.arch)
    pcfg = configs.smoke_parallel(args.arch)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0))

    pshape = ShapeConfig("p", args.prompt_len, args.batch, "prefill")
    dshape = ShapeConfig("d", args.prompt_len + args.gen, args.batch,
                         "decode")
    with set_mesh(mesh):
        prefill = jax.jit(steps.build_prefill_step(model, pcfg, mesh, pshape))
        decode = jax.jit(steps.build_serve_step(model, pcfg, mesh, dshape))
        cache = model.init_cache(dshape, pcfg.n_micro, filled=False)
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 0, arch.vocab)}
        if arch.is_encdec:
            batch = {"frames": jax.random.normal(
                key, (args.batch, args.prompt_len, arch.d_model)) * 0.1,
                "dec_tokens": batch["tokens"]}
        if arch.frontend == "vision_stub":
            batch["patches"] = jax.random.normal(
                key, (args.batch, 256, arch.d_model)) * 0.1

        logits, cache = prefill(params, cache, batch)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out = [tok]
        t0 = time.perf_counter()
        for _ in range(args.gen - 1):
            logits, cache = decode(params, cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(tok)
        toks = np.concatenate([np.asarray(t) for t in out], 1)
        print(f"{arch.name}: generated {toks.shape} tokens in "
              f"{time.perf_counter()-t0:.2f}s; sample: {toks[0].tolist()}")


if __name__ == "__main__":
    main()
