"""Serving driver: batched prefill + pipelined decode loop.

Both phases execute forward-only plans on the unified schedule runtime
(``run_pipeline_tasks`` via ``pipeline_call``): the resident KV caches are
plan events — read and updated only on each rank's scheduled F ticks, per
micro-batch slot — rather than tick-loop special cases.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m --smoke \\
        --prompt-len 32 --gen 16 --batch 4
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
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--pipe", type=int, default=0,
                    help="pipe degree (default: 1 with --smoke, else every "
                         "device)")
    ap.add_argument("--data", type=int, default=0,
                    help="data-parallel degree (default 1)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        arch = configs.smoke_arch(args.arch)
        pcfg = configs.smoke_parallel(args.arch).with_(
            pipe=args.pipe or 1, data=args.data or 1)
        dtype = jnp.float32
    else:
        arch = configs.get_arch(args.arch)
        pcfg = mesh_lib.fit_local(configs.get_parallel(args.arch),
                                  pipe=args.pipe, data=args.data)
        dtype = jnp.bfloat16
    mesh = mesh_lib.make_smoke_mesh(pcfg)

    max_len = args.prompt_len + args.gen
    pshape = ShapeConfig("prefill", args.prompt_len, args.batch, "prefill")
    dshape = ShapeConfig("decode", max_len, args.batch, "decode")
    pcfg = pcfg.with_(n_micro=configs.derive_n_micro(pshape, pcfg))
    model = LMModel(arch, pcfg, dtype=dtype)
    params = model.init(jax.random.PRNGKey(0))

    with set_mesh(mesh):
        prefill = jax.jit(steps.build_prefill_step(model, pcfg, mesh, pshape))
        decode = jax.jit(steps.build_serve_step(model, pcfg, mesh, dshape))
        cache = model.init_cache(dshape, pcfg.n_micro, filled=False)

        key = jax.random.PRNGKey(1)
        prompts = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, arch.vocab)
        batch = {"tokens": prompts}
        if arch.is_encdec:
            batch = {"frames": jax.random.normal(
                key, (args.batch, args.prompt_len, arch.d_model)) * 0.1,
                "dec_tokens": prompts}
        if arch.frontend == "vision_stub":
            batch["patches"] = jax.random.normal(
                key, (args.batch, 256, arch.d_model)).astype(dtype) * 0.1

        t0 = time.perf_counter()
        logits, cache = prefill(params, cache, batch)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0
        print(f"[serve] prefill {args.batch}x{args.prompt_len} "
              f"in {t_prefill:.3f}s")

        tokens = jnp.argmax(logits, -1).astype(jnp.int32)
        generated = [tokens]
        t0 = time.perf_counter()
        for i in range(args.gen - 1):
            logits, cache = decode(params, cache, tokens)
            if args.temperature > 0:
                key, sub = jax.random.split(key)
                tokens = jax.random.categorical(
                    sub, logits[:, 0] / args.temperature)[:, None]
                tokens = tokens.astype(jnp.int32)
            else:
                tokens = jnp.argmax(logits, -1).astype(jnp.int32)
            generated.append(tokens)
        jax.block_until_ready(tokens)
        dt = time.perf_counter() - t0
        toks = np.concatenate([np.asarray(t) for t in generated], axis=1)
        print(f"[serve] decoded {args.gen - 1} steps x {args.batch} seqs in "
              f"{dt:.3f}s ({(args.gen - 1) * args.batch / max(dt, 1e-9):.1f} tok/s)")
        print(f"[serve] sample tokens: {toks[0][:12].tolist()}")
        assert np.isfinite(np.asarray(logits)).all()


if __name__ == "__main__":
    main()
