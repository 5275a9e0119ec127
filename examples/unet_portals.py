"""Paper §3.3 in miniature: a pipelined U-Net whose long skip connections
are routed either THROUGH every intermediate stage (the symptomatic case)
or DIRECTLY via portals, verifying identical outputs and printing the
collective traffic of each compiled program.

Both modes lower to skip ROUTES in the unified schedule executor
(``run_pipeline_tasks``): the forward A/B runs a forward-only GPipe plan,
and the final section trains the portal model through the fused F+B
schedules — GPipe-tasked and 1F1B produce bitwise-identical losses and
gradients with the skip cotangents travelling the reverse routes.

    PYTHONPATH=src python examples/unet_portals.py
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
from jax import set_mesh
import numpy as np

from repro.configs.base import ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.launch.cache import enable_compile_cache
from repro.models import pipeline_hetero as PH
from repro.models.unet import UNetConfig, UNetModel
from repro.roofline import analysis as RA


def main():
    enable_compile_cache()
    cfg = UNetConfig(B=1, C=8, levels=4, img=64)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.img, cfg.img, 3))
    outs = {}
    for portals in (False, True):
        pcfg = ParallelConfig(pipe=4, tp=1, data=2, pod=1, n_micro=4,
                              portals=portals, remat="full")
        mesh = mesh_lib.make_smoke_mesh(pcfg)
        model = UNetModel(cfg, pcfg.pipe)
        params = model.init(jax.random.PRNGKey(0))
        prog = PH.build_hetero_program(model, params, 8 // pcfg.n_micro,
                                       pcfg, x[:2])
        with set_mesh(mesh):
            fwd = jax.jit(lambda xx: PH.hetero_forward(prog, mesh, pcfg, xx))
            y = fwd(x)
            cost = RA.analyze_hlo(fwd.lower(x).compile().as_text(), mesh.size)
        outs[portals] = np.asarray(y)
        mode = "portals " if portals else "threaded"
        print(f"{mode}: skip edges "
              f"{[(e.name, e.src_stage, e.dsts) for e in prog.skips]}, "
              f"boundary buffer {prog.carry_proto['buf'].shape}, "
              f"permute link bytes {cost.coll_link_bytes.get('collective-permute', 0):.3e}")
    np.testing.assert_allclose(outs[False], outs[True], rtol=2e-4, atol=2e-4)
    print("outputs identical — portals change the routing, not the math")

    # --- fused F+B schedules over the portal model -----------------------
    grads = {}
    for schedule in ("gpipe_tasked", "1f1b"):
        pcfg = ParallelConfig(pipe=4, tp=1, data=2, pod=1, n_micro=4,
                              portals=True, remat="full", schedule=schedule)
        mesh = mesh_lib.make_smoke_mesh(pcfg)
        model = UNetModel(cfg, pcfg.pipe)
        params = model.init(jax.random.PRNGKey(0))
        prog = PH.build_hetero_program(model, params, 8 // pcfg.n_micro,
                                       pcfg, x[:2])
        with set_mesh(mesh):
            tgt = jnp.zeros((8,) + tuple(prog.out_proto.shape[1:]))
            call = jax.jit(PH.hetero_grad_call(prog, mesh, pcfg))
            loss, g = call(prog.stacked_params, x, tgt)
        grads[schedule] = np.asarray(g)
        print(f"{schedule:>12}: loss {float(loss):.6f}, "
              f"grad norm {float(jnp.linalg.norm(g)):.6f}")
    np.testing.assert_array_equal(grads["gpipe_tasked"], grads["1f1b"])
    print("fused schedules bitwise-identical through the skip portals")


if __name__ == "__main__":
    main()
