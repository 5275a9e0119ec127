"""Paper Table 1 analogue: optimization components added incrementally.

torchgpipe's ablation (U-Net, 4 partitions, m=8) toggles [backward
dependency, copy streams, portals].  Under XLA the backward dependency (C2)
is structural — DESIGN.md §2 — so the measurable axes here are:

  row 0  baseline      serialized comm (optimization_barrier between compute
                       and sends = the "default stream" behaviour), skips
                       threaded through every stage, no checkpointing
  row 1  +checkpoint   per-(i,j) remat (GPipe memory behaviour)
  row 2  +overlap      async sends (copy-stream analogue)
  row 3  +portals      direct skip routing (thinner boundary buffers)

Reported per row: wall-clock throughput on an 8-host-device pipeline (n=4,
data=2), per-device compiled memory, and collective-permute link bytes from
the compiled HLO (the quantity Fig. 7's red bars visualize).
"""
import json

BENCH = """
import time, json
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro.configs.base import ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.unet import UNetConfig, UNetModel
from repro.models import pipeline_hetero as PH
from repro.roofline import analysis as RA

cfg = UNetConfig(B={B}, C={C}, levels=4, img={img})
B_GLOBAL = 16
rows = []
for name, kw in [
    ("baseline", dict(overlap=False, portals=False, remat="none")),
    ("+checkpoint", dict(overlap=False, portals=False, remat="full")),
    ("+overlap", dict(overlap=True, portals=False, remat="full")),
    ("+portals", dict(overlap=True, portals=True, remat="full")),
]:
    pcfg = ParallelConfig(pipe=4, tp=1, data=2, pod=1, n_micro=8, **kw)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = UNetModel(cfg, pcfg.pipe)
    params = model.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1),
                          (B_GLOBAL, cfg.img, cfg.img, cfg.in_ch))
    y = jax.random.normal(jax.random.PRNGKey(2),
                          (B_GLOBAL, cfg.img, cfg.img, cfg.out_ch))
    prog = PH.build_hetero_program(model, params,
                                   B_GLOBAL // pcfg.n_micro, pcfg, x[:2])
    with set_mesh(mesh):
        def loss(p, xx, yy):
            import repro.models.pipeline_hetero as P2
            prog2 = PH.HeteroProgram(p, prog.stage_apply, prog.carry_proto,
                                     prog.skips, prog.skip_protos,
                                     prog.out_proto)
            out = PH.hetero_forward(prog2, mesh, pcfg, xx)
            return jnp.mean((out - yy) ** 2)
        step = jax.jit(jax.grad(loss))
        g = step(prog.stacked_params, x, y)
        jax.block_until_ready(g)
        t0 = time.perf_counter()
        for _ in range(3):
            g = step(prog.stacked_params, x, y)
        jax.block_until_ready(g)
        dt = (time.perf_counter() - t0) / 3
        co = step.lower(prog.stacked_params, x, y).compile()
        mem = co.memory_analysis()
        cost = RA.analyze_hlo(co.as_text(), mesh.size)
    rows.append(dict(name=name, samples_per_s=B_GLOBAL / dt,
                     step_s=dt,
                     temp_gib=mem.temp_size_in_bytes / 2**30,
                     permute_bytes=cost.coll_link_bytes.get(
                         "collective-permute", 0.0)))
print("RESULT " + json.dumps(rows))
"""


def run(B=1, C=8, img=64):
    from benchmarks.util import run_with_devices
    out = run_with_devices(BENCH.format(B=B, C=C, img=img), 8, timeout=2400)
    for line in out.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise RuntimeError(f"no result in output:\n{out[-2000:]}")


def main():
    rows = run()
    base = rows[0]["samples_per_s"]
    print("name,us_per_call,derived")
    for r in rows:
        speedup = r["samples_per_s"] / base
        print(f"ablation/{r['name']},{r['step_s']*1e6:.0f},"
              f"speedup={speedup:.3f};mem_gib={r['temp_gib']:.3f};"
              f"permute_bytes={r['permute_bytes']:.3e}")


if __name__ == "__main__":
    main()
