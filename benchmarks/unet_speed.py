"""Paper Table 4 analogue: U-Net throughput vs pipeline width.

Naive-1 = no pipeline, no checkpointing (single device); Pipeline-n =
torchgpipe-style with n stages, batch/m chosen per column as in the paper.
Scaled-down (B, C) and image for host-device execution; the trend (single-
stage pipelining costs ~15%, wider pipelines win) is the reproduction
target, exact numbers are hardware-specific.
"""
import json

BENCH = """
import time, json, sys, types
import jax, jax.numpy as jnp
from jax import set_mesh
_m = types.ModuleType("benchmarks_schedule_model")
def _schedule_time(costs, sizes, m, remat=True):
    # per-SAMPLE critical path: ticks (m+n-1) x per-sample tick cost
    # (fwd max-stage + bwd max-stage x (2 + recompute)), amortized over m.
    bounds = [0]
    for s in sizes: bounds.append(bounds[-1] + s)
    stage = [sum(costs[bounds[j]:bounds[j+1]]) for j in range(len(sizes))]
    nn = len([s for s in sizes if s > 0])
    per_tick = max(stage) * (1.0 + (3.0 if remat else 2.0))
    return (m + nn - 1) / m * per_tick
def _sequential_time(costs, m):
    return sum(costs) * 3.0   # per sample, fwd + bwd, no recompute
_m.schedule_time = _schedule_time
_m.sequential_time = _sequential_time
sys.modules["benchmarks_schedule_model"] = _m
from repro.configs.base import ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.unet import UNetConfig, UNetModel
from repro.models import pipeline_hetero as PH

cfg = UNetConfig(B={B}, C={C}, levels=4, img={img})
n, m, B_GLOBAL = {n}, {m}, {batch}
remat = "none" if n == 0 else "full"
pipe = max(n, 1)
pcfg = ParallelConfig(pipe=pipe, tp=1, data=1, pod=1, n_micro=m, remat=remat)
mesh = mesh_lib.make_smoke_mesh(pcfg)
model = UNetModel(cfg, pcfg.pipe)
params = model.init(jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (B_GLOBAL, cfg.img, cfg.img, 3))
y = jax.random.normal(jax.random.PRNGKey(2), (B_GLOBAL, cfg.img, cfg.img, 1))
prog = PH.build_hetero_program(model, params, B_GLOBAL // m, pcfg, x[:2])
with set_mesh(mesh):
    def loss(p, xx, yy):
        prog2 = PH.HeteroProgram(p, prog.stage_apply, prog.carry_proto,
                                 prog.skips, prog.skip_protos, prog.out_proto)
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
from benchmarks_schedule_model import schedule_time, sequential_time
costs = [l.flops() for l in model.layers]
pred = (sequential_time(costs, m) if {n} == 0
        else schedule_time(costs, model.sizes, m))
print("RESULT " + json.dumps(dict(n={n}, m=m, samples_per_s=B_GLOBAL/dt,
                                  step_s=dt, pred_t=pred)))
"""

# (n, m, batch): n=0 encodes Naive-1 (no pipeline, no checkpointing)
COLUMNS = [(0, 1, 8), (1, 2, 16), (2, 8, 16), (4, 8, 16), (8, 16, 32)]


def run(B=1, C=8, img=64, columns=COLUMNS):
    from benchmarks.util import run_with_devices
    rows = []
    for n, m, batch in columns:
        txt = run_with_devices(
            BENCH.format(B=B, C=C, img=img, n=n, m=m, batch=batch),
            max(n, 2), timeout=2400)
        for line in txt.splitlines():
            if line.startswith("RESULT "):
                rows.append(json.loads(line[len("RESULT "):]))
    return rows


def main(columns=COLUMNS):
    rows = run(columns=columns)
    base = rows[0]["samples_per_s"]
    print("name,us_per_call,derived")
    for r in rows:
        tag = "naive-1" if r["n"] == 0 else f"pipeline-{r['n']}"
        basep = rows[0]["pred_t"]
        print(f"unet_speed/{tag},{r['step_s']*1e6:.0f},"
              f"measured_1core={r['samples_per_s']/base:.3f};"
              f"predicted_speedup={basep/r['pred_t']:.2f};m={r['m']}")


if __name__ == "__main__":
    main()
