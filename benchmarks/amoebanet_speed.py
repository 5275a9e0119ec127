"""Paper Table 2 analogue: AmoebaNet-D throughput for m x n pipeline grids.

The paper trains AmoebaNet-D (18, 256) on 224x224 synthetic images with
plain SGD and reports relative throughput for m in {1, 4, 32}, n in
{2, 4, 8}, baseline (m, n) = (1, 2).  Hardware here is XLA host devices, so
the model is scaled down (L=9, F=32, img=64) but the schedule/bubble
behaviour being measured is shape-independent.  m=1 applies checkpointing
to the last (only) micro-batch, matching the paper's footnote-5 comparison.
"""
import json

BENCH = """
import time, json, sys, types
import jax, jax.numpy as jnp
from jax import set_mesh
_m = types.ModuleType("benchmarks_schedule_model")
def _schedule_time(costs, sizes, m, remat=True):
    # per-SAMPLE critical path (see unet_speed).
    bounds = [0]
    for s in sizes: bounds.append(bounds[-1] + s)
    stage = [sum(costs[bounds[j]:bounds[j+1]]) for j in range(len(sizes))]
    n = len([s for s in sizes if s > 0])
    per_tick = max(stage) * (1.0 + (3.0 if remat else 2.0))
    return (m + n - 1) / m * per_tick
_m.schedule_time = _schedule_time
sys.modules["benchmarks_schedule_model"] = _m
from repro.configs.base import ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.amoebanet import AmoebaConfig, AmoebaNetModel
from repro.models import pipeline_hetero as PH

cfg = AmoebaConfig(L={L}, F={F}, img={img}, n_classes=100)
m, n = {m}, {n}
B_GLOBAL = max(16, m * 2)
pcfg = ParallelConfig(pipe=n, tp=1, data=1, pod=1, n_micro=m, remat="full",
                      remat_last_micro=(m == 1))
mesh = mesh_lib.make_smoke_mesh(pcfg)
model = AmoebaNetModel(cfg, pcfg.pipe)
params = model.init(jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (B_GLOBAL, cfg.img, cfg.img, 3))
labels = jax.random.randint(jax.random.PRNGKey(2), (B_GLOBAL,), 0, 100)
prog = PH.build_hetero_program(model, params, B_GLOBAL // m, pcfg, x[:2])
with set_mesh(mesh):
    def loss(p, xx, yy):
        prog2 = PH.HeteroProgram(p, prog.stage_apply, prog.carry_proto,
                                 prog.skips, prog.skip_protos, prog.out_proto)
        logits = PH.hetero_forward(prog2, mesh, pcfg, xx)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        return -jnp.take_along_axis(logp, yy[:, None], 1).mean()
    step = jax.jit(jax.grad(loss))
    g = step(prog.stacked_params, x, labels)
    jax.block_until_ready(g)
    t0 = time.perf_counter()
    for _ in range(3):
        g = step(prog.stacked_params, x, labels)
    jax.block_until_ready(g)
    dt = (time.perf_counter() - t0) / 3
costs = [c.flops() for c in model.layers]
from benchmarks_schedule_model import schedule_time  # injected below
print("RESULT " + json.dumps(dict(m=m, n=n, samples_per_s=B_GLOBAL/dt,
                                  step_s=dt,
                                  pred_t=schedule_time(costs, model.sizes, m))))
"""


def run(L=9, F=32, img=64, grid=((1, 2), (4, 2), (32, 2),
                                 (1, 4), (4, 4), (32, 4),
                                 (1, 8), (4, 8), (32, 8))):
    from benchmarks.util import run_with_devices
    rows = []
    for m, n in grid:
        out = run_with_devices(BENCH.format(L=L, F=F, img=img, m=m, n=n),
                               max(n, 2), timeout=2400)
        for line in out.splitlines():
            if line.startswith("RESULT "):
                rows.append(json.loads(line[len("RESULT "):]))
    return rows


def main(grid=None):
    rows = run(**({"grid": grid} if grid else {}))
    base = next(r for r in rows if (r["m"], r["n"]) == (1, 2))["samples_per_s"]
    print("name,us_per_call,derived")
    for r in rows:
        basep = next(x for x in rows if (x["m"], x["n"]) == (1, 2))["pred_t"]
        print(f"amoebanet/m{r['m']}_n{r['n']},{r['step_s']*1e6:.0f},"
              f"measured_1core={r['samples_per_s']/base:.3f};"
              f"predicted_speedup={basep/r['pred_t']:.2f}")


if __name__ == "__main__":
    main()
