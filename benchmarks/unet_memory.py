"""Paper Table 3 analogue: largest U-Net that fits per pipeline width.

The paper grows (B, C) until n GPUs (22 GiB each) are occupied.  Here the
fit test is ``memory_analysis()`` of the compiled train step against a
proportionally scaled budget (1 GiB/device at quarter-scale C, img=96 —
the paper-scale ladder's fp32 host arrays exceed this container's RAM):
for each n we report the largest configuration whose per-device footprint
(params + grads + activations with checkpointing) fits — reproducing the
table's "more stages => superlinearly bigger model" trend under
rematerialization.
"""
import json

BENCH = """
import json
import jax, jax.numpy as jnp
from jax import set_mesh
from repro.configs.base import ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.unet import UNetConfig, UNetModel
from repro.models import pipeline_hetero as PH

n = {n}
BUDGET = 1 * 2**30
rows = []
for (B, C) in {ladder}:
    cfg = UNetConfig(B=B, C=C, levels=5, img=96)
    pcfg = ParallelConfig(pipe=n, tp=1, data=1, pod=1, n_micro=8,
                          remat="full")
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = UNetModel(cfg, pcfg.pipe)
    try:
        params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        params = jax.tree.map(
            lambda p: jnp.zeros(p.shape, p.dtype), params)
        x = jax.ShapeDtypeStruct((32, 96, 96, 3), jnp.float32)
        y = jax.ShapeDtypeStruct((32, 96, 96, 1), jnp.float32)
        prog = PH.build_hetero_program(model, params, 32 // 8, pcfg,
                                       jax.ShapeDtypeStruct((4, 96, 96, 3),
                                                            jnp.float32))
        with set_mesh(mesh):
            def loss(p, xx, yy):
                prog2 = PH.HeteroProgram(p, prog.stage_apply,
                                         prog.carry_proto, prog.skips,
                                         prog.skip_protos, prog.out_proto)
                out = PH.hetero_forward(prog2, mesh, pcfg, xx)
                return jnp.mean((out - yy) ** 2)
            co = jax.jit(jax.grad(loss)).lower(
                jax.eval_shape(lambda: prog.stacked_params), x, y).compile()
        mem = co.memory_analysis()
        per_dev = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                   + mem.output_size_in_bytes)
        rows.append(dict(B=B, C=C, params=model.total_params(),
                         per_dev_gib=per_dev / 2**30,
                         fits=bool(per_dev <= BUDGET)))
    except Exception as e:
        rows.append(dict(B=B, C=C, error=str(e)[:200]))
print("RESULT " + json.dumps(dict(n=n, rows=rows)))
"""

LADDER = [(2, 18), (6, 24), (12, 32), (20, 40)]


def run(ns=(1, 2, 4), ladder=LADDER):
    from benchmarks.util import run_with_devices
    out = []
    for n in ns:
        txt = run_with_devices(BENCH.format(n=n, ladder=list(ladder)),
                               max(n, 2), timeout=3000)
        for line in txt.splitlines():
            if line.startswith("RESULT "):
                out.append(json.loads(line[len("RESULT "):]))
    return out


def main(ns=(1, 2, 4), ladder=LADDER):
    results = run(ns, ladder)
    print("name,us_per_call,derived")
    for res in results:
        best = None
        for r in res["rows"]:
            if r.get("fits"):
                best = r
        if best:
            print(f"unet_memory/pipeline-{res['n']},0,"
                  f"max_BC=({best['B']}:{best['C']});"
                  f"params={best['params']/1e6:.1f}M;"
                  f"mem_gib={best['per_dev_gib']:.1f}")
        else:
            print(f"unet_memory/pipeline-{res['n']},0,none_fit")


if __name__ == "__main__":
    main()
