import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (assignment §MULTI-POD DRY-RUN).

For every (architecture × input shape × mesh) cell: build the step function,
``jax.jit(...).lower(**abstract_inputs).compile()`` against the production
mesh — 16×16 single-pod and 2×16×16 multi-pod — and record
``memory_analysis()`` / ``cost_analysis()`` / the trip-count-corrected HLO
roofline terms into a JSON artifact that EXPERIMENTS.md §Dry-run/§Roofline
read.  A failure here (sharding mismatch, OOM at compile, unsupported
collective) is a bug in the framework.

Usage:
  python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results.json]
"""

import argparse
import json
import time
import traceback

import jax
from jax import set_mesh
import numpy as np

from repro import configs
from repro.configs.base import SHAPES_BY_NAME, V5E
from repro.core import plan as plan_lib
from repro.core import wire as wire_lib
from repro.runtime.compression import EFCompressor
from repro.launch import mesh as mesh_lib
from repro.launch import sharding as sharding_lib
from repro.launch import steps
from repro.models.lm import LMModel
from repro.roofline import analysis


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             keep_hlo: bool = False, pcfg_override=None,
             optimized: bool = False, verbose: bool = True,
             plan_spec=None) -> dict:
    arch = configs.get_arch(arch_name)
    shape = SHAPES_BY_NAME[shape_name]
    if not configs.shape_applies(arch, shape):
        return {"arch": arch_name, "shape": shape_name, "skipped": True,
                "reason": "long_500k needs sub-quadratic decode "
                          "(full-attention arch; DESIGN.md §4)"}
    pcfg = pcfg_override or configs.get_parallel(arch_name,
                                                 optimized=optimized)
    pcfg = pcfg.with_(pod=2 if multi_pod else 1,
                      n_micro=configs.derive_n_micro(
                          shape, pcfg.with_(pod=2 if multi_pod else 1)))
    if plan_spec is not None:
        # a PlanSpec (planner report entry) overrides the five pipeline
        # knobs wholesale; GSPMD axes (tp/data/pod) stay as derived above.
        # The production grid's model axis is fixed (dp2*pipe*tp), so when
        # the plan was made for fewer ranks than the grid's model axis,
        # the surplus becomes extra data parallelism (dp2).
        model_axis = pcfg.model_axis
        pcfg = plan_spec.apply_to(pcfg)
        want = pcfg.pipe * pcfg.tp
        if model_axis % want:
            raise SystemExit(
                f"plan pipe={pcfg.pipe} x tp={pcfg.tp} does not divide the "
                f"grid's model axis ({model_axis}); re-plan with a "
                f"hardware.yaml whose ranks divide it")
        pcfg = pcfg.with_(dp2=model_axis // want)
        dp = pcfg.pod * pcfg.data * pcfg.dp2 * pcfg.tp
        if (shape.global_batch // pcfg.n_micro) % dp:
            raise SystemExit(
                f"plan m={pcfg.n_micro} gives micro-batches of "
                f"{shape.global_batch // pcfg.n_micro} which do not divide "
                f"the grid's {dp}-way data parallelism; re-plan with "
                f"ranks={model_axis} in hardware.yaml and --dp {dp} so the "
                f"planner sees the full grid")
    base = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    mesh = mesh_lib.make_arch_mesh(pcfg, base=base)
    n_dev = mesh.size
    model = LMModel(arch, pcfg)
    t0 = time.time()
    cell = steps.build_cell(model, pcfg, mesh, shape)
    with set_mesh(mesh):
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings)
        lowered = jitted.lower(*cell.abstract_args)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    cost = analysis.analyze_hlo(hlo, n_dev)
    mf = analysis.model_flops_for(arch, shape) / n_dev
    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    # the cost model is schedule-parametric: a train cell's step time is
    # stretched by the SELECTED schedule's dedicated-device bubble (1F1B
    # and GPipe share a critical path; interleaved shrinks the fill by
    # ~1/v; zb fills bubbles with Bw work and, under residuals="reuse",
    # skips Bw's recompute entirely) — not by the GPipe clock
    # unconditionally.  The chain-hop comm term is priced from the
    # roofline constants (boundary bytes over ICI vs one stage-forward of
    # compute) and overlaps the next tick's compute under the mpmd
    # executor's double buffering, serializes after the producing task
    # under spmd.
    comm_units = bwd_comm_units = 0.0
    buf_report = {}
    wire_report = {}
    wspec = pcfg.wire_spec
    if shape.kind == "train" and pcfg.pipe > 1:
        mbg = shape.global_batch // pcfg.n_micro
        act_bytes = 2 if pcfg.activation_dtype == "bfloat16" else 4
        carry_bytes = mbg * shape.seq_len * arch.d_model * act_bytes
        # one stage-forward of compute per micro, in seconds (model FLOPs
        # are fwd+bwd ~ 3x fwd; a stage holds 1/pipe of the layers)
        fwd_unit_s = (analysis.model_flops_for(arch, shape) / 3.0
                      / pcfg.n_micro / pcfg.pipe) / V5E.peak_flops_bf16 \
            / max(pcfg.tp * pcfg.data * pcfg.pod, 1)
        hop_bytes = carry_bytes / max(pcfg.data * pcfg.pod, 1)
        # the wire codec prices each payload class in actual on-the-wire
        # bytes — forward carries at the chain precision, mirrored
        # cotangents at the cotangent precision
        hop_s = (hop_bytes * wire_lib.bytes_factor(wspec.chain,
                                                   block=wspec.block)
                 / V5E.ici_bw)
        bwd_hop_s = (hop_bytes * wire_lib.bytes_factor(wspec.cotangent,
                                                       block=wspec.block)
                     / V5E.ici_bw)
        comm_units = hop_s / fwd_unit_s if fwd_unit_s > 0 else 0.0
        bwd_comm_units = bwd_hop_s / fwd_unit_s if fwd_unit_s > 0 else 0.0
        tplan = plan_lib.plan_for(pcfg.schedule, pcfg.n_micro, pcfg.pipe,
                                  residuals=pcfg.residuals, wire=pcfg.wire)
        buf_report = sharding_lib.per_rank_buffer_bytes(tplan, carry_bytes)
        wire_report = wire_lib.plan_wire_report(tplan, carry_bytes)
    bubble = (plan_lib.schedule_bubble(pcfg.schedule, pcfg.n_micro,
                                       pcfg.pipe,
                                       residuals=pcfg.residuals,
                                       remat=pcfg.remat,
                                       executor=pcfg.executor,
                                       comm_cost=comm_units,
                                       bwd_comm_cost=bwd_comm_units)
              if shape.kind == "train" else 0.0)
    rep = analysis.RooflineReport(
        arch=arch_name, shape=shape_name,
        mesh="2x16x16" if multi_pod else "16x16",
        flops=cost.flops, bytes_hbm=cost.hbm_bytes,
        coll_bytes=cost.total_coll, coll_detail=cost.coll_link_bytes,
        model_flops_per_dev=mf, n_devices=n_dev,
        memory_per_device=per_dev_bytes,
        xla_flops=float(ca.get("flops", 0.0)),
        schedule=pcfg.schedule, bubble_fraction=round(bubble, 4),
        notes=f"pipe={pcfg.pipe} tp={pcfg.tp} m={pcfg.n_micro} "
              f"sched={pcfg.schedule} residuals={pcfg.residuals} "
              f"executor={pcfg.executor}")
    out = rep.to_dict()
    out.update({
        "skipped": False,
        "lower_s": round(t1 - t0, 1), "compile_s": round(t2 - t1, 1),
        "coll_counts": cost.coll_counts,
        "memory_analysis": {
            "argument_size": mem.argument_size_in_bytes,
            "output_size": mem.output_size_in_bytes,
            "temp_size": mem.temp_size_in_bytes,
            "alias_size": mem.alias_size_in_bytes,
        },
        "pcfg": {"pipe": pcfg.pipe, "tp": pcfg.tp, "data": pcfg.data,
                 "pod": pcfg.pod, "n_micro": pcfg.n_micro,
                 "remat": pcfg.remat, "residuals": pcfg.residuals,
                 "executor": pcfg.executor, "wire": pcfg.wire,
                 "grad_compression": pcfg.grad_compression},
        "comm_cost_units": round(comm_units, 4),
        "bwd_comm_cost_units": round(bwd_comm_units, 4),
        "advisories": list(pcfg.advisories()),
    })
    if buf_report:
        out["tick_buffers"] = buf_report
    if wire_report:
        out["wire"] = wire_report
    if shape.kind == "train" and pcfg.grad_compression == "int8_ef":
        # sizing from abstract params — no allocation, just the bytes the
        # cross-pod gradient all-reduce puts on the slow link per replica
        comp, raw = EFCompressor().payload_bytes(
            steps.abstract_params(model))
        out["grad_compression"] = {
            "mode": "int8_ef", "payload_bytes": comp,
            "uncompressed_bytes": raw,
            "ratio": round(comp / max(raw, 1), 4)}
    if verbose:
        print(f"[dryrun] {arch_name}/{shape_name} mesh={out['mesh']} "
              f"pipe={pcfg.pipe} tp={pcfg.tp} m={pcfg.n_micro} "
              f"executor={pcfg.executor} "
              f"compile={out['compile_s']}s "
              f"mem/dev={per_dev_bytes/2**30:.2f}GiB "
              f"t=(c {rep.t_compute*1e3:.1f} | m {rep.t_memory*1e3:.1f} | "
              f"x {rep.t_collective*1e3:.1f}) ms "
              f"bottleneck={rep.bottleneck} "
              f"roofline={rep.roofline_fraction:.3f}")
        print(f"[dryrun]   memory_analysis: {mem}")
        if buf_report:
            # per-rank (NOT uniform-max): what each rank's specialized
            # program declares for its park/inbox/residual slots.  The
            # byte figures cover park + inbox only — residual-slot bytes
            # are trace-time geometry (resid_info via build_train_step /
            # the schedules bench), so slots are printed but not priced.
            park = buf_report["per_rank_park_slots"]
            resid = buf_report["per_rank_resid_slots"]
            bb = buf_report["per_rank_buffer_bytes"]
            print(f"[dryrun]   per-rank park slots={park} "
                  f"resid slots={resid} (resid bytes are trace-time) "
                  f"park+inbox MiB={[round(b / 2**20, 1) for b in bb]} "
                  f"(uniform-max/rank "
                  f"{buf_report['uniform_max_buffer_bytes_per_rank'] / 2**20:.1f}"
                  f" MiB)")
        if wire_report:
            print(f"[dryrun]   wire={wire_report['wire']} "
                  f"bytes/tick={wire_report['bytes_per_tick']:.0f} "
                  f"ratio={wire_report['ratio']:.3f}")
        if "grad_compression" in out:
            gc = out["grad_compression"]
            print(f"[dryrun]   grad_compression=int8_ef "
                  f"payload={gc['payload_bytes']/2**20:.1f}MiB "
                  f"(raw {gc['uncompressed_bytes']/2**20:.1f}MiB, "
                  f"ratio {gc['ratio']:.3f})")
        for msg in pcfg.advisories():
            print(f"[dryrun]   ADVISORY: {msg}")
    if keep_hlo:
        out["hlo"] = hlo
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--optimized", action="store_true",
                    help="use the §Perf-hillclimbed parallel configs")
    ap.add_argument("--plan", default=None,
                    help="PlanReport JSON (from `hillclimb --hardware "
                         "... --out`); applies its top feasible plan")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    plan_spec = None
    if args.plan:
        from repro.planner.report import PlanReport
        with open(args.plan) as f:
            report = PlanReport.from_json(f.read())
        best = report.best
        if best is None:
            raise SystemExit(f"{args.plan}: no feasible plan in the report")
        plan_spec = best.spec
        print(f"[dryrun] applying plan: schedule={plan_spec.schedule.name} "
              f"residuals={plan_spec.schedule.residuals} "
              f"executor={plan_spec.schedule.executor} "
              f"m={plan_spec.microbatches} "
              f"partition={list(plan_spec.partition) or 'uniform'}")

    cells = []
    if args.all:
        for a in configs.ARCH_NAMES:
            for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
                cells.append((a, s))
    else:
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    results = []
    for mp in meshes:
        for a, s in cells:
            try:
                results.append(run_cell(a, s, multi_pod=mp,
                                        optimized=args.optimized,
                                        plan_spec=plan_spec))
            except Exception as e:   # a dry-run failure is a framework bug
                traceback.print_exc()
                results.append({"arch": a, "shape": s,
                                "mesh": "2x16x16" if mp else "16x16",
                                "skipped": False, "error": str(e)[:500]})
    if args.out:
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"[dryrun] wrote {len(results)} cells -> {args.out}")
    errs = [r for r in results if r.get("error")]
    if errs:
        raise SystemExit(f"{len(errs)} cells FAILED: "
                         f"{[(r['arch'], r['shape']) for r in errs]}")


if __name__ == "__main__":
    main()
