"""Schedule A/B benchmark: GPipe vs 1F1B vs interleaved vs zero-bubble,
crossed with the executor lowering (SPMD reference vs MPMD per-rank
specialized programs).

Runs the fused scheduler (``gpipe_tasked`` / ``1f1b`` / ``interleaved:2`` /
``zb`` / ``zb-reuse``) and the legacy-semantics autodiff path (``gpipe``,
the forward-only plan through the same executor) on real multi-device
pipelines (XLA host devices, reduced model — CPU is the runtime, TPU the
target) and emits a machine-readable ``BENCH_schedules.json`` so the perf
trajectory has a baseline.  ``zb-reuse`` is ``schedule="zb"`` with
``residuals="reuse"`` + ``remat="dots"`` (true ZB-H1: Bx stashes the
matmul outputs its remat materialized, Bw re-reads them instead of
recomputing — Bw is priced at 1 forward instead of 2), A/B'd against
recompute-mode ``zb`` with its residual-stash bytes reported.  Every fused
schedule's LM row additionally gets an ``executor="mpmd"`` A/B row: the
same plan lowered to per-rank specialized programs (``plan.specialize``)
with the chain ``ppermute`` double-buffered one tick ahead —
bitwise-identical results (tests/test_schedule_exec.py, which also covers
the portal/U-Net models under mpmd; the unet-portal rows here are
measured spmd-only), so the row reports the perf story: the
overlapped-comm device model and the per-rank declared buffer bytes.
Per row:

* ``us_per_step`` — measured wall-clock per train step.  This container
  timeshares every "device" over the same host cores, so wall-clock tracks
  TOTAL executed work plus per-tick overhead — it is the honest
  executor-overhead regression metric, but it cannot exhibit the
  critical-path speedup a schedule buys on dedicated devices
  (benchmarks/util.py documents the same convention for the paper tables).
* ``us_per_step_device_model`` — event-driven critical path of the task
  table on ``pipe`` DEDICATED devices (schedules.simulate_device_times),
  with per-task costs calibrated from a MEASURED single-device sequential
  step of the same model, plus a chain-hop comm term (``COMM_UNITS``
  stage-forward units per cross-rank boundary hop).  Under
  ``executor="spmd"`` the hop serializes after the producing task; under
  ``"mpmd"`` the double-buffered send overlaps the next tick's compute —
  so the mpmd model is <= the spmd model for every table, and the delta
  is exactly the comm the overlap hides.
* ``bubble_fraction_theoretical`` — idle (rank, tick) slots in the table.
* ``bubble_fraction_measured`` — cost-weighted idle share of the
  calibrated device-model critical path.
* ``speedup_vs_gpipe`` — gpipe_tasked's device-model step time over this
  row's: "did the schedule pay off" at a glance.
* ``per_stage_stash`` / ``per_stage_activation_bytes`` — the DONATED park
  buffer per rank (arrival buffer == stash, see repro.core.plan): the true
  per-device activation footprint, non-uniform across stages (1F1B's
  stage 0 parks nothing — its input is re-gathered from the micro-batch
  buffer).  ``stash_bound`` keeps the schedule-level ``min(n - j, m)`` /
  ``m`` bound for comparison with the paper; ``park_depth`` is the
  uniform SPMD buffer depth the compiled program allocates.  MPMD rows
  additionally carry ``per_rank_buffer_bytes`` — what each rank's
  SPECIALIZED program declares (park + backward inbox + residual slots,
  from ``plan.specialize``) — next to
  ``uniform_max_buffer_bytes_per_rank``, the flattened SPMD allocation;
  rank 0 under 1f1b/zb sits strictly below the uniform max.

Two model families cover the unified runtime's surface: the plain LM path
and a U-Net-style portal model (cross-stage skip edges lowered to plan
routes), so the bench trajectory breaks if either regresses.  The portal
rows carry the same device-model columns (calibrated against their own
measured gpipe_tasked wall), so smoke tripwires can compare against full
runs.

Wire engineering (PR 7) columns ride on every fused row:
``wire_bytes_per_tick`` / ``wire_bytes_per_step`` — actual bytes the
executor's collectives carry per tick/step under the row's codec —
``wire_ratio`` (encoded / fp32 bytes) and ``overlapped_route_hops`` (the
count certified by ``plan.assert_route_overlap``: every route hop latches
one tick before it ships, so none can serialize under mpmd).  Dedicated
``model="lm-wire"`` rows A/B the codec grid (fp32 / bf16 / int8-ef on
both executors): the lossless fp32 rows must be BITWISE equal to the
spmd baseline loss curve, the lossy rows must track it within tolerance
while still training.

``--smoke`` runs a tiny grid and fails if any fused schedule's wall-clock
exceeds its overhead cap vs gpipe_tasked, if zb-reuse's device model
exceeds zb-recompute's, if any schedule's mpmd device model exceeds its
spmd device model, or if any wire tripwire above trips — the CI
tripwires for executor regressions.
"""
import json
import os
import sys

from benchmarks.util import run_with_devices

OUT = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "BENCH_schedules.json")

BENCH = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from jax import set_mesh
from repro import configs
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.core import plan as plan_lib
from repro.core import schedules as S
from repro.launch import mesh as mesh_lib, steps
from repro.launch import sharding as sharding_lib
from repro.models.lm import LMModel
from repro.models import pipeline_hetero as PH
from repro.models.unet import UNetConfig, UNetModel
from repro.core import wire as wire_lib
from repro.optim import optimizers as optim

SMOKE = {smoke}
arch = configs.smoke_arch("smollm-360m")
shape = ShapeConfig("t", seq_len={seq}, global_batch={batch}, kind="train")
key = jax.random.PRNGKey(0)
rows = []

FUSED = ("gpipe_tasked", "1f1b", "interleaved:2", "zb", "zb-reuse")
SCHEDULES = FUSED if SMOKE else ("gpipe",) + FUSED
# chain-hop comm price, in stage-forward units: one boundary activation
# over an ICI-class link vs one stage forward of compute — a fixed
# TPU-flavoured ratio (the smoke model's own arithmetic intensity is too
# low to calibrate it honestly on CPU).  Reported per row so the A/B
# delta (what the mpmd overlap hides) is auditable.
COMM_UNITS = 0.1

def variant(name):
    # bench row name -> (schedule, residuals, remat).  zb-reuse pairs the
    # dots policy with residual reuse: the stash holds matmul outputs and
    # Bw recomputes only elementwise ops (bitwise vs recompute-zb).
    if name == "zb-reuse":
        return "zb", "reuse", "dots"
    return name, "recompute", "full"

def stash_report(name, pipe, m, carry_bytes, resid_info=None,
                 executor="spmd"):
    if name == "gpipe":
        # autodiff keeps every micro's boundary input alive as a residual
        return dict(park_depth=m, per_stage_stash=[m] * pipe,
                    stash_bound=[m] * pipe,
                    per_stage_activation_bytes=[m * carry_bytes] * pipe,
                    carry_bytes_per_micro=carry_bytes, residuals="autodiff")
    schedule, residuals, _ = variant(name)
    tplan = plan_lib.plan_for(schedule, m, pipe, residuals=residuals)
    bps = (resid_info or {{}}).get("resid_bytes_per_slot", 0)
    out = dict(park_depth=tplan.park_depth,
               per_stage_stash=list(tplan.per_stage_park),
               stash_bound=list(tplan.per_stage_stash),
               per_stage_activation_bytes=[d * carry_bytes
                                           for d in tplan.per_stage_park],
               carry_bytes_per_micro=carry_bytes,
               residuals=tplan.residuals,
               resid_slots=list(tplan.per_stage_resid),
               resid_depth=tplan.resid_depth,
               residual_bytes_per_slot=bps,
               residual_stash_bytes=[s * bps
                                     for s in tplan.per_stage_resid])
    if executor == "mpmd":
        # what each rank's SPECIALIZED program declares, vs the flattened
        # SPMD allocation (one executable must carry the ring max)
        out.update(sharding_lib.per_rank_buffer_bytes(tplan, carry_bytes,
                                                      bps))
    return out

def wire_cols(name, pipe, m, carry_bytes, wire="fp32", skips=()):
    # byte-priced wire traffic of the lowered plan, plus the plan-level
    # tripwire: assert_route_overlap proves every route hop has its
    # one-tick-earlier latch column, so under mpmd no hop can serialize
    # after its producing task.
    if name == "gpipe":
        return {{}}
    schedule, residuals, _ = variant(name)
    tplan = plan_lib.plan_for(schedule, m, pipe, residuals=residuals,
                              skips=skips, wire=wire)
    n_hops = plan_lib.assert_route_overlap(tplan)
    rep = wire_lib.plan_wire_report(tplan, carry_bytes)
    return dict(wire=rep["wire"],
                wire_bytes_per_tick=round(rep["bytes_per_tick"], 1),
                wire_bytes_per_step=round(rep["bytes_per_step"], 1),
                wire_ratio=round(rep["ratio"], 4),
                overlapped_route_hops=n_hops)

def schedule_model(name, pipe, m, unit_us, executor="spmd"):
    schedule, residuals, remat = variant(name)
    table, n_stages, ranks = plan_lib.schedule_table(schedule, m, pipe)
    cost = S.default_task_cost(n_stages, ranks, residuals=residuals,
                               remat=remat)
    t_end, busy = S.simulate_device_times(table, ranks, cost,
                                          comm_cost=COMM_UNITS,
                                          overlap_comm=executor == "mpmd")
    return dict(
        bubble_fraction_theoretical=round(S.bubble_fraction(table,
                                                            ranks=ranks), 4),
        bubble_fraction_measured=round(
            1.0 - sum(busy) / (ranks * t_end), 4) if t_end else 0.0,
        us_per_step_device_model=round(t_end * unit_us, 1),
        comm_cost_units=COMM_UNITS)

def time_step(step, *args):
    out = step(*args)                      # compile + warm
    jax.block_until_ready(jax.tree.leaves(out)[0])
    iters = 3 if SMOKE else 5
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        out = step(*args)
        jax.block_until_ready(jax.tree.leaves(out)[0])
        best = min(best, time.perf_counter() - t0)   # min: noise-robust
    return best, out

def lm_build(name, pipe, m, executor="spmd", wire="fp32"):
    schedule, residuals, remat = variant(name)
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=1, pod=1, n_micro=m,
                          remat=remat, schedule=schedule,
                          residuals=residuals, executor=executor,
                          wire=wire)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=8)
    opt = optim.init(ocfg, params)
    batch = {{k: jax.random.randint(key, v.shape, 0, arch.vocab)
             for k, v in model.input_specs(shape).items()}}
    resid_info = {{}}
    with set_mesh(mesh):
        step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape,
                                              ocfg, resid_info=resid_info))
        out = step(params, opt, batch)       # compile + warm
        jax.block_until_ready(jax.tree.leaves(out)[0])
    return step, params, opt, batch, mesh, float(out[2]["loss"]), resid_info

def lm_step_time(name, pipe, m):
    step, params, opt, batch, mesh, loss, _ = lm_build(name, pipe, m)
    with set_mesh(mesh):
        dt, _ = time_step(step, params, opt, batch)
    return dt, loss

EXECUTORS = ("spmd", "mpmd")

for pipe, m in {grid}:
    # calibrate the device-model unit: one MEASURED sequential step
    # (pipe=1, fused executor) = m micros x (F + fused B = 4) model-forward
    # units of real compute on this machine.
    t_seq, _ = lm_step_time("gpipe_tasked", 1, m)
    unit_us = t_seq * 1e6 / (4 * m)
    # compile every schedule x executor first, then time ROUND-ROBIN
    # (paired min-of-rounds): schedule-vs-schedule wall ratios on a
    # timeshared host are noise-dominated unless measured back-to-back.
    keys = [(s, e) for s in SCHEDULES
            for e in (EXECUTORS if s != "gpipe" else ("spmd",))]
    built = {{k: lm_build(k[0], pipe, m, executor=k[1]) for k in keys}}
    walls = {{k: float("inf") for k in keys}}
    rounds = 2 if SMOKE else 4
    for _ in range(rounds):
        for k in keys:
            step, params, opt, batch, mesh = built[k][:5]
            with set_mesh(mesh):
                dt, _ = time_step(step, params, opt, batch)
            walls[k] = min(walls[k], dt)
    base_model_us = None
    for name, executor in keys:
        mbg = shape.global_batch // m
        carry_bytes = mbg * shape.seq_len * arch.d_model * 4  # f32 boundary
        model_cols = schedule_model(name, pipe, m, unit_us, executor)
        if (name, executor) == ("gpipe_tasked", "spmd"):
            base_model_us = model_cols["us_per_step_device_model"]
        # the loss is executor- and schedule-invariant (bitwise contract)
        rows.append(dict(
            model="lm", schedule=name, pipe=pipe, n_micro=m,
            executor=executor,
            us_per_step=round(walls[(name, executor)] * 1e6, 1),
            us_per_step_sequential=round(t_seq * 1e6, 1),
            loss=built[(name, executor)][5], **model_cols,
            **wire_cols(name, pipe, m, carry_bytes),
            **stash_report(name, pipe, m, carry_bytes,
                           resid_info=built[(name, executor)][6],
                           executor=executor)))
    del built
    for r in rows:
        if r["model"] == "lm" and r["pipe"] == pipe and r["n_micro"] == m:
            r["speedup_vs_gpipe"] = round(
                base_model_us / r["us_per_step_device_model"], 3)

# --- portal-model variant: U-Net skips through the unified runtime -------
if not SMOKE:
    ucfg = UNetConfig(B=1, C=8, levels=4, img=32)
    UB = 8
    x = jax.random.normal(jax.random.PRNGKey(1), (UB, ucfg.img, ucfg.img, 3))
    for pipe, m in [(4, 4)]:
        losses = {{}}
        urows = []
        for name in FUSED:
            schedule, residuals, remat = variant(name)
            pcfg = ParallelConfig(pipe=pipe, tp=1, data=2, pod=1, n_micro=m,
                                  portals=True, remat=remat,
                                  schedule=schedule, residuals=residuals)
            mesh = mesh_lib.make_smoke_mesh(pcfg)
            umodel = UNetModel(ucfg, pipe * pcfg.virtual_stages)
            uparams = umodel.init(jax.random.PRNGKey(0))
            prog = PH.build_hetero_program(umodel, uparams, UB // m, pcfg,
                                           x[:2])
            carry_bytes = (UB // m) * prog.carry_proto["buf"].shape[1] * 4
            resid_info = {{}}
            with set_mesh(mesh):
                tgt = jnp.zeros((UB,) + tuple(prog.out_proto.shape[1:]),
                                jnp.float32)
                call = jax.jit(PH.hetero_grad_call(prog, mesh, pcfg,
                                                   resid_info=resid_info))
                dt, (loss, _) = time_step(call, prog.stacked_params, x, tgt)
            losses[name] = float(loss)
            urows.append(dict(
                model="unet-portal", schedule=name, pipe=pipe, n_micro=m,
                executor="spmd", n_skip_edges=len(prog.skips),
                us_per_step=round(dt * 1e6, 1), loss=float(loss),
                **wire_cols(name, pipe, m, carry_bytes,
                            skips=prog.skips),
                **stash_report(name, pipe, m, carry_bytes,
                               resid_info=resid_info)))
        # device-model columns for the portal rows, calibrated against the
        # measured gpipe_tasked wall (no single-device portal run exists):
        # unit_us = wall(gpipe_tasked) / t_end_model(gpipe_tasked), so the
        # gpipe_tasked row's model time equals its wall by construction
        # and the other rows scale by the table critical path.  The
        # uniform-stage cost model approximates the hetero stage split.
        base_tbl, base_n, base_r = plan_lib.schedule_table("gpipe_tasked",
                                                           m, pipe)
        t_base, _ = S.simulate_device_times(
            base_tbl, base_r, S.default_task_cost(base_n, base_r),
            comm_cost=COMM_UNITS)
        u_unit = [r for r in urows
                  if r["schedule"] == "gpipe_tasked"][0]["us_per_step"] \
            / t_base
        for r in urows:
            r.update(schedule_model(r["schedule"], pipe, m, u_unit))
        rows.extend(urows)
        # the unified runtime's contract: schedules are the same computation
        assert len(set(losses.values())) == 1, losses

# --- wire tripwires: the codec on the real executor (smoke AND full) -----
# fp32 is the lossless mode: its identity codec plus the double-buffered
# route latches must not perturb a single bit, so both executors' 5-step
# loss curves must be BITWISE equal to the spmd baseline (the pre-codec
# PR 6 path computes exactly this curve).  Lossy codecs must track the
# fp32 curve (int8-ef's error feedback keeps the drift bounded) and still
# train.  Each codec row lands in the JSON with its on-the-wire bytes per
# tick and compressed/uncompressed ratio.
wp, wm = {grid}[0]

def wire_curve(executor, wire, n_steps=5):
    step, params, opt, batch, mesh, _, _ = lm_build(
        "1f1b", wp, wm, executor=executor, wire=wire)
    ls = []
    with set_mesh(mesh):
        p, o = params, opt
        for _ in range(n_steps):
            p, o, aux = step(p, o, batch)
            ls.append(float(aux["loss"]))
    return ls

base_curve = wire_curve("spmd", "fp32")
w_carry = (shape.global_batch // wm) * shape.seq_len * arch.d_model * 4
for executor in ("spmd", "mpmd"):
    for wname in ("fp32", "bf16", "int8-ef"):
        cur = wire_curve(executor, wname)
        if wname == "fp32":
            assert cur == base_curve, (executor, wname, cur, base_curve)
        else:
            assert all(abs(a - b) <= 0.05 * abs(b) + 1e-6
                       for a, b in zip(cur, base_curve)), \\
                (executor, wname, cur, base_curve)
            assert cur[-1] < cur[0], (executor, wname, cur)
        rows.append(dict(model="lm-wire", schedule="1f1b", pipe=wp,
                         n_micro=wm, executor=executor,
                         loss_curve=[round(l, 6) for l in cur],
                         **wire_cols("1f1b", wp, wm, w_carry, wire=wname)))

print("JSON" + json.dumps(rows))
"""


def main(grid=((2, 4), (4, 4), (4, 8)), batch=16, seq=32, n_devices=8,
         smoke=False):
    if smoke:
        grid, batch, seq = ((2, 4),), 8, 16
    out = run_with_devices(
        BENCH.format(grid=tuple(grid), batch=batch, seq=seq,
                     smoke=repr(smoke)),
        n_devices=n_devices, timeout=5400)
    rows = json.loads(out.split("JSON", 1)[1])
    for r in rows:
        if r["model"] == "lm-wire":
            # codec A/B rows carry loss curves + wire bytes, not wall time
            print(f"wire_{r['schedule']}_p{r['pipe']}_m{r['n_micro']}"
                  f"_{r['executor']}_{r['wire']},"
                  f"{r['wire_bytes_per_tick']},ratio={r['wire_ratio']}")
            continue
        extra = ""
        if "us_per_step_device_model" in r:
            extra = (f",model={r['us_per_step_device_model']}"
                     f",bubble={r['bubble_fraction_theoretical']}")
        print(f"schedule_{r['model']}_{r['schedule']}_p{r['pipe']}"
              f"_m{r['n_micro']}_{r.get('executor', 'spmd')},"
              f"{r['us_per_step']}{extra}")

    by_key = {(r["model"], r["pipe"], r["n_micro"], r["schedule"],
               r.get("executor", "spmd")): r for r in rows}
    for (model, pipe, m, s, ex), r in by_key.items():
        g = by_key.get((model, pipe, m, "gpipe_tasked", "spmd"))
        if g is None:
            continue
        if s == "1f1b":
            # the donated stash is non-uniform: stage 0 parks nothing (its
            # input is re-gathered), later stages stay within the paper
            # bound (+1 in-flight arrival) and under GPipe's footprint
            assert r["per_stage_stash"][0] == 0
            assert len(set(r["per_stage_stash"])) > 1 or pipe == 1
            assert all(a <= b + 1 for a, b in zip(r["per_stage_stash"],
                                                  r["stash_bound"]))
            assert r["stash_bound"] == [min(pipe - j, m)
                                        for j in range(pipe)]
            assert sum(r["per_stage_activation_bytes"]) \
                <= sum(g["per_stage_activation_bytes"])
        if smoke and s in ("1f1b", "interleaved:2", "zb", "zb-reuse"):
            # CI tripwire: fused-executor overhead must stay bounded.  At
            # the smoke shape compute is negligible, so interleaved pays
            # its v-fold branch-dispatch overhead in full — it gets a
            # proportionally wider bound; so does the mpmd lowering, whose
            # R-way rank switch adds pure dispatch (never compute) at this
            # degenerate scale.  spmd rows must stay within 1.5x.
            cap = 2.5 if (s.startswith("interleaved") or ex == "mpmd") \
                else 1.5
            assert r["us_per_step"] <= cap * g["us_per_step"], \
                (s, ex, r["us_per_step"], g["us_per_step"], cap)

    # residual-reuse tripwire (smoke AND full): dropping Bw's recompute
    # must shorten the zb dedicated-device step, and the reuse row must
    # actually carry a residual stash.
    for (model, pipe, m, s, ex), r in by_key.items():
        if s != "zb-reuse" or model != "lm":
            continue
        z = by_key[(model, pipe, m, "zb", ex)]
        assert r["us_per_step_device_model"] <= z["us_per_step_device_model"], \
            (pipe, m, ex, r["us_per_step_device_model"],
             z["us_per_step_device_model"])
        assert r["residuals"] == "reuse" and sum(r["resid_slots"]) > 0
        assert sum(r["residual_stash_bytes"]) > 0, r["residual_bytes_per_slot"]

    # wire tripwires (smoke AND full): every fused plan passed the
    # in-bench assert_route_overlap latch check (column present); default
    # rows ship fp32 (ratio 1.0) with real bytes on the wire; the codec
    # A/B rows' compressed/uncompressed ratios match their bytes factors
    # (bf16 halves the wire, int8-ef lands near 0.25 + per-block scales).
    for r in rows:
        if "wire_ratio" not in r:
            assert r["schedule"] == "gpipe", r["schedule"]
            continue
        assert r["wire_bytes_per_tick"] > 0, r
        if r["model"] == "lm-wire":
            want = {"fp32": 1.0, "bf16": 0.5}.get(r["wire"])
            if want is not None:
                assert abs(r["wire_ratio"] - want) < 1e-6, r
            else:
                assert 0.2 < r["wire_ratio"] < 0.3, r
        else:
            assert r["wire"] == "fp32" and r["wire_ratio"] == 1.0, r

    # executor A/B tripwires (smoke AND full):
    #  * the mpmd (comm-overlapped) device model must be <= spmd for EVERY
    #    fused schedule — the double buffering can only hide comm;
    #  * mpmd rows declare per-rank buffer bytes strictly below the
    #    uniform SPMD max for at least one rank (1f1b/zb: rank 0 parks 0).
    for (model, pipe, m, s, ex), r in by_key.items():
        if model != "lm" or ex != "mpmd":
            continue
        sp = by_key[(model, pipe, m, s, "spmd")]
        assert r["us_per_step_device_model"] <= \
            sp["us_per_step_device_model"], \
            (s, pipe, m, r["us_per_step_device_model"],
             sp["us_per_step_device_model"])
        if s in ("1f1b", "zb", "zb-reuse") and pipe > 1:
            uni = r["uniform_max_buffer_bytes_per_rank"]
            assert any(b < uni for b in r["per_rank_buffer_bytes"]), \
                (s, pipe, m, r["per_rank_buffer_bytes"], uni)

    if smoke:
        print("# smoke OK (fused schedules within their overhead caps; "
              "zb-reuse device model <= zb-recompute; mpmd device model "
              "<= spmd with per-rank buffers below uniform max; route "
              "latches verified and wire codecs bitwise/tolerance-checked)")
        return rows

    # schedule-payoff acceptance: on dedicated devices, interleaving and/or
    # split backward must strictly undercut plain 1F1B at pipe=4
    for m in (4, 8):
        f = by_key.get(("lm", 4, m, "1f1b", "spmd"))
        if f is None:
            continue
        better = [s for s in ("interleaved:2", "zb", "zb-reuse")
                  if ("lm", 4, m, s, "spmd") in by_key
                  and by_key[("lm", 4, m, s, "spmd")]["us_per_step_device_model"]
                  < f["us_per_step_device_model"]]
        assert better, f"no schedule beats 1f1b at pipe=4, m={m}"
    report = {"bench": "schedules", "arch": "smollm-360m(smoke)+unet(smoke)",
              "rows": rows}
    with open(OUT, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {OUT}")
    return report


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)
