"""Schedule-driven execution: the fused scheduler (repro.core.plan +
run_pipeline_tasks) must make 1F1B, GPipe, interleaved and split-backward
*the same computation in a different order* — bitwise-identical losses and
gradients — and must match the legacy autodiff backward to numerical
tolerance.

Host-side plan properties run in-process; executor equivalence runs on 8
XLA host devices in a subprocess (one subprocess amortizes jit time over
the whole (pipe, m) grid)."""
import numpy as np
import pytest

from conftest import run_subprocess

from repro.core import plan as PL
from repro.core import schedules as S


# ---------------------------------------------------------------------------
# Plan lowering properties (host-side, no devices)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n", [(1, 1), (4, 1), (1, 4), (4, 2), (8, 2),
                                 (4, 4), (8, 4), (6, 3)])
def test_plan_stash_bound_and_donated_park(m, n):
    """``per_stage_stash`` carries the schedule-level bound (peak_stash:
    ``m`` for GPipe, ``min(n - j, m)`` for 1F1B); ``per_stage_park`` is the
    DONATED arrival-buffer high-water the executor actually allocates —
    non-uniform, with stage 0 parking nothing (its input is re-gathered,
    not stashed), and never above bound + the one-tick in-flight arrival."""
    for name, table in (("gpipe", S.gpipe_schedule(m, n, checkpoint=False)),
                        ("1f1b", S.one_f_one_b_schedule(m, n))):
        plan = PL.lower_tasks(table, m, n)
        assert list(plan.per_stage_stash) == S.peak_stash(table, n), name
        assert plan.park_depth == max(plan.per_stage_park)
        assert plan.per_stage_park[0] == 0     # stage 0: nothing to park
        for j in range(n):
            assert plan.per_stage_park[j] <= plan.per_stage_stash[j] + 1
    gpipe = PL.plan_for("gpipe", m, n)
    f1b = PL.plan_for("1f1b", m, n)
    assert all(gpipe.per_stage_stash[j] == m for j in range(n))
    # the true per-stage bound, not a flattened SPMD max: stage j stashes
    # at most min(n - j, m) micro-batches under 1F1B
    assert all(f1b.per_stage_stash[j] == min(n - j, m) for j in range(n))
    assert (f1b.per_stage_stash_bytes(100)
            == tuple(100 * d for d in f1b.per_stage_park))
    # 1F1B's memory bound is the point: strictly better whenever m > n
    if m > n and n > 1:
        assert f1b.park_depth < gpipe.park_depth


@pytest.mark.parametrize("schedule,v", [("gpipe", 1), ("1f1b", 1),
                                        ("zb", 1), ("interleaved:2", 2)])
@pytest.mark.parametrize("m,n", [(4, 2), (8, 4), (4, 4)])
def test_plan_task_coverage(schedule, v, m, n):
    """Every task appears exactly once, at most one task per rank per tick,
    park/backward-inbox arrivals never overtake their consumers, and every
    parked slot is consumed."""
    p = PL.plan_for(schedule, m, n)
    assert p.n_ranks == n and p.n_chunks == v and p.n_stages == n * v
    split = schedule == "zb"
    seen = set()
    for t in range(p.n_ticks):
        for j in range(n):
            k = p.kind[t, j]
            if k == PL.NOP:
                continue
            s = int(p.chunk[t, j]) * n + j
            task = (int(k), int(p.micro[t, j]), s)
            assert task not in seen, task
            seen.add(task)
            if s > 0 and k == PL.FWD:
                assert p.park_read[t, j] >= 0   # boundary input is parked
    per_stage_kinds = 3 if split else 2
    assert len(seen) == per_stage_kinds * m * n * v, schedule
    # slot pairing: a parked value is read at least once before its slot is
    # overwritten, and nothing stays parked forever
    for arr, rd in ((p.park_recv, p.park_read), (p.b_recv, p.b_read)):
        for j in range(n):
            events = []
            for t in range(p.n_ticks):
                if arr[t, j] >= 0:
                    events.append(("park", t, int(arr[t, j])))
                if rd[t, j] >= 0:
                    events.append(("read", t, int(rd[t, j])))
            read_since_park = {}
            # sort by (tick, event): "park" < "read", so a same-tick
            # arrive-then-consume pairs up correctly
            for ev, t, slot in sorted(events, key=lambda e: (e[1], e[0])):
                if ev == "park":
                    assert read_since_park.get(slot, True), \
                        f"slot {slot} overwritten unread at tick {t}"
                    read_since_park[slot] = False
                elif slot in read_since_park:
                    read_since_park[slot] = True
            assert all(read_since_park.values()), \
                f"rank {j}: parked value never consumed"


def test_plan_zb_split_events():
    """Split-backward lowering: Bw re-reads the SAME park / b-inbox slots
    its Bx used (the weight grad re-seeds from the parked cotangent), and
    ticks where a rank would idle under 1F1B now carry Bw work."""
    m, n = 8, 4
    p = PL.plan_for("zb", m, n)
    f1b = PL.plan_for("1f1b", m, n)
    kinds = set(int(k) for k in p.kind.ravel())
    assert PL.BWD_X in kinds and PL.BWD_W in kinds and PL.BWD not in kinds
    # every (micro, stage) Bx/Bw pair shares its park slot
    for j in range(n):
        by_micro = {}
        for t in range(p.n_ticks):
            if p.kind[t, j] in (PL.BWD_X, PL.BWD_W):
                by_micro.setdefault(int(p.micro[t, j]), []).append(
                    (int(p.kind[t, j]), int(p.park_read[t, j]),
                     int(p.b_read[t, j])))
        for i, evs in by_micro.items():
            assert len(evs) == 2, (j, i)
            (kx, px, bx), (kw, pw, bw) = sorted(evs)
            assert (kx, kw) == (PL.BWD_X, PL.BWD_W)
            assert px == pw and bx == bw, (j, i)
    # the fill: zb has strictly fewer idle slots than 1f1b
    assert (p.kind == PL.NOP).sum() / p.kind.size \
        < (f1b.kind == PL.NOP).sum() / f1b.kind.size


def test_plan_interleaved_chunks():
    """Interleaved lowering: rank r hosts chunks {r, r+n, ...}; the chunk
    column selects them; per-rank park covers both chunks' arrivals."""
    m, n, v = 8, 4, 2
    p = PL.plan_for("interleaved:2", m, n)
    assert p.n_chunks == v and p.n_stages == n * v
    for t in range(p.n_ticks):
        for j in range(n):
            if p.kind[t, j] != PL.NOP:
                assert 0 <= p.chunk[t, j] < v
    # every global stage s executes on rank s % n with chunk s // n
    stages_seen = set()
    for t in range(p.n_ticks):
        for j in range(n):
            if p.kind[t, j] == PL.FWD:
                stages_seen.add(int(p.chunk[t, j]) * n + j)
    assert stages_seen == set(range(n * v))
    table = S.interleaved_1f1b_schedule(m, n, v)
    assert list(p.per_stage_stash) == S.peak_stash(table, n * v, ranks=n)


def test_plan_segments_and_compaction():
    """Segments partition the tick axis, each declaring exactly the branch
    set its ticks use; all-rank-NOP ticks are dropped at lowering."""
    for schedule, m, n in [("gpipe_tasked", 8, 4), ("1f1b", 8, 4),
                           ("zb", 8, 4), ("interleaved:2", 8, 4)]:
        p = PL.plan_for(schedule, m, n)
        assert len(p.segments) <= PL.MAX_SEGMENTS
        assert p.segments[0].start == 0 and p.segments[-1].stop == p.n_ticks
        for a, b in zip(p.segments, p.segments[1:]):
            assert a.stop == b.start
        for seg in p.segments:
            used = set(int(k) for k in p.kind[seg.start:seg.stop].ravel())
            assert used <= set(seg.kinds), (schedule, seg)
        # no tick is empty (compaction) — some rank works every tick
        assert ((p.kind != PL.NOP).sum(axis=1) > 0).all(), schedule
    # GPipe's fill is a pure-F phase: its first segment has no B branches
    g = PL.plan_for("gpipe_tasked", 8, 4)
    assert not (set(g.segments[0].kinds)
                & {PL.BWD, PL.BWD_X, PL.BWD_W})


def test_forward_plan_is_clock_cycle():
    """The forward-only plan reproduces Algorithm 1's F_{t-j, j}
    arithmetic: the same executor that runs fused F+B tables runs this
    plan for inference / autodiff-backward execution."""
    m, n = 6, 4
    p = PL.plan_for("gpipe_fwd", m, n)
    assert not p.has_backward
    assert p.n_ticks == m + n - 1
    for t in range(p.n_ticks):
        for j in range(n):
            if 0 <= t - j < m:
                assert p.kind[t, j] == PL.FWD and p.micro[t, j] == t - j
            else:
                assert p.kind[t, j] == PL.NOP
    # no backward machinery in a forward-only plan
    assert (p.b_read == -1).all() and (p.b_recv == -1).all()


def test_device_model_schedule_payoff():
    """The dedicated-device critical path (the schedule-comparison clock)
    shows the new schedules' payoff: interleaving strictly undercuts 1F1B
    at every grid point; split backward wins exactly where the 1F1B bubble
    outweighs its extra recompute (m close to n)."""
    cases = [(4, 4), (8, 4), (8, 2)]
    for m, n in cases:
        t_f, _ = S.simulate_device_times(S.one_f_one_b_schedule(m, n), n)
        t_g, _ = S.simulate_device_times(
            S.gpipe_schedule(m, n, checkpoint=False), n)
        assert t_f == pytest.approx(t_g)   # same critical path (flush)
        t_i, _ = S.simulate_device_times(
            S.interleaved_1f1b_schedule(m, n, 2),
            n, S.default_task_cost(2 * n, n))
        assert t_i < t_f, (m, n)
    t_zb, _ = S.simulate_device_times(S.zb_schedule(4, 4), 4)
    t_f, _ = S.simulate_device_times(S.one_f_one_b_schedule(4, 4), 4)
    assert t_zb < t_f                      # high-bubble regime: zb pays off


# ---------------------------------------------------------------------------
# Executor equivalence (8 host devices, subprocess)
# ---------------------------------------------------------------------------

MPMD_BITWISE = """
import zlib
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.models import pipeline_hetero as PH
from repro.models.unet import UNetConfig, UNetModel
from repro.core.pipeline import pipeline_grad_call, microbatch, unmicrobatch

key = jax.random.PRNGKey(0)
shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")

def lm_lg(arch_name, schedule, pipe, m, executor, residuals="recompute",
          remat="full", stream=False, data=1):
    arch = configs.smoke_arch(arch_name)
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=data, pod=1, n_micro=m,
                          remat=remat, schedule=schedule,
                          residuals=residuals, executor=executor,
                          stream_inputs=stream)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    batch = {}
    for k, v in model.input_specs(shape).items():
        kk = jax.random.fold_in(key, zlib.crc32(k.encode()) % 1000)
        batch[k] = (jax.random.randint(kk, v.shape, 0, arch.vocab)
                    if v.dtype == jnp.int32
                    else jax.random.normal(kk, v.shape, v.dtype) * 0.1)
    mbg = shape.global_batch // m
    cp = {"h": jax.ShapeDtypeStruct((mbg, 16, arch.d_model), jnp.float32)}
    with set_mesh(mesh):
        pg, _ = pipeline_grad_call(
            model.make_stage_apply(model.consts()), mesh=mesh, cfg=pcfg,
            loss_fn=lambda hp, c, la: model.head_loss(hp, c["h"],
                                                      la["labels"]),
            skips=model.skips(), skip_protos=model.skip_protos(mbg, 16),
            carry_proto=cp)
        @jax.jit
        def fused(p, b):
            fresh, evjp = jax.vjp(
                lambda e: model.embed_inputs(e, b), p["embed"])
            head_ps = {"head": p["head"], "embed": p["embed"]}
            loss, gs, gh, ig = pg(p["stages"], head_ps, microbatch(fresh, m),
                                  microbatch({"labels": b["labels"]}, m))
            (ge,) = evjp(unmicrobatch(ig))
            ge = jax.tree.map(jnp.add, ge, gh["embed"])
            return loss, {"embed": ge, "stages": gs, "head": gh["head"]}
        loss, grads = fused(params, batch)
    return np.asarray(loss), jax.tree.map(np.asarray, grads)

def check(tag, a, b):
    la, ga = a
    lb, gb = b
    assert np.array_equal(la, lb), (tag, la, lb)
    for (path, x), y in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        assert np.array_equal(x, y), (tag, path)
    print("MPMD BITWISE OK", *tag)

# LM: every fused schedule family, plus streaming, plus pipe=4 with DP
for case in [("1f1b", 2, 4, "recompute", "full", False, 1),
             ("gpipe_tasked", 2, 4, "recompute", "full", False, 1),
             ("interleaved:2", 2, 4, "recompute", "full", False, 1),
             ("zb", 2, 4, "recompute", "full", False, 1),
             ("zb", 2, 4, "reuse", "dots", False, 1),
             ("1f1b", 2, 4, "recompute", "full", True, 1),
             ("1f1b", 4, 8, "recompute", "full", False, 2)]:
    sched, pipe, m, residuals, remat, stream, data = case
    spmd = lm_lg("smollm-360m", sched, pipe, m, "spmd", residuals, remat,
                 stream, data)
    mpmd = lm_lg("smollm-360m", sched, pipe, m, "mpmd", residuals, remat,
                 stream, data)
    check(("lm",) + case, spmd, mpmd)

# whisper encoder-decoder: multi-destination skip portals through the plan
for sched, residuals, remat in [("1f1b", "recompute", "full"),
                                ("zb", "reuse", "dots")]:
    spmd = lm_lg("whisper-tiny", sched, 2, 4, "spmd", residuals, remat)
    mpmd = lm_lg("whisper-tiny", sched, 2, 4, "mpmd", residuals, remat)
    check(("whisper", sched, residuals), spmd, mpmd)

# U-Net heterogeneous (switch-program) portals
ucfg = UNetConfig(B=1, C=8, levels=3, img=16)
UB, pipe, m = 8, 2, 4
x = jax.random.normal(jax.random.fold_in(key, 7), (UB, ucfg.img, ucfg.img, 3))
results = {}
for executor in ("spmd", "mpmd"):
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=1, pod=1, n_micro=m,
                          portals=True, schedule="1f1b", executor=executor)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    umodel = UNetModel(ucfg, pipe)
    uparams = umodel.init(jax.random.PRNGKey(0))
    prog = PH.build_hetero_program(umodel, uparams, UB // m, pcfg, x[:2])
    tgt = jnp.zeros((UB,) + tuple(prog.out_proto.shape[1:]), jnp.float32)
    with set_mesh(mesh):
        call = jax.jit(PH.hetero_grad_call(prog, mesh, pcfg))
        loss, g_stage = call(prog.stacked_params, x, tgt)
    results[executor] = (np.asarray(loss), np.asarray(g_stage))
assert np.array_equal(results["spmd"][0], results["mpmd"][0])
assert np.array_equal(results["spmd"][1], results["mpmd"][1])
print("MPMD BITWISE OK unet-hetero")
print("ALL MPMD BITWISE OK")
"""


def test_mpmd_executor_bitwise_vs_spmd():
    """The MPMD lowering (per-rank specialized programs + double-buffered
    chain sends) is bitwise-identical in loss AND grads to the SPMD
    reference for every fused schedule family — on the LM, the whisper
    portal model and the hetero U-Net, including streamed inputs, DP, and
    residual reuse."""
    out = run_subprocess(MPMD_BITWISE, n_devices=8, timeout=2400)
    assert "ALL MPMD BITWISE OK" in out


UNIFIED_EXTRAS = """
import zlib
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig, ParallelConfig
from repro.launch import mesh as mesh_lib
from repro.models.lm import LMModel
from repro.core import plan as plan_lib
from repro.core.pipeline import (pipeline_call, pipeline_grad_call,
                                 run_pipeline_tasks, microbatch,
                                 last_stage_output, unmicrobatch)

key = jax.random.PRNGKey(0)

# --- 1. skip-connection model: all fused schedules vs legacy GPipe -------
arch = configs.smoke_arch("whisper-tiny")
shape = ShapeConfig("t", seq_len=16, global_batch=16, kind="train")

def whisper_lg(schedule, pipe, m, stream=False):
    pcfg = ParallelConfig(pipe=pipe, tp=1, data=1, pod=1, n_micro=m,
                          remat="full", schedule=schedule,
                          stream_inputs=stream)
    mesh = mesh_lib.make_smoke_mesh(pcfg)
    model = LMModel(arch, pcfg, dtype=jnp.float32)
    params = model.init(key)
    batch = {}
    for k, v in model.input_specs(shape).items():
        kk = jax.random.fold_in(key, zlib.crc32(k.encode()) % 1000)
        batch[k] = (jax.random.randint(kk, v.shape, 0, arch.vocab)
                    if v.dtype == jnp.int32
                    else jax.random.normal(kk, v.shape, v.dtype) * 0.1)
    consts = model.consts()
    mbg = shape.global_batch // m
    cp = {"h": jax.ShapeDtypeStruct((mbg, 16, arch.d_model), jnp.float32)}
    with set_mesh(mesh):
        if schedule == "gpipe":       # legacy semantics: autodiff backward
            pipe_fn = pipeline_call(
                model.make_stage_apply(consts), mesh=mesh, cfg=pcfg,
                skips=model.skips(),
                skip_protos=model.skip_protos(mbg, 16), carry_proto=cp)
            def loss_fn(p, b):
                fresh = model.embed_inputs(p["embed"], b)
                outs, _ = pipe_fn(p["stages"], microbatch(fresh, m), None)
                h = unmicrobatch(last_stage_output(outs)["h"])
                return model.head_loss(p, h, b["labels"])
            loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
            return np.asarray(loss), jax.tree.map(np.asarray, grads)
        pg, tplan = pipeline_grad_call(
            model.make_stage_apply(consts), mesh=mesh, cfg=pcfg,
            loss_fn=lambda hp, c, la: model.head_loss(hp, c["h"],
                                                      la["labels"]),
            skips=model.skips(), skip_protos=model.skip_protos(mbg, 16),
            carry_proto=cp)
        # portal events made it into the plan
        assert {rt.name for rt in tplan.routes} \
            == {s.name for s in model.skips()}
        @jax.jit
        def fused(p, b):
            fresh, evjp = jax.vjp(
                lambda e: model.embed_inputs(e, b), p["embed"])
            head_ps = {"head": p["head"], "embed": p["embed"]}
            loss, gs, gh, ig = pg(p["stages"], head_ps, microbatch(fresh, m),
                                  microbatch({"labels": b["labels"]}, m))
            (ge,) = evjp(unmicrobatch(ig))
            ge = jax.tree.map(jnp.add, ge, gh["embed"])
            return loss, {"embed": ge, "stages": gs, "head": gh["head"]}
        loss, grads = fused(params, batch)
        return np.asarray(loss), jax.tree.map(np.asarray, grads)

def assert_bitwise(ga, gb, tag):
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(ga)[0],
                            jax.tree_util.tree_leaves(gb)):
        assert np.array_equal(a, b), (tag, path)

for pipe, m in [(2, 4), (4, 4)]:
    l_t, g_t = whisper_lg("gpipe_tasked", pipe, m)
    l_f, g_f = whisper_lg("1f1b", pipe, m)
    l_z, g_z = whisper_lg("zb", pipe, m)
    assert np.array_equal(l_t, l_f), (pipe, m, l_t, l_f)
    assert np.array_equal(l_t, l_z), (pipe, m, l_t, l_z)
    assert_bitwise(g_t, g_f, ("1f1b", pipe, m))
    # split backward through skip portals: Bx ships the skip cotangents on
    # the critical path, Bw re-seeds the weight VJP — still bitwise
    assert_bitwise(g_t, g_z, ("zb", pipe, m))
    l_r, g_r = whisper_lg("gpipe", pipe, m)
    np.testing.assert_allclose(l_t, l_r, rtol=2e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g_r)[0],
                            jax.tree_util.tree_leaves(g_t)):
        np.testing.assert_allclose(a, b, rtol=5e-4, atol=5e-5,
                                   err_msg=f"{(pipe, m)} {path}")
    print("skip-model grid point OK", pipe, m)

# --- 1b. interleaved: same GLOBAL stage split on half the ranks is the
# SAME computation bitwise: interleaved:2 @ pipe=2 == 1f1b @ pipe=4
# (both cut whisper into 4 global stages; the portal whose src and dst
# land on one rank becomes an identity hold).
l4, g4 = whisper_lg("1f1b", 4, 8)
li, gi = whisper_lg("interleaved:2", 2, 8)
assert np.array_equal(l4, li), (l4, li)
assert_bitwise(g4, gi, "interleaved-vs-1f1b")
print("interleaved bitwise OK")

# --- 2. streamed inputs through the fused executor: bitwise --------------
l0, g0 = whisper_lg("1f1b", 4, 8, stream=False)
l1, g1 = whisper_lg("1f1b", 4, 8, stream=True)
assert np.array_equal(l0, l1), (l0, l1)
assert_bitwise(g0, g1, "streamed-1f1b")
lz1, gz1 = whisper_lg("zb", 4, 8, stream=True)
lz0, gz0 = whisper_lg("zb", 4, 8, stream=False)
assert np.array_equal(lz0, lz1)
assert_bitwise(gz0, gz1, "streamed-zb")
print("streamed fused OK")

# --- 3. resident state threaded through an F+B step ----------------------
n, m, mb, D = 2, 4, 2, 8
pcfg = ParallelConfig(pipe=n, tp=1, data=1, pod=1, n_micro=m,
                      schedule="1f1b", remat="full")
mesh = mesh_lib.make_smoke_mesh(pcfg)
W = jax.random.normal(key, (n, D, D)) * 0.3
x = jax.random.normal(jax.random.fold_in(key, 1), (m, mb, D))
labels = jax.random.normal(jax.random.fold_in(key, 2), (m, mb, D))

def stage_apply(p, carry, skips_in, resident, ctx):
    h = jnp.where(ctx.stage == 0, ctx.fresh["h"], carry["h"])
    h2 = jnp.tanh(h @ p)
    res = dict(resident)
    if "seen" in res:
        res["seen"] = jax.lax.dynamic_update_index_in_dim(
            resident["seen"], jnp.mean(h2), ctx.micro, 0)
    return {"h": h2}, {}, res

def loss_fn(hp, carry, la):
    return jnp.mean((carry["h"] - la["y"]) ** 2)

tplan = plan_lib.plan_for("1f1b", m, n)

def run(with_res):
    resident = {"seen": jnp.zeros((m,))} if with_res else {}
    def inner(rank, res):
        loss, gs, gh, ig, res2 = run_pipeline_tasks(
            stage_apply, W[rank[0]], {"h": x}, pcfg, tplan=tplan,
            head_params={}, loss_args_mb={"y": labels},
            loss_fn=loss_fn, resident=jax.tree.map(lambda a: a[0], res),
            rank=rank[0])
        return (loss[None], jax.tree.map(lambda a: a[None], gs),
                jax.tree.map(lambda a: a[None], res2))
    fn = jax.shard_map(inner, mesh=mesh, in_specs=(P("pipe"), P("pipe")),
                       out_specs=(P("pipe"), P("pipe"), P("pipe")),
                       axis_names={"pipe"}, check_vma=False)
    rk = jnp.arange(n, dtype=jnp.int32)
    rr = jax.tree.map(lambda a: jnp.stack([a] * n), resident)
    return jax.jit(lambda: fn(rk, rr))()

loss0, g0, _ = run(False)
loss1, g1, res = run(True)
# resident must not perturb the training computation ...
assert np.array_equal(np.asarray(loss0), np.asarray(loss1))
assert np.array_equal(np.asarray(g0), np.asarray(g1))
# ... and must hold each stage's per-micro statistics, updated on F ticks
h = x
expect = []
for j in range(n):
    h = jnp.tanh(h @ W[j])
    expect.append(jnp.mean(h, axis=(1, 2)))
np.testing.assert_allclose(np.asarray(res["seen"]), np.stack(expect),
                           rtol=1e-6)
print("resident fused OK")
print("UNIFIED EXTRAS OK")
"""


def test_unified_executor_skips_streaming_resident():
    """The tentpole's acceptance surface: (1) a skip-connection model runs
    ALL fused F+B schedules (gpipe_tasked / 1f1b / zb) with
    bitwise-identical losses and grads, matching the autodiff reference to
    tolerance; (2) interleaved:2 on half the ranks is bitwise-identical to
    1f1b on the full rank count (same global stage split — the same
    computation, reordered); (3) ``stream_inputs`` lowers to plan injection
    ticks and is bitwise vs replicated inputs for both fused and
    split-backward schedules; (4) resident state threads through an F+B
    step without perturbing gradients."""
    out = run_subprocess(UNIFIED_EXTRAS, n_devices=8, timeout=2400)
    assert "UNIFIED EXTRAS OK" in out
