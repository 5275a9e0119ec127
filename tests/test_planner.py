"""Automatic planner: spec API, budget invariant, BENCH dominance, bitwise.

The two ISSUE-6 tripwires live here as properties:
  (a) planner-predicted peak memory never exceeds the hardware budget its
      chosen plan declared;
  (b) on every BENCH_schedules.json row, the planner's top choice has
      device-model step time <= the hand-picked config for that row.
"""
import json
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs
from repro.configs.base import (ParallelConfig, PlanSpec, ScheduleSpec,
                                ShapeConfig, parse_schedule)
from repro.core import balance as B
from repro.core import plan as plan_lib
from repro.core.stage import pad_layout, partition_layout
from repro.launch import steps
from repro.planner import (HardwareSpec, PlanReport, plan_profile,
                           profile_arch, profile_unet, score_candidate)
from repro.planner.smoke import _row_spec

BENCH = os.path.join(os.path.dirname(__file__), "..",
                     "BENCH_schedules.json")


# ---------------------------------------------------------------------------
# Structured spec API (satellite 1)
# ---------------------------------------------------------------------------

def test_schedule_spec_roundtrip_and_shim():
    for s in ("gpipe", "1f1b", "zb", "interleaved:3", "gpipe_tasked"):
        spec = ScheduleSpec.from_string(s)
        assert spec.name == s
        assert ScheduleSpec.from_dict(spec.to_dict()) == spec
        assert parse_schedule(s) == (spec.base, spec.virtual_stages)
    with pytest.raises(ValueError, match="virtual"):
        ScheduleSpec.from_string("interleaved:0")
    with pytest.raises(ValueError):
        ScheduleSpec(base="nope")


def test_plan_spec_roundtrip_and_apply():
    spec = PlanSpec(
        schedule=ScheduleSpec(base="zb", residuals="reuse", executor="mpmd"),
        pipe=4, microbatches=8, partition=(2, 1, 1, 0))
    assert PlanSpec.from_dict(spec.to_dict()) == spec
    base = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=2)
    pcfg = spec.apply_to(base)
    hand = ParallelConfig(pipe=4, tp=1, data=1, pod=1, n_micro=8,
                          schedule="zb", residuals="reuse", executor="mpmd",
                          partition=(2, 1, 1, 0))
    assert pcfg == hand
    assert pcfg.spec == spec


def test_parallel_config_validates_partition():
    with pytest.raises(ValueError, match="partition"):
        ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=2,
                       partition=(1, 2, 3))
    ok = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=2,
                        partition=[3, 1])
    assert ok.partition == (3, 1)


# ---------------------------------------------------------------------------
# Partitioned stage layout (satellite 3)
# ---------------------------------------------------------------------------

@given(st.integers(1, 40), st.integers(1, 8))
@settings(max_examples=60, deadline=None)
def test_partition_layout_matches_legacy_uniform(n_layers, n_stages):
    L, mask = pad_layout(n_layers, n_stages)
    lay = partition_layout(n_layers, n_stages)
    assert lay.L_per_stage == L
    assert np.array_equal(lay.mask, mask)
    assert sum(lay.sizes) == n_layers
    # flat front-to-back fill: slot (s, l) holds layer s*L + l
    for s in range(n_stages):
        for l in range(lay.sizes[s]):
            assert lay.slot_layer[s, l] == s * L + l


@given(st.integers(2, 24), st.integers(2, 6))
@settings(max_examples=40, deadline=None)
def test_partition_layout_balanced(n_layers, n_stages):
    sizes = B.block_partition([1.0] * n_layers, n_stages)
    lay = partition_layout(n_layers, n_stages, sizes)
    assert lay.sizes == tuple(sizes)
    # every real layer appears exactly once, contiguously per stage
    seen = sorted(int(x) for x in lay.slot_layer.reshape(-1) if x >= 0)
    assert seen == list(range(n_layers))
    for s in range(n_stages):
        lo, hi = lay.bounds[s], lay.bounds[s + 1]
        assert list(lay.slot_layer[s, :lay.sizes[s]]) == list(range(lo, hi))
        if lay.sizes[s]:
            assert lay.stage_of(lo) == s


def test_stage_partition_wires_balance():
    arch = configs.smoke_arch("smollm-360m")
    pcfg = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=2)
    for by in ("flops", "size"):
        part = steps.stage_partition(arch, pcfg, by=by, seq_len=64)
        assert len(part) == 2 and sum(part) == arch.n_layers
    with pytest.raises(ValueError, match="objective"):
        steps.stage_partition(arch, pcfg, by="vibes")


def test_arch_layer_costs_encdec():
    arch = configs.smoke_arch("whisper-tiny")
    flops, pbytes = B.arch_layer_costs(arch, 64)
    assert len(flops) == arch.enc_layers + arch.n_layers
    # decoder layers carry the cross-attention extra
    assert min(pbytes[arch.enc_layers:]) > max(pbytes[:arch.enc_layers])


# ---------------------------------------------------------------------------
# Hardware spec (tentpole input)
# ---------------------------------------------------------------------------

def test_hardware_yaml_roundtrip(tmp_path):
    text = ("name: test-8\nranks: 8\nmemory_bytes: 1073741824\n"
            "flops: 1.0e12\nici_bytes_per_s: 1.0e9\n")
    p = tmp_path / "hardware.yaml"
    p.write_text(text)
    hw = HardwareSpec.from_yaml(str(p))
    assert (hw.name, hw.ranks) == ("test-8", 8)
    assert hw == HardwareSpec.from_dict(hw.to_dict())
    from repro.planner.hardware import _parse_flat_yaml
    flat = _parse_flat_yaml(text)
    assert HardwareSpec.from_dict(flat) == hw
    with pytest.raises(ValueError, match="unknown"):
        HardwareSpec.from_dict({"ranks": 2, "warp_drive": 9})


def test_plan_cost_uniform_weights_match_default():
    pc0 = plan_lib.plan_cost("1f1b", 6, 3)
    pc1 = plan_lib.plan_cost("1f1b", 6, 3, stage_weights=[1.0, 1.0, 1.0])
    assert pc0.t_end == pytest.approx(pc1.t_end)
    assert pc0.park == pc1.park and pc0.resid == pc1.resid


# ---------------------------------------------------------------------------
# Tripwire (a): hypothesis budget invariant
# ---------------------------------------------------------------------------

@given(st.integers(1, 3).map(lambda k: 2 ** k),     # ranks 2/4/8
       st.integers(20, 34),                         # log2 memory budget
       st.sampled_from(["smollm-360m", "whisper-tiny"]),
       st.integers(3, 5).map(lambda k: 2 ** k))     # global batch
@settings(max_examples=12, deadline=None)
def test_planner_respects_memory_budget(ranks, logmem, arch_name, batch):
    arch = configs.smoke_arch(arch_name)
    shape = ShapeConfig("smoke", 64, batch, "train")
    hw = HardwareSpec(ranks=ranks, memory_bytes=float(2 ** logmem))
    report = plan_profile(profile_arch(arch, shape), hw,
                          shape_name=shape.name,
                          microbatches=[m for m in (1, 2, 4, batch)
                                        if batch % m == 0])
    for c in report.candidates:
        if c.feasible:
            assert max(c.mem_bytes) <= hw.memory_bytes
    best = report.best
    if best is not None:
        assert best.feasible
        assert max(best.mem_bytes) <= hw.memory_bytes
    else:
        assert all(not c.feasible for c in report.candidates)


def test_planner_report_json_roundtrip():
    arch = configs.smoke_arch("smollm-360m")
    shape = ShapeConfig("smoke", 64, 8, "train")
    report = plan_profile(profile_arch(arch, shape),
                          HardwareSpec(ranks=2, memory_bytes=2.0 * 2**30),
                          shape_name=shape.name, microbatches=[2, 4])
    again = PlanReport.from_json(report.to_json())
    assert again.to_dict() == report.to_dict()
    assert again.best.spec == report.best.spec


def test_planner_executor_restriction():
    arch = configs.smoke_arch("smollm-360m")
    shape = ShapeConfig("smoke", 64, 8, "train")
    profile = profile_arch(arch, shape)
    hw = HardwareSpec(ranks=2, memory_bytes=2.0 * 2**30)
    report = plan_profile(profile, hw, shape_name=shape.name,
                          executors=("spmd",))
    assert report.candidates
    assert all(c.spec.schedule.executor == "spmd"
               for c in report.candidates)
    pcfg = ParallelConfig.auto(arch, shape, hw, executors=("spmd",))
    assert pcfg.executor == "spmd"


# ---------------------------------------------------------------------------
# Tripwire (b): BENCH dominance (planner top <= every hand-picked row)
# ---------------------------------------------------------------------------

@given(st.integers(0, 37))
@settings(max_examples=38, deadline=None)
def test_planner_dominates_bench_rows(idx):
    with open(BENCH) as f:
        rows = json.load(f)["rows"]
    row = rows[idx % len(rows)]
    batch = 16
    if batch % int(row["n_micro"]):
        return
    if row["model"] == "lm":
        profile = profile_arch(configs.smoke_arch("smollm-360m"),
                               ShapeConfig("smoke", 128, batch, "train"))
    else:
        from repro.models.unet import UNetConfig
        profile = profile_unet(UNetConfig(B=1, C=4, levels=3, img=32), batch)
    hw = HardwareSpec(ranks=int(row["pipe"]), memory_bytes=64.0 * 2**30)
    report = plan_profile(profile, hw, shape_name="bench")
    hand = score_candidate(profile, hw, _row_spec(row))
    top = report.best
    assert top is not None
    assert top.step_s <= hand.step_s * (1 + 1e-9), \
        (row["schedule"], row["n_micro"], top.step_s, hand.step_s)


# ---------------------------------------------------------------------------
# Acceptance: plan #1 trains bitwise-identically to the hand-built config
# ---------------------------------------------------------------------------

def test_auto_plan_trains_bitwise_like_hand_config():
    from conftest import run_subprocess
    run_subprocess("""
        import jax, jax.numpy as jnp
        from jax import set_mesh
        from repro import configs
        from repro.configs.base import ParallelConfig, PlanSpec, ShapeConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.launch import mesh as mesh_lib, steps
        from repro.models.lm import LMModel
        from repro.optim import optimizers as optim
        from repro.planner import HardwareSpec, plan_arch

        arch = configs.smoke_arch("smollm-360m")
        shape = ShapeConfig("smoke", 32, 8, "train")
        hw = HardwareSpec(ranks=2, memory_bytes=2.0 * 2**30)
        report = plan_arch(arch, shape, hw)
        best = report.best.spec
        # round-trip through the JSON report, exactly like dryrun --plan
        best = PlanSpec.from_dict(
            type(report).from_json(report.to_json()).best.spec.to_dict())
        base = ParallelConfig(pipe=hw.ranks, tp=1, data=1, pod=1, n_micro=1)
        pcfg_auto = best.apply_to(base)
        pcfg_hand = base.with_(
            pipe=best.pipe, n_micro=best.microbatches,
            schedule=best.schedule.name,
            residuals=best.schedule.residuals,
            executor=best.schedule.executor, partition=best.partition)
        assert pcfg_auto == pcfg_hand

        def losses(pcfg):
            mesh = mesh_lib.make_smoke_mesh(pcfg)
            model = LMModel(arch, pcfg, dtype=jnp.float32)
            params = model.init(jax.random.PRNGKey(0))
            ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=2,
                                         total_steps=4)
            opt = optim.init(ocfg, params)
            data = SyntheticLM(DataConfig(vocab=arch.vocab, seq_len=32,
                                          global_batch=8))
            out = []
            with set_mesh(mesh):
                step = jax.jit(steps.build_train_step(model, pcfg, mesh,
                                                      shape, ocfg))
                for i in range(3):
                    batch = {k: jnp.asarray(v)
                             for k, v in data.batch_at(i).items()}
                    params, opt, m = step(params, opt, batch)
                    out.append(float(m["loss"]))
            return out

        la, lh = losses(pcfg_auto), losses(pcfg_hand)
        assert la == lh, (la, lh)
        print("bitwise ok", la)
    """, n_devices=2, timeout=560)


def test_balanced_partition_trains_close_to_uniform():
    from conftest import run_subprocess
    run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import set_mesh
        from repro import configs
        from repro.configs.base import ParallelConfig, ShapeConfig
        from repro.data.pipeline import DataConfig, SyntheticLM
        from repro.launch import mesh as mesh_lib, steps
        from repro.models.lm import LMModel
        from repro.optim import optimizers as optim

        arch = configs.smoke_arch("smollm-360m")   # 4 layers
        shape = ShapeConfig("smoke", 32, 8, "train")
        base = ParallelConfig(pipe=2, tp=1, data=1, pod=1, n_micro=2,
                              schedule="1f1b")
        data = SyntheticLM(DataConfig(vocab=arch.vocab, seq_len=32,
                                      global_batch=8))

        def loss_of(pcfg):
            mesh = mesh_lib.make_smoke_mesh(pcfg)
            model = LMModel(arch, pcfg, dtype=jnp.float32)
            params = model.init(jax.random.PRNGKey(0))
            opt = optim.init(optim.OptimizerConfig(), params)
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
            with set_mesh(mesh):
                step = jax.jit(steps.build_train_step(
                    model, pcfg, mesh, shape))
                _, _, m = step(params, opt, batch)
            return float(m["loss"])

        l_uniform = loss_of(base)
        l_cut = loss_of(base.with_(partition=(3, 1)))
        # same math, different stage cuts: layer params are drawn from the
        # same per-layer keys, so losses agree to float tolerance
        assert np.isclose(l_uniform, l_cut, rtol=1e-5), (l_uniform, l_cut)
        print("partition ok", l_uniform, l_cut)
    """, n_devices=2, timeout=560)
