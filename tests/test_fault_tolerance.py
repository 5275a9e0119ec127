"""Robustness suite: checkpoint atomicity under injected mid-write crashes,
layout-agnostic restore (restack round-trip properties, fingerprint gate),
the optimizer's non-finite step guard, straggler pricing monotonicity, and
the planner-driven elastic-restart integration path.
"""
import os

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp

from conftest import run_subprocess
from repro.ckpt.checkpoint import CheckpointManager, _commit_step
from repro.core import plan as plan_lib
from repro.core import stage as stage_lib
from repro.optim import optimizers as optim
from repro.runtime import elastic
from repro.runtime.fault_tolerance import (FaultInjector, StepWatchdog,
                                           Straggler, Supervisor)


def _tree_bitwise_equal(a, b) -> bool:
    eq = jax.tree.map(
        lambda x, y: bool(np.array_equal(np.asarray(x), np.asarray(y))),
        a, b)
    return all(jax.tree.leaves(eq))


# ---------------------------------------------------------------------------
# Checkpoint hardening: stray files, mid-write crashes, async error surfacing
# ---------------------------------------------------------------------------

def test_commit_re_strict():
    assert _commit_step("step_000123.COMMIT") == 123
    for bad in ("notes.COMMIT", "step_abc.COMMIT", "step_1.COMMIT.bak",
                ".nfs00042", "step_000123", "astep_000123.COMMIT"):
        assert _commit_step(bad) is None


def test_latest_step_ignores_stray_files(tmp_path):
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, keep=2, async_write=False)
    mgr.save(3, {"w": jnp.arange(4.0)})
    mgr.save(6, {"w": jnp.arange(4.0) + 1})
    for stray in ("notes.COMMIT", "step_xx.COMMIT", ".nfs000",
                  "step_000003.COMMIT.bak"):
        open(os.path.join(d, stray), "w").write("junk")
    os.makedirs(os.path.join(d, "step_junkdir"), exist_ok=True)
    assert mgr.latest_step() == 6
    # _gc over the polluted listing must neither crash nor delete strays'
    # committed neighbors out of order
    mgr.save(9, {"w": jnp.arange(4.0) + 2})
    assert mgr.latest_step() == 9
    tree, meta = mgr.restore(9, {"w": jnp.zeros(4)})
    assert meta["step"] == 9
    assert np.array_equal(np.asarray(tree["w"]), np.arange(4.0) + 2)
    mgr.close()


@pytest.mark.parametrize("crash_point", ["pre_rename", "pre_commit"])
def test_mid_write_crash_keeps_previous_commit(tmp_path, crash_point):
    """Dying between staging and COMMIT must leave the previous committed
    step as the restore target (the atomic-commit protocol's whole job)."""
    d = str(tmp_path / "ckpt")

    def hook(point, step):
        if point == crash_point and step == 6:
            raise OSError(f"injected crash at {point} of step {step}")

    mgr = CheckpointManager(d, keep=4, fault_hook=hook)
    mgr.save(3, {"w": jnp.arange(4.0)})
    mgr.wait()
    mgr.save(6, {"w": jnp.full((4,), 9.0)})
    with pytest.raises(OSError, match="injected crash"):
        mgr.wait()                      # async writer error surfaces here
    assert mgr.latest_step() == 3       # step 6 never committed
    with pytest.raises(FileNotFoundError):
        mgr.restore(6, {"w": jnp.zeros(4)})
    tree, meta = mgr.restore(3, {"w": jnp.zeros(4)})
    assert meta["step"] == 3
    assert np.array_equal(np.asarray(tree["w"]), np.arange(4.0))
    # the manager stays usable: a later save commits cleanly
    mgr.save(9, {"w": jnp.arange(4.0) + 7})
    mgr.wait()
    assert mgr.latest_step() == 9
    mgr.close()


def test_async_write_error_surfaces_at_close(tmp_path):
    """A failed FINAL save (nobody calls save/wait again) must raise at
    close() rather than vanish with the daemon thread."""
    mgr = CheckpointManager(str(tmp_path / "ckpt"),
                            fault_hook=lambda p, s: (_ for _ in ()).throw(
                                RuntimeError("disk full")))
    mgr.save(5, {"w": jnp.zeros(3)})
    with pytest.raises(RuntimeError, match="disk full"):
        mgr.close()
    mgr.close()                         # idempotent after surfacing


# ---------------------------------------------------------------------------
# Watchdog warmup
# ---------------------------------------------------------------------------

def test_watchdog_warmup_excludes_compile_steps():
    wd = StepWatchdog(window=8, multiple=3.0, warmup=2)
    wd.observe(0, 30.0)                 # jit compile spikes: excluded
    wd.observe(1, 25.0)
    assert wd.times == [] and wd.stragglers == []
    for i in range(2, 8):
        wd.observe(i, 0.01)
    wd.observe(8, 0.5)                  # 50x the median -> straggler
    assert wd.stragglers == [8]
    # a restart re-jits: the next `warmup` observations are excluded again
    wd.restarted()
    wd.observe(9, 40.0)
    wd.observe(10, 35.0)
    assert wd.stragglers == [8]
    wd.observe(11, 0.5)                 # still slow vs surviving history
    assert wd.stragglers == [8, 11]


def test_watchdog_raises_typed_straggler():
    wd = StepWatchdog(window=4, multiple=3.0, warmup=0,
                      raise_on_straggler=True)
    for i in range(4):
        wd.observe(i, 0.01)
    with pytest.raises(Straggler) as ei:
        wd.observe(4, 1.0)
    assert ei.value.step == 4 and ei.value.dt == 1.0


# ---------------------------------------------------------------------------
# Non-finite step guard + dynamic loss scale
# ---------------------------------------------------------------------------

def _toy_state():
    params = {"w": jnp.arange(1.0, 5.0), "b": jnp.full((2,), 0.5)}
    return params


def test_nonfinite_guard_is_bitwise_noop():
    params = _toy_state()
    cfg = optim.OptimizerConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    state = optim.init(cfg, params)
    good = jax.tree.map(jnp.ones_like, params)
    params1, state1, m1 = optim.apply(cfg, state, params, good)
    assert float(m1["finite"]) == 1.0 and int(state1.skipped) == 0
    assert not _tree_bitwise_equal(params1, params)

    bad = {"w": jnp.array([1.0, jnp.nan, 1.0, 1.0]), "b": jnp.ones(2)}
    params2, state2, m2 = optim.apply(cfg, state1, params1, bad)
    assert float(m2["finite"]) == 0.0
    assert int(state2.skipped) == 1 and int(state2.good) == 0
    assert _tree_bitwise_equal(params2, params1)
    assert _tree_bitwise_equal(state2.mu, state1.mu)
    assert _tree_bitwise_equal(state2.nu, state1.nu)
    assert int(state2.step) == int(state1.step)   # skipped, not consumed
    assert float(m2["grad_norm"]) == 0.0          # no NaN leaks to metrics

    # inf is caught the same way as nan
    inf = {"w": jnp.full((4,), jnp.inf), "b": jnp.ones(2)}
    params3, state3, m3 = optim.apply(cfg, state2, params2, inf)
    assert float(m3["finite"]) == 0.0 and int(state3.skipped) == 2
    assert _tree_bitwise_equal(params3, params2)


def test_nonfinite_loss_alone_skips():
    params = _toy_state()
    cfg = optim.OptimizerConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    state = optim.init(cfg, params)
    good = jax.tree.map(jnp.ones_like, params)
    p2, s2, m2 = optim.apply(cfg, state, params, good,
                             loss=jnp.float32(jnp.nan))
    assert float(m2["finite"]) == 0.0 and int(s2.skipped) == 1
    assert _tree_bitwise_equal(p2, params)


def test_dynamic_loss_scale_halves_and_regrows():
    params = _toy_state()
    cfg = optim.OptimizerConfig(lr=1e-2, warmup_steps=1, total_steps=10,
                                dynamic_loss_scale=True,
                                loss_scale_growth=2)
    state = optim.init(cfg, params)
    assert float(state.scale) == 2.0 ** 15
    bad = {"w": jnp.array([jnp.inf, 0, 0, 0.0]), "b": jnp.zeros(2)}
    p, state, _ = optim.apply(cfg, state, params, bad)
    assert float(state.scale) == 2.0 ** 14        # overflow halves
    assert _tree_bitwise_equal(p, params)
    good = jax.tree.map(jnp.ones_like, params)
    p, state, _ = optim.apply(cfg, state, p, good)
    assert float(state.scale) == 2.0 ** 14 and int(state.good) == 1
    p, state, _ = optim.apply(cfg, state, p, good)
    assert float(state.scale) == 2.0 ** 15        # 2 finite steps -> regrow
    assert int(state.good) == 0


def test_guard_disabled_matches_legacy():
    params = _toy_state()
    on = optim.OptimizerConfig(lr=1e-2, warmup_steps=1, total_steps=10)
    off = optim.OptimizerConfig(lr=1e-2, warmup_steps=1, total_steps=10,
                                skip_nonfinite=False)
    good = jax.tree.map(jnp.ones_like, params)
    p1, s1, _ = optim.apply(on, optim.init(on, params), params, good)
    p2, s2, _ = optim.apply(off, optim.init(off, params), params, good)
    assert _tree_bitwise_equal(p1, p2)
    assert _tree_bitwise_equal(s1.mu, s2.mu)


# ---------------------------------------------------------------------------
# Fault injector: NaN poisoning
# ---------------------------------------------------------------------------

def test_poison_float_leaves_only():
    inj = FaultInjector(poison_at_steps=(3,))
    batch = {"tokens": jnp.ones((2, 4), jnp.int32),
             "frames": jnp.ones((2, 4), jnp.bfloat16)}
    out = inj.maybe_poison(3, batch)
    assert np.array_equal(np.asarray(out["tokens"]),
                          np.asarray(batch["tokens"]))
    assert not bool(jnp.isfinite(out["frames"]).all())
    # off-step: untouched
    same = inj.maybe_poison(4, batch)
    assert bool(jnp.isfinite(same["frames"]).all())


def test_poison_int_only_batch_is_an_error():
    inj = FaultInjector(poison_at_steps=(0,))
    with pytest.raises(ValueError, match="no float leaf"):
        inj.maybe_poison(0, {"tokens": jnp.ones((2, 4), jnp.int32)})


# ---------------------------------------------------------------------------
# Straggler-priced device model: monotone in the slowdown factor
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["gpipe", "1f1b", "gpipe_tasked", "zb"]),
       st.integers(min_value=2, max_value=4),
       st.integers(min_value=1, max_value=3),
       st.integers(min_value=0, max_value=3),
       st.tuples(st.floats(min_value=1.0, max_value=4.0),
                 st.floats(min_value=1.0, max_value=4.0)))
def test_rank_slowdown_monotone(schedule, n, k, r, factors):
    m = n * k
    r = r % n
    lo, hi = sorted(factors)
    base = plan_lib.plan_time(schedule, m, n)
    ones = plan_lib.plan_time(schedule, m, n, rank_slowdown=[1.0] * n)
    assert ones == pytest.approx(base)            # unit slowdown is free

    def slowed(f):
        slow = [1.0] * n
        slow[r] = f
        return plan_lib.plan_time(schedule, m, n, rank_slowdown=slow)

    t_lo, t_hi = slowed(lo), slowed(hi)
    assert t_lo >= base - 1e-9
    assert t_hi >= t_lo - 1e-9                    # monotone in the factor
    # a slow rank can never beat scaling the whole pipeline by the factor
    assert t_hi <= base * hi + 1e-9


def test_rank_slowdown_validation():
    with pytest.raises(ValueError):
        plan_lib.plan_time("gpipe", 4, 2, rank_slowdown=[1.0])
    with pytest.raises(ValueError):
        plan_lib.plan_time("gpipe", 4, 2, rank_slowdown=[1.0, 0.5])


# ---------------------------------------------------------------------------
# Layout-agnostic restack: round-trip property
# ---------------------------------------------------------------------------

def _rand_partition(rng, n_layers, n_stages):
    """Random composition of n_layers into n_stages positive parts."""
    cuts = sorted(rng.choice(np.arange(1, n_layers),
                             size=n_stages - 1, replace=False).tolist()) \
        if n_stages > 1 else []
    bounds = [0] + cuts + [n_layers]
    return [bounds[i + 1] - bounds[i] for i in range(n_stages)]


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=4, max_value=10),
       st.integers(min_value=1, max_value=4),
       st.integers(min_value=1, max_value=4),
       st.booleans(), st.booleans(),
       st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_restack_round_trip_property(L, n_a, n_b, uniform_a, uniform_b,
                                     seed):
    """stack@A + restack->B == stack@B bitwise, for uniform AND balance-
    partitioned layouts on both sides."""
    n_a, n_b = min(n_a, L), min(n_b, L)
    rng = np.random.default_rng(seed)
    layers = [{"w": jnp.asarray(rng.normal(size=(3, 2)).astype(np.float32)),
               "b": jnp.asarray(rng.normal(size=(2,)).astype(np.float32))}
              for _ in range(L)]
    part_a = None if uniform_a else _rand_partition(rng, L, n_a)
    part_b = None if uniform_b else _rand_partition(rng, L, n_b)
    stacked_a = stage_lib.stack_layer_params(layers, n_a, part_a)
    stacked_b = stage_lib.stack_layer_params(layers, n_b, part_b)
    lay_a = stage_lib.partition_layout(L, n_a, part_a)
    lay_b = stage_lib.partition_layout(L, n_b, part_b)
    out, mask = elastic.restack_stages(stacked_a, lay_a.mask, n_b,
                                       layout=lay_b)
    assert np.array_equal(mask, np.asarray(lay_b.mask))
    assert _tree_bitwise_equal(out, stacked_b)


def test_restack_rejects_wrong_layer_count():
    layers = [{"w": jnp.ones((2,))} for _ in range(6)]
    stacked = stage_lib.stack_layer_params(layers, 3)
    lay = stage_lib.partition_layout(6, 3)
    bad = stage_lib.partition_layout(5, 2)        # holds 5 layers, not 6
    with pytest.raises(ValueError, match="holds 5 layers"):
        elastic.restack_stages(stacked, lay.mask, 2, layout=bad)


def test_map_stages_touches_only_stage_subtrees():
    tree = {
        "params": {"stages": {"w": 1}, "embed": {"w": 2}},
        "opt": optim.OptState(
            step=jnp.int32(0),
            mu={"stages": {"w": 3}, "embed": {"w": 4}},
            nu={"stages": {"w": 5}, "embed": {"w": 6}},
            master={"stages": {"w": 7}}),
    }
    out = elastic._map_stages(tree, lambda sub: {"w": sub["w"] * 100})
    assert out["params"]["stages"]["w"] == 100
    assert out["params"]["embed"]["w"] == 2
    assert out["opt"].mu["stages"]["w"] == 300
    assert out["opt"].mu["embed"]["w"] == 4
    assert out["opt"].nu["stages"]["w"] == 500
    assert out["opt"].master["stages"]["w"] == 700
    assert isinstance(out["opt"], optim.OptState)


# ---------------------------------------------------------------------------
# Fingerprint gate
# ---------------------------------------------------------------------------

def test_adapt_state_fingerprint_gate():
    lay = stage_lib.partition_layout(4, 2)
    state = {"params": {"stages": {"w": jnp.ones((2, 2, 3))}}}
    fp = "deadbeefdeadbeef"
    meta_ok = {"fingerprint": fp,
               "layer_mask": np.asarray(lay.mask).tolist()}
    # same layout: identity, bitwise (the very same object)
    out = elastic.adapt_state(state, meta_ok, fingerprint=fp,
                              layout=lay, n_stages=2)
    assert out is state
    with pytest.raises(ValueError, match="no arch fingerprint"):
        elastic.adapt_state(state, {}, fingerprint=fp, layout=lay,
                            n_stages=2)
    with pytest.raises(ValueError, match="does not match"):
        elastic.adapt_state(state, {"fingerprint": "0badf00d0badf00d",
                                    "layer_mask": meta_ok["layer_mask"]},
                            fingerprint=fp, layout=lay, n_stages=2)


def test_arch_fingerprint_stability():
    a1 = {"d_model": 64, "n_layers": 4}
    a2 = {"n_layers": 4, "d_model": 64}           # key order irrelevant
    a3 = {"d_model": 65, "n_layers": 4}
    assert elastic.arch_fingerprint(a1) == elastic.arch_fingerprint(a2)
    assert elastic.arch_fingerprint(a1) != elastic.arch_fingerprint(a3)


# ---------------------------------------------------------------------------
# Supervisor: eval_shape restore proto, shrink-without-rebuild refusal
# ---------------------------------------------------------------------------

def test_supervisor_restore_proto_is_abstract(tmp_path):
    """The restore proto must be built under jax.eval_shape — make_state's
    arrays are tracers there, so a restart never pays a full init."""
    concrete_calls = []

    def make_state(restored):
        if restored is not None:
            return restored
        w = jnp.zeros((5,))
        concrete_calls.append(not isinstance(w, jax.core.Tracer))
        return {"w": w}

    def step_fn(state, i):
        return {"w": state["w"] + 1.0}, {"loss": float(i)}

    d = str(tmp_path / "ckpt")
    sup = Supervisor(ckpt=CheckpointManager(d), make_state=make_state,
                     step_fn=step_fn, ckpt_every=2)
    out = sup.run(4)
    assert concrete_calls == [True]               # one real init, no restore
    sup.ckpt.close()

    sup2 = Supervisor(ckpt=CheckpointManager(d), make_state=make_state,
                      step_fn=step_fn, ckpt_every=2)
    out2 = sup2.run(6)                            # resumes at step 4
    assert concrete_calls == [True, False]        # proto call was abstract
    assert [h["step"] for h in out2["history"]] == [4, 5]
    assert np.array_equal(np.asarray(out2["state"]["w"]), np.full((5,), 6.0))
    sup2.ckpt.close()
    assert np.array_equal(np.asarray(out["state"]["w"]), np.full((5,), 4.0))


def test_supervisor_shrink_without_rebuild_refuses(tmp_path):
    sup = Supervisor(
        ckpt=CheckpointManager(str(tmp_path / "ckpt")),
        make_state=lambda r: r if r is not None else {"w": jnp.zeros(2)},
        step_fn=lambda s, i: (s, {"loss": 0.0}),
        injector=FaultInjector(shrink_at={1: 2}))
    with pytest.raises(RuntimeError, match="no rebuild hook"):
        sup.run(4)
    sup.ckpt.close()


def test_supervisor_straggler_event_path(tmp_path):
    """raise_on_straggler routes through on_straggler and records the
    typed event; pool=None means restart-in-place."""
    import time as _time
    seen = []

    def step_fn(state, i):
        if i == 6 and not seen:
            _time.sleep(0.25)
        else:
            _time.sleep(0.002)
        return {"w": state["w"] + 1}, {"loss": 0.0}

    sup = Supervisor(
        ckpt=CheckpointManager(str(tmp_path / "ckpt")),
        make_state=lambda r: r if r is not None else {"w": jnp.zeros(2)},
        step_fn=step_fn, ckpt_every=4,
        watchdog=StepWatchdog(window=8, multiple=10.0, warmup=1,
                              raise_on_straggler=True),
        on_straggler=lambda step: seen.append(step))
    out = sup.run(8)
    assert seen == [6]
    assert [e["kind"] for e in out["events"]] == ["straggler"]
    assert out["stragglers"] == [6]
    assert out["restarts"] == 1
    sup.ckpt.close()


# ---------------------------------------------------------------------------
# Integration: NaN-poisoned batch through a real jitted train step
# ---------------------------------------------------------------------------

def test_whisper_poisoned_batch_skips_and_converges():
    """whisper-tiny smoke (bf16 float frames): one poisoned batch leaves
    params/opt bitwise unchanged, increments the skip counter, and the run
    keeps converging on the next clean batch."""
    run_subprocess("""
import numpy as np
import jax, jax.numpy as jnp
from repro import configs
from jax import set_mesh
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib, steps
from repro.models.lm import LMModel
from repro.optim import optimizers as optim
from repro.runtime.fault_tolerance import FaultInjector

arch = configs.smoke_arch("whisper-tiny")
pcfg = configs.smoke_parallel("whisper-tiny")
shape = ShapeConfig("train", 32, 4, "train")
pcfg = pcfg.with_(n_micro=configs.derive_n_micro(shape, pcfg))
ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=2, total_steps=10,
                             dynamic_loss_scale=True)
model = LMModel(arch, pcfg, dtype=jnp.float32)
mesh = mesh_lib.make_smoke_mesh(pcfg)
data = SyntheticLM(DataConfig(seed=0, vocab=arch.vocab, seq_len=32,
                              global_batch=4), arch)
inj = FaultInjector(poison_at_steps=(2,))
with set_mesh(mesh):
    step = jax.jit(steps.build_train_step(model, pcfg, mesh, shape, ocfg))
    params = model.init(jax.random.PRNGKey(0))
    opt = optim.init(ocfg, params)
    losses = []
    for i in range(5):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        batch = inj.maybe_poison(i, batch)
        p2, o2, m = step(params, opt, batch)
        if i == 2:
            eq = jax.tree.map(lambda a, b: bool(
                np.array_equal(np.asarray(a), np.asarray(b))), params, p2)
            assert all(jax.tree.leaves(eq)), "poisoned step moved params"
            assert float(m["finite"]) == 0.0
            assert int(o2.skipped) == 1
            assert float(o2.scale) == float(opt.scale) / 2
        else:
            assert float(m["finite"]) == 1.0
            losses.append(float(m["loss"]))
        params, opt = p2, o2
assert int(opt.skipped) == 1
assert losses[-1] < losses[0], f"did not converge: {losses}"
print("poison-skip ok", losses)
""", n_devices=1, timeout=600)


# ---------------------------------------------------------------------------
# Integration: elastic restart — shrink pipe=4 -> pipe=2 mid-run, resumed
# curve bitwise-matches a direct pipe=2 run restored from the same commit
# ---------------------------------------------------------------------------

def test_elastic_restart_matches_direct_degraded_run(tmp_path):
    run_subprocess(f"""
import os, shutil
import numpy as np
import jax.numpy as jnp
from repro import configs
from repro.configs.base import ShapeConfig
from repro.launch.train import ElasticTrainer
from repro.optim import optimizers as optim
from repro.planner import search as planner_search
from repro.planner.hardware import HardwareSpec
from repro.runtime import elastic
from repro.runtime.fault_tolerance import FaultInjector

arch = configs.smoke_arch("smollm-360m")
shape = ShapeConfig("train", 32, 4, "train")
pcfg = configs.smoke_parallel("smollm-360m").with_(pipe=4)
pcfg = pcfg.with_(n_micro=configs.derive_n_micro(shape, pcfg))
ocfg = optim.OptimizerConfig(lr=1e-3, warmup_steps=4, total_steps=12)
d1 = {str(tmp_path / 'elastic')!r}
d2 = {str(tmp_path / 'direct')!r}

# --- run A: pipe=4, pool shrinks to 2 devices at step 7 ------------------
inj = FaultInjector(shrink_at={{7: 2}})
tr = ElasticTrainer(arch, pcfg, shape, ocfg, injector=inj)
sup = tr.supervisor(d1, ckpt_every=3, keep=8)
out = sup.run(12)
assert out["restarts"] == 1
assert out["events"] == [{{"kind": "pool_shrink", "step": 7, "pool": 2,
                           "restart": 1}}]
assert tr.pcfg.pipe == 2, tr.pcfg
elastic_losses = {{}}
for h in out["history"]:          # last occurrence wins (post-restore)
    elastic_losses[h["step"]] = h["loss"]

# --- the degraded layout must equal the planner's own answer -------------
new = elastic.choose_layout(2, pcfg)
report = planner_search.plan_arch(arch, shape,
                                  HardwareSpec(ranks=new.pipe),
                                  executors=("spmd",))
ref_pcfg = report.best.spec.apply_to(new) if report.best else new
assert ref_pcfg.layout_dict() == tr.pcfg.layout_dict(), (
    ref_pcfg.layout_dict(), tr.pcfg.layout_dict())

# --- run B: direct pipe=2 restore from the SAME step-6 commit ------------
os.makedirs(d2)
shutil.copytree(os.path.join(d1, "step_000006"),
                os.path.join(d2, "step_000006"))
shutil.copy(os.path.join(d1, "step_000006.COMMIT"), d2)
tr_b = ElasticTrainer(arch, ref_pcfg, shape, ocfg)
sup_b = tr_b.supervisor(d2, ckpt_every=3, keep=8)
out_b = sup_b.run(12)
assert out_b["restarts"] == 0
steps_b = [h["step"] for h in out_b["history"]]
assert steps_b == list(range(6, 12)), steps_b

# --- post-restore loss curves must be BITWISE equal ----------------------
for h in out_b["history"]:
    a, b = elastic_losses[h["step"]], h["loss"]
    assert a == b, (h["step"], a, b)
print("elastic == direct over", steps_b)
""", n_devices=8, timeout=600)
