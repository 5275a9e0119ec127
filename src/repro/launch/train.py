"""End-to-end training driver: data pipeline -> pipelined train step ->
optimizer -> async checkpoints, under the fault-tolerance supervisor.

CPU-runnable (reduced configs) and production-launchable (full configs on a
real mesh):

    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \\
        --smoke --steps 50 --ckpt-dir /tmp/ckpt

``--smoke`` uses the reduced arch on one device; otherwise the full
assigned config in bf16, pipelined over every device present.  Either way
``--pipe``/``--data`` set the layout over the devices present.

Fault-tolerance demo knobs: ``--fail-at`` injects plain preemptions,
``--shrink-at step:pool`` kills a slice for good (the supervisor re-plans
on the surviving pool via :class:`ElasticTrainer.rebuild` and resumes from
the last commit), ``--poison-at`` NaNs a batch (the optimizer's non-finite
guard skips the step and training continues).
"""
import argparse
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import set_mesh
from jax.profiler import TraceAnnotation as span
import numpy as np

from repro import configs
from repro.ckpt.checkpoint import CheckpointManager
from repro.configs.base import ParallelConfig, ShapeConfig
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import mesh as mesh_lib, sharding, steps
from repro.launch.cache import enable_compile_cache
from repro.models.lm import LMModel
from repro.optim import optimizers as optim
from repro.planner import search as planner_search
from repro.planner.hardware import HardwareSpec
from repro.runtime import elastic
from repro.runtime.fault_tolerance import (FaultInjector, StepWatchdog,
                                           Supervisor)


class ElasticTrainer:
    """Owns the (model, mesh, jitted step) triple and can REBUILD it for a
    different device pool mid-run — the planner-driven elastic restart
    path the Supervisor drives:

        PoolShrink -> choose_layout (keep tp, shrink pipe) -> planner
        search on the surviving pool -> restack checkpoint -> re-jit
        -> resume from the last commit, degraded.

    All the Supervisor hooks (``make_state`` / ``step`` / ``adapt_state``
    / ``meta`` / ``rebuild`` / ``on_straggler``) are bound methods, so a
    rebuild transparently redirects every subsequent call to the new
    layout.
    """

    def __init__(self, arch, pcfg: ParallelConfig, shape: ShapeConfig,
                 ocfg: optim.OptimizerConfig, *,
                 data: Optional[SyntheticLM] = None,
                 dtype=jnp.float32,
                 hardware: Optional[HardwareSpec] = None,
                 executors: Tuple[str, ...] = ("spmd",),
                 injector: Optional[FaultInjector] = None,
                 verbose: bool = False):
        self.arch = arch
        self.shape = shape
        self.ocfg = ocfg
        self.dtype = dtype
        self.hw = hardware or HardwareSpec(ranks=max(pcfg.pipe, 1))
        self.executors = tuple(executors)
        self.injector = injector
        self.verbose = verbose
        self.data = data or SyntheticLM(
            DataConfig(seed=0, vocab=arch.vocab, seq_len=shape.seq_len,
                       global_batch=shape.global_batch), arch)
        self.fingerprint = elastic.arch_fingerprint(arch)
        self.rebuilds: List[Dict[str, Any]] = []
        self._setup(pcfg)

    # ----------------------------------------------------------- assembly
    def _setup(self, pcfg: ParallelConfig):
        self.pcfg = pcfg
        self.model = LMModel(self.arch, pcfg, dtype=self.dtype)
        self.mesh = mesh_lib.make_smoke_mesh(pcfg)
        with set_mesh(self.mesh):
            # params and optimizer state are donated: at full width they do
            # not fit twice.  Checkpoints copy to host before the next step.
            self.jit_step = jax.jit(steps.build_train_step(
                self.model, pcfg, self.mesh, self.shape, self.ocfg),
                donate_argnums=(0, 1))

    def plan_layout(self, pool: int) -> ParallelConfig:
        """The layout a pool of ``pool`` devices gets: ``choose_layout``
        picks the shape (tp preserved, pipe shrunk), then the planner
        searches schedule x partition x microbatch on the shrunken
        hardware spec.  Pure — the elastic integration test calls this to
        build the reference run on the degraded pool directly."""
        new = elastic.choose_layout(pool, self.pcfg)
        report = planner_search.plan_arch(
            self.arch, self.shape, self.hw.with_(ranks=new.pipe),
            executors=self.executors)
        best = report.best
        if best is not None:
            new = best.spec.apply_to(new)
        return new

    def rebuild(self, pool: int):
        """Supervisor hook: re-plan + re-jit for the surviving pool."""
        new = self.plan_layout(pool)
        self.rebuilds.append({"pool": pool, "layout": new.layout_dict()})
        if self.verbose:
            print(f"[train] rebuild: pool={pool} -> pipe={new.pipe} "
                  f"data={new.data} schedule={new.schedule} "
                  f"m={new.n_micro}")
        self._setup(new)

    # ------------------------------------------------------- state hooks
    def make_state(self, restored):
        """Fresh (or restored) state, placed on the mesh: each stage's
        parameters and moments on its own pipe rank."""
        state = restored
        if state is None:
            params = self.model.init(jax.random.PRNGKey(0))
            state = {"params": params,
                     "opt": optim.init(
                         self.ocfg, params,
                         with_ef=self.pcfg.grad_compression == "int8_ef")}
        pspecs = sharding.param_specs(state["params"], self.mesh)
        specs = {"params": pspecs,
                 "opt": sharding.opt_state_specs(pspecs, state["opt"])}
        return jax.device_put(state, sharding.named(specs, self.mesh))

    def adapt_state(self, tree, extra):
        """Verify fingerprint + restack a restored checkpoint onto the
        CURRENT layout (no-op when the layout matches)."""
        n_stages = self.pcfg.pipe * self.pcfg.virtual_stages
        return elastic.adapt_state(tree, extra,
                                   fingerprint=self.fingerprint,
                                   layout=self.model.layout,
                                   n_stages=n_stages)

    def meta(self, step: int) -> Dict[str, Any]:
        """Checkpoint ``extra``: fingerprint + layout + mask + cursor."""
        return elastic.layout_meta(self.pcfg, self.model.layer_mask,
                                   fingerprint=self.fingerprint,
                                   data_step=step)

    # -------------------------------------------------------------- step
    def step(self, state, i: int):
        """One training step.  Its host phases are profiler spans
        (``train.step`` around ``train.batch``, ``train.put``,
        ``train.dispatch`` and ``train.readback``), seen in any
        ``jax.profiler`` trace of the run."""
        with span("train.step"):
            with span("train.batch"):
                batch = self.data.batch_at(i)
                if self.injector is not None:
                    batch = self.injector.maybe_poison(i, batch)
            with span("train.put"):
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
            with span("train.dispatch"), set_mesh(self.mesh):
                p, o, m = self.jit_step(state["params"], state["opt"], batch)
            with span("train.readback"):
                metrics = {"loss": float(m["loss"]),
                           "grad_norm": float(m["grad_norm"])}
                if "skipped" in m:
                    metrics["skipped"] = int(m["skipped"])
                    metrics["finite"] = float(m["finite"])
        return {"params": p, "opt": o}, metrics

    # --------------------------------------------------- straggler model
    def on_straggler(self, step: int) -> Optional[int]:
        """Model-backed escalation: price staying (one rank slowed by
        ``hw.straggler_factor``) against the planner's best plan on
        pool-minus-straggler; return the shrunken pool size when the
        restart wins, None to restart on the same pool."""
        profile = planner_search.profile_arch(self.arch, self.shape)
        out = planner_search.straggler_mitigation(
            profile, self.hw.with_(ranks=self.pcfg.pipe), self.pcfg.spec,
            executors=self.executors)
        if self.verbose:
            print(f"[train] straggler at step {step}: stay {out['stay_s']} "
                  f"vs remesh {out['remesh_s']} -> {out['action']}")
        if out["action"] != "restart":
            return None
        return (self.pcfg.pipe - 1) * self.pcfg.tp * self.pcfg.data \
            * self.pcfg.pod

    # -------------------------------------------------------- supervisor
    def supervisor(self, ckpt_dir: str, *, ckpt_every: int = 10,
                   keep: int = 3, watchdog: Optional[StepWatchdog] = None,
                   max_restarts: int = 8) -> Supervisor:
        return Supervisor(
            ckpt=CheckpointManager(ckpt_dir, keep=keep),
            make_state=self.make_state, step_fn=self.step,
            ckpt_every=ckpt_every, max_restarts=max_restarts,
            watchdog=watchdog, injector=self.injector,
            rebuild=self.rebuild, adapt_state=self.adapt_state,
            meta_fn=self.meta, on_straggler=self.on_straggler)


def _parse_shrink(specs: List[str]) -> Dict[int, int]:
    out: Dict[int, int] = {}
    for s in specs:
        try:
            step, pool = s.split(":")
            out[int(step)] = int(pool)
        except ValueError:
            raise SystemExit(f"--shrink-at wants step:pool, got {s!r}")
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m",
                    choices=configs.ARCH_NAMES)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--pipe", type=int, default=0,
                    help="pipe degree (default: 1 with --smoke, else every "
                         "device; on CPU, devices come from "
                         "--xla_force_host_platform_device_count)")
    ap.add_argument("--data", type=int, default=0,
                    help="data-parallel degree (default 1)")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject preemptions at these steps (demo/testing)")
    ap.add_argument("--shrink-at", nargs="*", default=[], metavar="STEP:POOL",
                    help="kill the pool down to POOL devices at STEP "
                         "(elastic re-plan demo)")
    ap.add_argument("--poison-at", type=int, nargs="*", default=[],
                    help="NaN the batch at these steps (skip-step guard "
                         "demo; needs a float-input arch, e.g. "
                         "whisper-tiny)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.smoke:
        arch = configs.smoke_arch(args.arch)
        pcfg = configs.smoke_parallel(args.arch).with_(
            pipe=args.pipe or 1, data=args.data or 1)
        dtype = jnp.float32
    else:
        arch = configs.get_arch(args.arch)
        pcfg = mesh_lib.fit_local(configs.get_parallel(args.arch),
                                  pipe=args.pipe, data=args.data)
        dtype = jnp.bfloat16

    shape = ShapeConfig("train", args.seq_len, args.batch, "train")
    pcfg = pcfg.with_(n_micro=configs.derive_n_micro(shape, pcfg))
    ocfg = optim.OptimizerConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                                 total_steps=args.steps,
                                 dynamic_loss_scale=not args.smoke)
    injector = FaultInjector(fail_at_steps=tuple(args.fail_at),
                             shrink_at=_parse_shrink(args.shrink_at),
                             poison_at_steps=tuple(args.poison_at))
    trainer = ElasticTrainer(arch, pcfg, shape, ocfg, dtype=dtype,
                             injector=injector, verbose=True)
    print(f"[train] {arch.name}: {arch.total_params()/1e6:.1f}M params, "
          f"pipe={pcfg.pipe} tp={pcfg.tp} m={pcfg.n_micro} "
          f"mesh={dict(trainer.mesh.shape)}")

    log_every = max(1, args.steps // 20)
    base_step = trainer.step

    def step(state, i):
        t0 = time.perf_counter()
        state, metrics = base_step(state, i)
        if i % log_every == 0:
            skipped = metrics.get("skipped", 0)
            print(f"[train] step {i:5d} loss {metrics['loss']:.4f} "
                  f"skipped {skipped} dt {time.perf_counter()-t0:.2f}s")
        return state, metrics

    sup = trainer.supervisor(args.ckpt_dir, ckpt_every=args.ckpt_every,
                             keep=2, watchdog=StepWatchdog(warmup=2))
    sup.step_fn = step
    out = sup.run(args.steps)
    losses = [h["loss"] for h in out["history"]]
    print(f"[train] done: loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"restarts={out['restarts']}, events={out['events']}, "
          f"stragglers={len(out['stragglers'])}")

    # Demo-mode assertions: injected faults must leave a healthy resumed
    # loss curve behind (the CI robustness job runs with --fail-at and
    # --shrink-at and relies on these).
    if args.fail_at or args.shrink_at:
        assert out["restarts"] >= 1, "injected fault did not restart"
        resumed = [h["loss"] for h in out["history"]
                   if h["step"] >= args.steps // 2]
        assert resumed and np.isfinite(resumed).all(), \
            "resumed loss curve has non-finite entries"
        assert losses[-1] < losses[0], \
            f"loss did not improve across restarts: {losses[0]} -> {losses[-1]}"
    if args.shrink_at:
        shrunk = _parse_shrink(args.shrink_at)
        assert trainer.rebuilds, "pool shrink did not trigger a rebuild"
        assert trainer.pcfg.pipe * trainer.pcfg.tp * trainer.pcfg.data \
            * trainer.pcfg.pod <= min(shrunk.values()), \
            "rebuild did not fit the surviving pool"
        print(f"[train] elastic restart ok: now pipe={trainer.pcfg.pipe} "
              f"(pool {min(shrunk.values())})")
    if args.poison_at:
        total_skipped = max(h.get("skipped", 0) for h in out["history"])
        assert total_skipped >= len(args.poison_at), \
            f"poisoned batches not skipped (counter={total_skipped})"
        print(f"[train] skip-step guard ok: {total_skipped} skipped")


if __name__ == "__main__":
    main()
