"""Fault-tolerant training supervisor: checkpoint/restart, retries,
preemption simulation, straggler-aware step watchdog, elastic re-mesh.

At thousand-node scale the train loop is a state machine around three
invariants:

  1. every batch is a pure function of (seed, step)  -> data replays exactly
     after restart (data/pipeline.py);
  2. (params, opt_state, step) is atomically checkpointed -> a restart
     resumes bit-identically from the last commit (ckpt/checkpoint.py);
  3. any step may die (preemption, ICI timeout, straggler)  -> the
     supervisor restores and retries with bounded backoff, re-creating the
     compiled step (a new jax client in a real redeploy).

``FaultInjector`` deterministically raises at chosen steps so the tests can
prove invariant 3 — including :class:`PoolShrink` events (a slice is lost
for good) and NaN batch poisoning (a data/hardware corruption the optimizer
guard must absorb).  ``StepWatchdog`` flags steps exceeding a straggler
multiple of the trailing median; the supervisor's ``on_straggler`` hook can
consult the planner's degraded-pool device model and restart onto
pool-minus-straggler (see elastic.py for the re-mesh path).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.ckpt.checkpoint import CheckpointManager


class Preemption(RuntimeError):
    """Simulated node loss / SIGTERM-style preemption."""


class PoolShrink(Preemption):
    """A slice died and is NOT coming back: ``pool`` devices survive.

    Unlike a plain :class:`Preemption` (same pool after restart), recovery
    requires the supervisor's ``rebuild`` hook: re-plan for the surviving
    pool, restack the checkpoint onto the new layout, resume degraded."""

    def __init__(self, step: int, pool: int):
        super().__init__(f"injected pool shrink at step {step}: "
                         f"{pool} devices survive")
        self.step = step
        self.pool = pool


class Straggler(Preemption):
    """A rank ran a step past the watchdog's straggler multiple."""

    def __init__(self, step: int, dt: float, median: float):
        super().__init__(f"straggler step {step}: {dt:.3f}s vs "
                         f"median {median:.3f}s")
        self.step = step
        self.dt = dt


@dataclass
class FaultInjector:
    """Deterministic fault injection for the supervisor tests.

    ``fail_at_steps`` raises ``exc`` (default plain preemption) once per
    step; ``shrink_at`` maps step -> surviving pool size and raises
    :class:`PoolShrink` once; ``poison_at_steps`` corrupts the batch's
    float leaves with NaN (every visit — a corrupt shard replays
    deterministically, exactly like the data pipeline would)."""
    fail_at_steps: Sequence[int] = ()
    exc: type = Preemption
    shrink_at: Mapping[int, int] = field(default_factory=dict)
    poison_at_steps: Sequence[int] = ()
    _raised: set = field(default_factory=set)
    _shrunk: set = field(default_factory=set)

    def maybe_fail(self, step: int):
        if step in self.shrink_at and step not in self._shrunk:
            self._shrunk.add(step)
            raise PoolShrink(step, int(self.shrink_at[step]))
        if step in self.fail_at_steps and step not in self._raised:
            self._raised.add(step)
            raise self.exc(f"injected fault at step {step}")

    def maybe_poison(self, step: int, batch: Any) -> Any:
        """NaN the first element of every float leaf of ``batch``.

        Int-only batches (token LMs) cannot carry a NaN — poisoning one
        is a clear error, not a silent no-op the test suite would
        misread as 'guard held'."""
        if step not in self.poison_at_steps:
            return batch

        poisoned = 0

        def poison(leaf):
            nonlocal poisoned
            dt = getattr(leaf, "dtype", None)
            if dt is not None and jnp.issubdtype(dt, jnp.floating):
                a = np.asarray(leaf).copy()
                a.flat[0] = float("nan")
                poisoned += 1
                return jnp.asarray(a)
            return leaf

        out = jax.tree.map(poison, batch)
        if not poisoned:
            raise ValueError(
                f"poison_at_steps={step}: batch has no float leaf to NaN "
                "(int token batches can't carry a NaN — poison a float "
                "input arch, e.g. whisper frames)")
        return out


@dataclass
class StepWatchdog:
    """Detects stragglers: steps slower than ``multiple``x the trailing
    median.  On real fleets this triggers slice replacement; here it
    records and (optionally) raises for the supervisor to restart.

    The first ``warmup`` observations after (re)start are excluded: the
    jit compile inflates them by orders of magnitude, and letting them
    into the window would poison the median for a full ``window`` steps
    (every healthy step would look like an inverse straggler, and a real
    straggler would hide under the compile spike)."""
    window: int = 16
    multiple: float = 3.0
    warmup: int = 1
    raise_on_straggler: bool = False
    times: List[float] = field(default_factory=list)
    stragglers: List[int] = field(default_factory=list)
    _warm: int = 0

    def restarted(self):
        """A restart re-jits: exclude the next ``warmup`` steps again."""
        self._warm = 0

    def observe(self, step: int, dt: float):
        if self._warm < self.warmup:
            self._warm += 1
            return
        hist = sorted(self.times[-self.window:])
        if hist:
            med = hist[len(hist) // 2]
            if dt > self.multiple * max(med, 1e-9):
                self.stragglers.append(step)
                if self.raise_on_straggler:
                    raise Straggler(step, dt, med)
        self.times.append(dt)


@dataclass
class Supervisor:
    """run() drives make_step()/state through n_steps with restart-on-fault.

    make_state(restored) -> state      (build or adopt restored pytree;
                                        must be traceable — the restore
                                        proto is built via jax.eval_shape)
    step_fn(state, step)  -> state, metrics
    state_for_ckpt(state) -> pytree    (what to persist)

    Elastic hooks (all optional):

    rebuild(pool)         -> None      re-plan + re-jit for a shrunken
                                       pool (PoolShrink recovery); the
                                       make_state/step_fn closures must
                                       reflect the new layout afterwards.
    adapt_state(tree, extra) -> tree   verify fingerprint / restack a
                                       restored checkpoint onto the
                                       current layout (elastic.adapt_state)
    meta_fn(step)         -> dict      extra metadata saved with each
                                       checkpoint (elastic.layout_meta)
    on_straggler(step)    -> pool|None model-backed mitigation: a pool
                                       size to rebuild onto (drop the
                                       straggler) or None to just restart
    """
    ckpt: CheckpointManager
    make_state: Callable[[Optional[Any]], Any]
    step_fn: Callable[[Any, int], Any]
    state_for_ckpt: Callable[[Any], Any] = lambda s: s
    ckpt_every: int = 10
    max_restarts: int = 8
    backoff_s: float = 0.0
    watchdog: Optional[StepWatchdog] = None
    injector: Optional[FaultInjector] = None
    rebuild: Optional[Callable[[int], None]] = None
    adapt_state: Optional[Callable[[Any, Dict], Any]] = None
    meta_fn: Optional[Callable[[int], Dict]] = None
    on_straggler: Optional[Callable[[int], Optional[int]]] = None

    def run(self, n_steps: int) -> Dict[str, Any]:
        restarts = 0
        history: List[Dict] = []
        events: List[Dict] = []
        while True:
            try:
                state, start = self._restore_or_init()
                for step in range(start, n_steps):
                    if self.injector is not None:
                        self.injector.maybe_fail(step)
                    t0 = time.perf_counter()
                    state, metrics = self.step_fn(state, step)
                    dt = time.perf_counter() - t0
                    if self.watchdog is not None:
                        self.watchdog.observe(step, dt)
                    history.append({"step": step, **metrics})
                    if (step + 1) % self.ckpt_every == 0:
                        self._save(step + 1, state)
                self._save(n_steps, state)
                self.ckpt.wait()
                return {"state": state, "history": history,
                        "restarts": restarts, "events": events,
                        "stragglers": (list(self.watchdog.stragglers)
                                       if self.watchdog else [])}
            except PoolShrink as e:
                restarts = self._count_restart(restarts, e)
                if self.rebuild is None:
                    raise RuntimeError(
                        f"pool shrank to {e.pool} devices but this "
                        "Supervisor has no rebuild hook — cannot restart "
                        "onto the degraded pool") from e
                events.append({"kind": "pool_shrink", "step": e.step,
                               "pool": e.pool, "restart": restarts})
                self.rebuild(e.pool)
                self._restarted()
            except Straggler as e:
                restarts = self._count_restart(restarts, e)
                pool = (self.on_straggler(e.step)
                        if self.on_straggler is not None else None)
                events.append({"kind": "straggler", "step": e.step,
                               "pool": pool, "restart": restarts})
                if pool is not None and self.rebuild is not None:
                    self.rebuild(pool)
                self._restarted()
            except Preemption as e:
                restarts = self._count_restart(restarts, e)
                events.append({"kind": "preemption", "restart": restarts})
                self._restarted()

    def _count_restart(self, restarts: int, e: Preemption) -> int:
        restarts += 1
        if restarts > self.max_restarts:
            raise RuntimeError(
                f"exceeded max_restarts={self.max_restarts}") from e
        if self.backoff_s:
            time.sleep(self.backoff_s * restarts)
        return restarts

    def _restarted(self):
        if self.watchdog is not None:
            self.watchdog.restarted()

    def _save(self, step: int, state):
        with jax.profiler.TraceAnnotation("train.ckpt_save"):
            extra = self.meta_fn(step) if self.meta_fn is not None else None
            self.ckpt.save(step, self.state_for_ckpt(state), extra=extra)

    def _restore_or_init(self):
        step = self.ckpt.latest_step()
        if step is None:
            return self.make_state(None), 0
        # Shapes only: restore matches leaves by flattened path name, so
        # the proto never needs real values — materializing full params
        # (and then throwing them away) would double every restart's init
        # cost for nothing.
        proto = jax.eval_shape(
            lambda: self.state_for_ckpt(self.make_state(None)))
        tree, meta = self.ckpt.restore(step, proto)
        if self.adapt_state is not None:
            tree = self.adapt_state(tree, meta.get("extra") or {})
        return self.make_state(tree), meta["step"]
