"""Native optimizers (no optax in this environment): AdamW, SGD+momentum,
global-norm clipping, warmup+cosine schedules.

Optimizer state mirrors the parameter pytree leaf-for-leaf, so every state
leaf inherits the parameter's sharding (FSDP/ZeRO: moments live sharded over
the ``data`` axis exactly like their parameters — the ZeRO-1/2 part of the
ZeRO-3 story; the parameter all-gather/grad reduce-scatter is GSPMD's job).
Master weights and moments are fp32 regardless of parameter dtype.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.scopes import OPTIMIZER, scoped


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: Any          # first moment (fp32), pytree like params
    nu: Any          # second moment (fp32) — zeros pytree for sgd
    master: Any      # fp32 master copy of params
    ef: Any = ()     # int8-EF gradient-compression residuals (or ())
    skipped: Any = ()  # int32 scalar: steps skipped by the non-finite guard
    good: Any = ()   # int32 scalar: consecutive finite steps (scale growth)
    scale: Any = ()  # fp32 scalar: current loss scale (1.0 when static)


@dataclass(frozen=True)
class OptimizerConfig:
    name: str = "adamw"          # adamw | sgd
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.01
    momentum: float = 0.9        # sgd
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10000
    min_lr_ratio: float = 0.1
    # Non-finite step guard: when any grad leaf (or the loss) is NaN/inf,
    # the whole update is discarded jit-safely (jnp.where on one global
    # flag — params and every optimizer moment stay bitwise unchanged)
    # and the skip counter increments.  One poisoned batch costs one
    # step, not the run.
    skip_nonfinite: bool = True
    # AMP-style dynamic loss scaling for reduced-precision grads: the
    # loss is multiplied by ``scale`` before autodiff, grads unscaled
    # here; overflow halves the scale (floored at min), and
    # ``loss_scale_growth`` consecutive finite steps double it.
    dynamic_loss_scale: bool = False
    init_loss_scale: float = 2.0 ** 15
    loss_scale_factor: float = 2.0
    loss_scale_growth: int = 200
    min_loss_scale: float = 1.0


def schedule(cfg: OptimizerConfig, step):
    """Linear warmup then cosine decay to min_lr_ratio."""
    step = step.astype(jnp.float32)
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (
        1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * cos


def global_norm(tree) -> jnp.ndarray:
    leaves = [jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves)) if leaves else jnp.zeros(())


def clip_by_global_norm(grads, max_norm: float):
    gn = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(gn, 1e-12))
    return jax.tree.map(lambda g: g * scale, grads), gn


def init(cfg: OptimizerConfig, params, *, with_ef: bool = False) -> OptState:
    """``with_ef`` allocates the error-feedback residual pytree for int8-EF
    gradient compression (ParallelConfig.grad_compression="int8_ef"); it
    mirrors the params leaf-for-leaf so it shards like the moments."""
    f32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    # a copy even where params are fp32 already: the step donates params
    # and optimizer state, which must not share a buffer
    master = jax.tree.map(lambda p: jnp.array(p, jnp.float32, copy=True),
                          params)
    scale0 = cfg.init_loss_scale if cfg.dynamic_loss_scale else 1.0
    return OptState(step=jnp.zeros((), jnp.int32),
                    mu=jax.tree.map(f32, params),
                    nu=jax.tree.map(f32, params),
                    master=master,
                    ef=jax.tree.map(f32, params) if with_ef else (),
                    skipped=jnp.zeros((), jnp.int32),
                    good=jnp.zeros((), jnp.int32),
                    scale=jnp.asarray(scale0, jnp.float32))


def _absent(field) -> bool:
    """True when an OptState guard field holds its legacy () placeholder."""
    return isinstance(field, tuple) and field == ()


def _all_finite(grads, loss=None) -> jnp.ndarray:
    """Scalar bool: every grad element (and the loss, if given) is finite."""
    flags = [jnp.all(jnp.isfinite(l)) for l in jax.tree.leaves(grads)]
    if loss is not None:
        flags.append(jnp.all(jnp.isfinite(loss)))
    return functools.reduce(jnp.logical_and, flags,
                            jnp.asarray(True)) if flags else jnp.asarray(True)


@scoped(OPTIMIZER)
def apply(cfg: OptimizerConfig, state: OptState, params, grads,
          *, loss=None) -> Tuple[Any, OptState, dict]:
    """One optimizer step. Returns (new_params, new_state, metrics).

    With ``cfg.skip_nonfinite`` (the default) the update is gated on a
    global finiteness flag over the raw grads (and ``loss``, when the
    caller passes it): a non-finite step leaves params and every state
    moment bitwise unchanged via ``jnp.where`` — jit-safe, no host sync —
    and increments ``state.skipped``.  With ``cfg.dynamic_loss_scale``
    the incoming grads (and ``loss``) are SCALED by ``state.scale``;
    overflow is detected on the scaled grads, then grads are unscaled
    before clipping/moments.  Legacy states (guard fields ``()``) take
    the exact pre-guard path.
    """
    grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
    dyn = cfg.dynamic_loss_scale and not _absent(state.scale)
    guarded = (cfg.skip_nonfinite or dyn) and not _absent(state.skipped)
    finite = _all_finite(grads, loss) if guarded else None
    if dyn:
        inv = 1.0 / state.scale
        grads = jax.tree.map(lambda g: g * inv, grads)
    if cfg.clip_norm > 0:
        grads, gn = clip_by_global_norm(grads, cfg.clip_norm)
    else:
        gn = global_norm(grads)
    step = state.step + 1
    lr = schedule(cfg, step)

    if cfg.name == "adamw":
        b1, b2 = cfg.b1, cfg.b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g,
                          state.nu, grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(p32, m, v):
            mhat = m / c1
            vhat = v / c2
            return p32 - lr * (mhat / (jnp.sqrt(vhat) + cfg.eps)
                               + cfg.weight_decay * p32)
        master = jax.tree.map(upd, state.master, mu, nu)
    elif cfg.name == "sgd":
        mu = jax.tree.map(lambda m, g: cfg.momentum * m + g, state.mu, grads)
        nu = state.nu
        master = jax.tree.map(
            lambda p32, m: p32 - lr * (m + cfg.weight_decay * p32),
            state.master, mu)
    else:
        raise ValueError(f"unknown optimizer {cfg.name!r}")

    new_params = jax.tree.map(lambda p, p32: p32.astype(p.dtype),
                              params, master)
    if finite is None:
        new_state = OptState(step=step, mu=mu, nu=nu, master=master,
                             ef=state.ef, skipped=state.skipped,
                             good=state.good, scale=state.scale)
        return new_params, new_state, {"grad_norm": gn, "lr": lr}

    # Gate EVERYTHING on the one global flag: a skipped step is bitwise
    # a no-op (params, moments, master, step count all unchanged).
    def gate(new, old):
        return jax.tree.map(lambda n, o: jnp.where(finite, n, o), new, old)

    new_params = gate(new_params, params)
    mu = gate(mu, state.mu)
    nu = gate(nu, state.nu)
    master = gate(master, state.master)
    step = jnp.where(finite, step, state.step)
    skipped = state.skipped + jnp.where(finite, 0, 1).astype(jnp.int32)
    good = jnp.where(finite, state.good + 1, 0).astype(jnp.int32)
    scale = state.scale
    if dyn:
        grown = good >= cfg.loss_scale_growth
        scale_ok = jnp.where(grown, state.scale * cfg.loss_scale_factor,
                             state.scale)
        good = jnp.where(grown, 0, good).astype(jnp.int32)
        scale_bad = jnp.maximum(state.scale / cfg.loss_scale_factor,
                                cfg.min_loss_scale)
        scale = jnp.where(finite, scale_ok, scale_bad)
    new_state = OptState(step=step, mu=mu, nu=nu, master=master,
                         ef=state.ef, skipped=skipped, good=good,
                         scale=scale)
    metrics = {"grad_norm": jnp.where(finite, gn, 0.0), "lr": lr,
               "finite": finite.astype(jnp.float32), "skipped": skipped,
               "loss_scale": state.scale}
    return new_params, new_state, metrics
