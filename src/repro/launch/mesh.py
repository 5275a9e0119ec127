"""Mesh construction: the local mesh every entry point runs on, and the
production grid (assignment spec) that ``launch/dryrun.py`` compiles for.

``make_production_mesh`` is exactly the assignment's canonical grid:
``(data=16, model=16)`` per pod, ``(pod=2, data=16, model=16)`` multi-pod.
Per architecture, the ``model`` axis factors into ``pipe × tp`` over the same
device grid (MaxText-style ici_pipeline × ici_tensor) via
:func:`make_arch_mesh`; the ``tp`` axis is innermost so tensor-parallel
collectives ride adjacent ICI links while the pipeline's single-hop
``collective-permute`` tolerates the stride.

Nothing here touches jax device state at import time — meshes are built
inside functions only.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh

from repro.configs.base import ParallelConfig


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=jax.devices()[:n])


def make_arch_mesh(pcfg: ParallelConfig, *, base: Mesh) -> Mesh:
    """Refine the production mesh ``base``'s ``model`` axis into
    ``pipe × tp``.

    Returns a 4-axis mesh ``(pod, data, pipe, tp)`` over the identical device
    grid (pod=1 single-pod).
    """
    devs = np.asarray(base.devices)
    if devs.ndim == 2:
        devs = devs[None]                       # (pod=1, data, model)
    pod, data, model = devs.shape
    if (pod, data) != (pcfg.pod, pcfg.data) or pcfg.model_axis != model:
        raise ValueError(
            f"parallel config (pod={pcfg.pod}, data={pcfg.data}, "
            f"pipe={pcfg.pipe}, tp={pcfg.tp}, dp2={pcfg.dp2}) does not tile "
            f"the production grid {devs.shape}")
    # model axis factors as (dp2, pipe, tp): surplus model-axis capacity for
    # small architectures becomes extra data parallelism (dp2), keeping the
    # assignment's canonical (data, model) grid intact.
    grid = devs.reshape(pod, data, pcfg.dp2, pcfg.pipe, pcfg.tp) \
        .reshape(pod, data * pcfg.dp2, pcfg.pipe, pcfg.tp)
    return Mesh(grid, ("pod", "data", "pipe", "tp"),
                axis_types=(AxisType.Auto,) * 4)


# The chain-collective topology lives next to the plan IR (one definition
# for the executor, comm accounting, and tests); re-exported here because
# mesh construction is where device-topology questions get asked first.
from repro.core.plan import pipe_ring_perm  # noqa: E402,F401


def fit_local(pcfg: ParallelConfig, *, pipe: int = 0,
              data: int = 0) -> ParallelConfig:
    """``pcfg`` laid out over the devices present: one pod, no tensor
    parallelism, ``data`` replicas (default 1) of a ``pipe``-stage pipeline
    (default: every remaining device)."""
    n = len(jax.devices())
    data = data or 1
    pipe = pipe or max(1, n // data)
    if pipe * data > n:
        raise ValueError(f"pipe={pipe} x data={data} needs {pipe * data} "
                         f"devices, {n} present")
    return pcfg.with_(pod=1, tp=1, dp2=1, data=data, pipe=pipe)


def make_smoke_mesh(pcfg: ParallelConfig) -> Mesh:
    """Mesh over the first ``pod * data * pipe * tp`` local devices: the
    reduced configs, and the entry points' runs on the devices present."""
    n = pcfg.pod * pcfg.data * pcfg.pipe * pcfg.tp
    devs = np.array(jax.devices()[:n]).reshape(
        pcfg.pod, pcfg.data, pcfg.pipe, pcfg.tp)
    return Mesh(devs, ("pod", "data", "pipe", "tp"),
                axis_types=(AxisType.Auto,) * 4)
