"""The comparison that decides ``correct`` for a training cell.

The program's first steps are read during set-up, through the trainer's own
call on the window's own feed; the reference follows the same steps from the
same weights.  Three numbers are compared, each against a limit kept in
``bench/workloads/<cell>.json`` where it has one:

- ``loss_gap``: ``|loss - loss_ref| / loss_ref`` of the first step, at the
  seeded weights.  The later steps' losses are printed beside it but not
  compared: the program computes with bf16 copies of its fp32 master
  weights, and the warm-up's first updates (1.5e-5 to 4.5e-5) are below
  half a bf16 ulp of most weights, so the program's second and third losses
  read a model that has taken only part of an update the master holds in
  full (see PERF.md, Findings).
- ``grad_gap``: over parameters (each layer's its own), the largest gap
  between the first step's gradient norm in the program and in the
  reference, over the larger of the reference's norm of that parameter and
  its median parameter's.  The program's gradient is read from Adam's first
  moment after one step, ``mu = (1 - b1) * clip * g`` with
  ``clip = min(1, clip_norm / |g|)`` and ``|g|`` the global norm the step
  reports.
- ``change_gap``: the same measure of the norms of each parameter's change
  over the steps, read from the optimizer's fp32 master weights before the
  window's first step takes them.  Parameters whose first gradient in the
  reference is under a thousandth of the median parameter's are left out:
  under Adam they move by round-off alone.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import numpy as np

NAMES = ("loss_gap", "grad_gap", "change_gap")


def _flat(norms: Dict[str, np.ndarray]) -> Dict[Tuple[str, int], float]:
    out = {}
    for name, v in norms.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        for i, x in enumerate(v):
            out[(name, i)] = float(x)
    return out


def worst_gap(prog: Dict[Tuple[str, int], float],
              ref: Dict[Tuple[str, int], float], keep=None) -> float:
    """Largest ``|p - r| / max(r, median r)`` over the kept parameters;
    inf where a number is not finite or a parameter is missing."""
    keys = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref) or not keys:
        return math.inf
    med = float(np.median([ref[k] for k in keys]))
    gaps = [abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys]
    return max(gaps) if all(map(math.isfinite, gaps)) else math.inf


def program_grad_norms(moment_norms, grad_norm: float, optimizer: Dict):
    """The first step's raw gradient norms, from Adam's first moment after
    one step and the global norm the step reports."""
    clip = min(1.0, optimizer["clip_norm"] / max(grad_norm, 1e-12))
    return {k: np.asarray(v) / ((1 - optimizer["b1"]) * clip)
            for k, v in moment_norms.items()}


def gaps(run: Dict, ref: Dict) -> Dict[str, float]:
    """Compare ``run`` (the program's readings, or a planted fault's) with
    ``ref`` (the reference's).  Each holds ``losses``, ``grad_norms`` (the
    first step's raw gradient norm per parameter and layer) and
    ``change_norms`` (the change over the steps, likewise)."""
    loss = abs(run["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0])
    g_ref = _flat(ref["grad_norms"])
    med = float(np.median(list(g_ref.values())))
    moved = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    return {"loss_gap": loss if math.isfinite(loss) else math.inf,
            "grad_gap": worst_gap(_flat(run["grad_norms"]), g_ref),
            "change_gap": worst_gap(_flat(run["change_norms"]),
                                    _flat(ref["change_norms"]), moved)}


def judge(values: Dict[str, float], limits: Dict[str, float]
          ) -> Tuple[bool, List[Tuple[str, float, float]]]:
    """``(correct, [(name, value, limit), ...])`` over the numbers the
    cell's limits name: a number with no upper reading has no limit, is
    printed but not compared (PERF.md gives its readings)."""
    rows = [(k, values[k], float(limits[k])) for k in NAMES if k in limits]
    return bool(rows) and all(v <= lim for _, v, lim in rows), rows
