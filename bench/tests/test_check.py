"""The arithmetic of the comparison that decides ``correct``."""
import math

import numpy as np
import pytest

from bench import check

OPT = {"b1": 0.9, "clip_norm": 1.0}


def _run(loss, g, d):
    return {"losses": loss, "grad_norms": g, "change_norms": d}


def test_worst_gap_uses_the_median_as_floor():
    ref = {("a", 0): 1.0, ("a", 1): 2.0, ("b", 0): 1e-6}
    prog = {("a", 0): 1.1, ("a", 1): 2.0, ("b", 0): 2e-6}
    # b's own norm is tiny: its gap counts against the median (1.0)
    assert check.worst_gap(prog, ref) == pytest.approx(0.1)
    assert check.worst_gap({("a", 0): 1.0}, ref) == math.inf
    assert check.worst_gap({**prog, ("a", 0): math.nan}, ref) == math.inf


def test_program_gradient_from_the_first_moment():
    # mu = (1 - b1) * clip * g, clip = 1 / |g| when |g| > clip_norm
    g = np.array([3.0, 4.0])
    mu = 0.1 * g / 5.0
    back = check.program_grad_norms({"w": mu}, 5.0, OPT)["w"]
    np.testing.assert_allclose(back, g)
    np.testing.assert_allclose(
        check.program_grad_norms({"w": 0.1 * g}, 0.5, OPT)["w"], g)


def test_gaps_and_judge():
    ref = _run([10.0, 9.0, 8.0], {"w": np.array([1.0, 1.0])},
               {"w": np.array([0.5, 0.5])})
    same = check.gaps(ref, ref)
    assert same == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}
    ok, rows = check.judge(same, {"loss_gap": 0.1, "grad_gap": 0.1,
                                  "change_gap": 0.1})
    assert ok and [r[0] for r in rows] == list(check.NAMES)
    unchanged = _run(ref["losses"], ref["grad_norms"], {"w": np.zeros(2)})
    assert check.gaps(unchanged, ref)["change_gap"] == 1.0
    later = _run([10.0, 1.0, 1.0], ref["grad_norms"], ref["change_norms"])
    assert check.gaps(later, ref)["loss_gap"] == 0.0      # first step only
    assert not check.judge(check.gaps(unchanged, ref),
                           {"loss_gap": 0.1, "grad_gap": 0.1, "change_gap": 0.1})[0]


def test_unmoved_parameters_leave_the_change_out():
    ref = _run([1.0], {"w": np.array([1.0, 1.0, 1e-9])},
               {"w": np.array([0.5, 0.5, 0.5])})
    run = _run([1.0], ref["grad_norms"], {"w": np.array([0.5, 0.5, 5.0])})
    assert check.gaps(run, ref)["change_gap"] == 0.0
