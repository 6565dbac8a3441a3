"""The comparison that decides ``correct``, on readings made up by hand."""
import math

from bench import check
from bench.reference import Readings


def _readings(losses, grad, change):
    return Readings(list(losses), dict(grad), dict(change))


REF = _readings([10.0, 9.0, 8.0],
                {"a": 4.0, "b": 1.0, "c": 9.0, "still": 1e-12},
                {"a": 1.0, "b": 4.0, "c": 1.0, "still": 1.0})


def test_same_readings_give_zero():
    vals, _ = check.numbers(REF, REF)
    assert vals == {"loss_gap": 0.0, "grad_gap": 0.0, "change_gap": 0.0}


def test_gaps_are_worst_case_over_norms():
    prog = _readings([10.0, 9.09, 8.0],
                     {"a": 4.0, "b": 1.21, "c": 9.0, "still": 1e-12},
                     {"a": 1.0, "b": 4.0, "c": 0.0, "still": 100.0})
    vals, where = check.numbers(prog, REF)
    assert math.isclose(vals["loss_gap"], 0.01, rel_tol=1e-9)
    assert where["loss_gap"] == "step 1"
    # |1.1 - 1| over max(1, median(2, 1, 3, 0) = 1.5)
    assert math.isclose(vals["grad_gap"], 0.1 / 1.5, rel_tol=1e-9)
    # "still" has a gradient under a thousandth of the median: left out;
    # "c" did not move where the reference moved it by 1
    assert math.isclose(vals["change_gap"], 1.0 / 1.0, rel_tol=1e-9)
    assert where["change_gap"] == "c"


def test_non_finite_fails():
    prog = _readings([float("nan"), 9.0, 8.0], REF.grad_sumsq,
                     REF.change_sumsq)
    vals, _ = check.numbers(prog, REF)
    ok, checks = check.judge(vals, {"loss_gap": 1.0, "grad_gap": 1.0,
                                    "change_gap": 1.0})
    assert not ok and checks["loss_gap"]["value"] == float("inf")


def test_judge_holds_each_number_to_its_limit():
    vals = {"loss_gap": 1e-4, "grad_gap": 0.02, "change_gap": 0.3}
    ok, _ = check.judge(vals, {"loss_gap": 2e-4, "grad_gap": 0.05,
                               "change_gap": 0.4})
    assert ok
    ok, checks = check.judge(vals, {"loss_gap": 2e-4, "grad_gap": 0.01,
                                    "change_gap": 0.4})
    assert not ok and checks["grad_gap"]["limit"] == 0.01
