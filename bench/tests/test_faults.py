"""Runs with the timed path broken underneath come out not correct.

The chip look is skipped: the tiny cells (dense ``tiny`` and
``tiny-moe``) run on the CPU through the harness's own set-up, window,
reference and comparison.  Their limits are set for the tiny sizes
(``data/<cell>-limits.json``).  ``tiny-moe`` runs in bfloat16 with INT8
gathers, so it checks that the MoE groups are wired through the harness;
the MoE arithmetic is checked in float32 (``test_moe_arithmetic.py``)."""
import pytest

from bench.tests import faults
from bench.tests.faults import run_in_child


def test_sound_run_is_correct():
    res = faults.run_faulty("none")
    assert res["correct"], res["checks"]


def test_moe_sound_run_is_correct():
    res = faults.run_faulty("none", "1x1", "tiny-moe")
    assert res["correct"], res["checks"]


def _cases():
    dense = [("state_unchanged", "change_gap"), ("half_batch", "loss_gap"),
             ("loss_altered", "loss_gap"), ("int4_gathers", "loss_gap")]
    moe = [("state_unchanged", "change_gap"), ("half_batch", "loss_gap"),
           ("loss_altered", "loss_gap"), ("int4_gathers", "grad_gap"),
           ("shared_expert_left_out", "grad_gap")]
    return [pytest.param("tiny", f, n, id=f"{f}-{n}") for f, n in dense] + \
        [pytest.param("tiny-moe", f, n, id=f"tiny-moe-{f}-{n}")
         for f, n in moe]


@pytest.mark.parametrize("config,fault,fails", _cases())
def test_fault_is_not_correct(config, fault, fails):
    res = faults.run_faulty(fault, "1x1", config)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] > c["limit"], res["checks"]


def test_exchange_left_out_is_not_correct():
    res, = run_in_child(["exchange_left_out"], "2x2")
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > \
        res["checks"]["grad_gap"]["limit"], res["checks"]


def test_moe_on_four_chips_is_correct_and_not_without_the_exchange():
    sound, broken = run_in_child(["none", "exchange_left_out"], "2x2",
                                 "tiny-moe")
    assert sound["correct"], sound["checks"]
    assert not broken["correct"]
    assert broken["checks"]["grad_gap"]["value"] > \
        broken["checks"]["grad_gap"]["limit"], broken["checks"]
