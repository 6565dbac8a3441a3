"""The program's MoE arithmetic against the plain reference, in float32.

``tiny-moe-f32`` is the tiny MoE cell computed in float32 with nothing
quantized (``faults.unquantized_float32``), run through the harness's own
set-up, window, reference and comparison on the CPU.  There the program
equals the reference to float32 round-off, so its limits
(``data/tiny-moe-f32-limits.json``) see what bfloat16 and INT8/INT4 hide
in ``tiny-moe``: the precision below float32, the balance term, the
gates' renormalisation, and whose tokens are routed together.
"""
import pytest

from bench.tests import faults
from bench.tests.faults import run_in_child

CONFIG = "tiny-moe-f32"


def test_float32_moe_sound_run_is_correct():
    res = faults.run_faulty("none", "1x1", CONFIG)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault,fails", [
    ("bf16_compute", "loss_gap"),
    ("balance_left_out", "loss_gap"),
    ("gates_not_renormalised", "grad_gap"),
])
def test_float32_moe_fault_is_not_correct(fault, fails):
    res = faults.run_faulty(fault, "1x1", CONFIG)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] > c["limit"], res["checks"]


def test_float32_moe_on_four_chips_routes_each_chips_tokens():
    """On 2x2 the program routes each chip's tokens together (the file's
    ``routing_scope`` "chip"): correct; with the balance term left out, or
    with the reference routing the whole batch together as the paper
    does, not correct."""
    sound, no_balance, batch = run_in_child(
        ["none", "balance_left_out", "reference_routes_the_batch"], "2x2",
        CONFIG)
    assert sound["correct"], sound["checks"]
    for res in (no_balance, batch):
        assert not res["correct"]
        c = res["checks"]["loss_gap"]
        assert c["value"] > c["limit"], res["checks"]
