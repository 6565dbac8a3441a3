"""The float8 reading: the reference computed in float8, put in the
program's place.  On the tiny cell it comes out not correct, while the
program passes (``test_faults.py``); at the cells' own sizes it fails
none of their numbers (PERF.md), so the control that sets the limits is
the program's INT4 weight gathers (``test_faults.py``, ``int4_gathers``).
"""
import numpy as np
import pytest

from bench import check, reference, tokens
from bench.tests import faults


@pytest.mark.parametrize("seed", [faults.SEED, 11, 12])
def test_float8_control_fails(seed):
    cell = faults.tiny()
    cfg, tr = cell.config, cell.traffic
    gen = tokens.Tokens(tr, cfg["vocab"], seed)
    batches = [gen.batch(k) for k in range(3)]
    base = reference.Reference(cfg, tr).run(seed, batches)
    ctl = reference.Reference(cfg, tr, "fp8").run(seed, batches)
    ok, checks = check.judge(check.numbers(ctl, base)[0], cell.limits)
    assert not ok, checks
    assert checks["loss_gap"]["value"] > checks["loss_gap"]["limit"]


def test_reference_is_deterministic_and_learns():
    cell = faults.tiny()
    cfg, tr = cell.config, cell.traffic
    gen = tokens.Tokens(tr, cfg["vocab"], 3)
    batches = [gen.batch(0)] * 3
    a = reference.Reference(cfg, tr).run(3, batches)
    b = reference.Reference(cfg, tr).run(3, batches)
    assert a.losses == b.losses
    assert a.losses[2] < a.losses[0]       # the same rows, three updates
    assert set(a.change_sumsq) == set(a.grad_sumsq)
    assert all(np.isfinite(list(a.change_sumsq.values())))
