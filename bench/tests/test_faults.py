"""Runs with the timed path broken underneath come out not correct.

The chip look is skipped: the tiny cell runs on the CPU through the
harness's own set-up, window, reference and comparison.  Its limits are
set for the tiny sizes (``data/tiny-limits.json``)."""
import json
import os
import subprocess
import sys

import pytest

from bench.tests import faults


def test_sound_run_is_correct():
    res = faults.run_faulty("none")
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault,fails", [
    ("state_unchanged", "change_gap"),
    ("half_batch", "loss_gap"),
    ("loss_altered", "loss_gap"),
    ("int4_gathers", "loss_gap"),
])
def test_fault_is_not_correct(fault, fails):
    res = faults.run_faulty(fault)
    assert not res["correct"]
    c = res["checks"][fails]
    assert c["value"] > c["limit"], res["checks"]


def test_exchange_left_out_is_not_correct():
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([root, os.path.join(root, "src")]))
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.faults", "exchange_left_out",
         "2x2"], env=env, cwd=root, capture_output=True, text=True,
        timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["correct"]
    assert res["checks"]["grad_gap"]["value"] > \
        res["checks"]["grad_gap"]["limit"], res["checks"]
