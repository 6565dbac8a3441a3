"""Faults planted under the benchmark, and its control, for its CPU tests
and for ``bench/control.py``.

Each fault wraps the program's step (``Program.fn``) or one of its collectives
so that a run goes on as usual with the timed path broken.  Run as a
script it drives one tiny cell with one fault and prints the result
line: ``python -m bench.tests.faults <fault> <mesh>``; multi-device
meshes need ``--xla_force_host_platform_device_count`` set.
"""
import contextlib
import dataclasses
import json
import os
import sys
import time

import jax
import jax.numpy as jnp

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2 ** 31 + 77


def tiny(mesh: str = "1x1"):
    from bench import spec
    chips = 4 if mesh == "2x2" else 1
    return spec.make_cell("tiny", chips, f"{DATA}/tiny.json",
                          f"{DATA}/tiny-{mesh}.json",
                          f"{DATA}/tiny-limits.json")


def state_unchanged(fn):
    """The step computes, then hands back the state it was given."""
    def step(p, o, b):
        _, _, m = fn(jax.tree.map(jnp.copy, p), jax.tree.map(jnp.copy, o), b)
        return p, o, m
    return step


def half_batch(fn):
    """Half of the rows left out: the rest stand in for them, so the mean
    is taken over the first half alone."""
    def step(p, o, b):
        h = next(iter(b.values())).shape[0] // 2
        return fn(p, o, {k: jnp.concatenate([v[:h], v[:h]])
                         for k, v in b.items()})
    return step


def loss_altered(fn):
    """The loss is off by 1% where the step produces it."""
    def step(p, o, b):
        p, o, m = fn(p, o, b)
        return p, o, dict(m, loss=m["loss"] * 1.01)
    return step


@contextlib.contextmanager
def int4_gathers():
    """The control: a program built inside this gathers its weights in
    INT4 (``qwz_bits=4``), the precision below the INT8 gathers that the
    configuration states, through the program's own path."""
    from bench import run
    from repro.train import policy
    make, check = policy.make_policy, run._check_precision

    def make_int4(*a, **k):
        pol = make(*a, **k)
        return dataclasses.replace(
            pol, zcfg=dataclasses.replace(pol.zcfg, qwz_bits=4))

    policy.make_policy = make_int4
    run._check_precision = lambda stated, pol: None
    try:
        yield
    finally:
        policy.make_policy, run._check_precision = make, check


STEP_FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
               "loss_altered": loss_altered, "none": lambda fn: fn}
# planted where the step is built, not around it
BUILD_FAULTS = ("exchange_left_out", "int4_gathers")


def leave_out_exchange():
    """Every device keeps its own slice of its local gradient: the
    reduce between chips is left out."""
    from jax import lax

    from repro.core import collectives as cl
    from repro.core import schedule, zeropp

    def local_only(dW, z):
        world = cl.axis_size(z.dp_axes)
        n = dW.shape[0] // world
        return lax.dynamic_slice_in_dim(
            dW, cl.flat_rank(z.dp_axes) * n, n).astype(z.grad_dtype)

    zeropp.grad_reduce = local_only
    schedule.grad_reduce = local_only


@contextlib.contextmanager
def exchange_left_out():
    """A program built inside this leaves out the exchange between chips
    (it stays left out: the collectives are replaced for the process)."""
    leave_out_exchange()
    yield


def run_faulty(fault: str, mesh: str = "1x1"):
    from bench import run
    if fault == "exchange_left_out":
        leave_out_exchange()
    wrap = STEP_FAULTS["none" if fault in BUILD_FAULTS else fault]

    class Broken(run.Program):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.fn = wrap(self.fn)

    cell = tiny(mesh)
    orig, run.Program = run.Program, Broken
    ctx = int4_gathers() if fault == "int4_gathers" else \
        contextlib.nullcontext()
    try:
        with ctx:
            return run.run_cell(cell, SEED, 0.2, False,
                                jax.devices()[:cell.chips],
                                time.perf_counter())
    finally:
        run.Program = orig


if __name__ == "__main__":
    print(json.dumps(run_faulty(sys.argv[1], sys.argv[2])), flush=True)
