"""Faults planted under the benchmark, and its control, for its CPU tests
and for ``bench/control.py``.

Each fault wraps the program's step (``Program.fn``) or one of its collectives
so that a run goes on as usual with the timed path broken.  Run as a
script it drives one tiny cell with each fault in turn and prints one
result line for each: ``python -m bench.tests.faults <fault>[,<fault>...]
<mesh> [<config>]``; multi-device meshes need
``--xla_force_host_platform_device_count`` set.  The tiny cells are
``tiny`` (dense), ``tiny-moe`` and ``tiny-moe-f32``, each with its own
limits.  ``tiny-moe-f32`` states float32 compute with nothing quantized,
which no traffic variant gives: its program is built inside
:func:`unquantized_float32`.
"""
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 2 ** 31 + 77


def tiny(mesh: str = "1x1", config: str = "tiny"):
    from bench import spec
    chips = 4 if mesh == "2x2" else 1
    return spec.make_cell(config, chips, f"{DATA}/{config}.json",
                          f"{DATA}/tiny-{mesh}.json",
                          f"{DATA}/{config}-limits.json")


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


@contextlib.contextmanager
def shared_expert_left_out():
    """A program built inside this leaves the MoE layers' shared experts
    out of their output."""
    from repro.models import moe
    shared = moe.shared_ffn
    moe.shared_ffn = lambda x, gu, dn: jnp.zeros(
        (x.shape[0], dn.shape[-1]), x.dtype)
    try:
        yield
    finally:
        moe.shared_ffn = shared


@contextlib.contextmanager
def unquantized_float32():
    """A program built inside this computes in float32 and gathers and
    reduces unquantized in float32, as ``tiny-moe-f32`` states; the
    harness still checks the precision it runs at."""
    from repro.train import policy
    make = policy.make_policy

    def make_f32(*a, **k):
        return make(*a, **dict(k, qwz=False, qgz=False,
                               compute_dtype=jnp.float32,
                               param_dtype=jnp.float32,
                               reduce_dtype=jnp.float32))

    policy.make_policy = make_f32
    try:
        yield
    finally:
        policy.make_policy = make


@contextlib.contextmanager
def bf16_compute():
    """The float32 cell's control: a program built inside this computes
    in bfloat16, the precision below the float32 its configuration
    states, its weights still gathered unquantized."""
    from bench import run
    from repro.train import policy
    make, check = policy.make_policy, run._check_precision

    def make_bf16(*a, **k):
        pol = make(*a, **k)
        return dataclasses.replace(pol, zcfg=dataclasses.replace(
            pol.zcfg, compute_dtype=jnp.bfloat16))

    policy.make_policy = make_bf16
    run._check_precision = lambda stated, pol: None
    try:
        yield
    finally:
        policy.make_policy, run._check_precision = make, check


@contextlib.contextmanager
def balance_left_out():
    """A program built inside this leaves the MoE balance term out of its
    loss."""
    from repro.models import moe
    dispatch = moe.moe_dispatch

    def without(*a, **k):
        d = dispatch(*a, **k)
        return d._replace(aux_loss=jnp.zeros_like(d.aux_loss))

    moe.moe_dispatch = without
    try:
        yield
    finally:
        moe.moe_dispatch = dispatch


@contextlib.contextmanager
def gates_not_renormalised():
    """A program built inside this takes the raw top-k softmax scores as
    gates, where its configuration states them renormalised."""
    from repro.models import moe
    route = moe.route_topk
    moe.route_topk = lambda logits, top_k, norm_topk=True: route(
        logits, top_k, False)
    try:
        yield
    finally:
        moe.route_topk = route


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


STEP_FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
               "loss_altered": loss_altered, "none": lambda fn: fn}
# planted where the step is built, not around it
BUILD_FAULTS = {"exchange_left_out": exchange_left_out,
                "int4_gathers": int4_gathers,
                "shared_expert_left_out": shared_expert_left_out,
                "bf16_compute": bf16_compute,
                "balance_left_out": balance_left_out,
                "gates_not_renormalised": gates_not_renormalised}
# how a test configuration's stated precision is built, where the
# traffic's variant alone does not give it
STATED = {"tiny-moe-f32": unquantized_float32}
# the reference told the paper's behaviour where the program departs from
# it: a sound program then has to come out not correct
PAPER = {"reference_routes_the_batch": {"routing_scope": "batch"}}


def run_faulty(fault: str, mesh: str = "1x1", config: str = "tiny"):
    from bench import run
    wrap = STEP_FAULTS.get(fault, STEP_FAULTS["none"])

    class Broken(run.Program):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.fn = wrap(self.fn)

    cell = tiny(mesh, config)
    cell.config.update(PAPER.get(fault, {}))
    orig, run.Program = run.Program, Broken
    stated = STATED.get(config, contextlib.nullcontext)()
    planted = BUILD_FAULTS.get(fault, contextlib.nullcontext)()
    try:
        with stated, planted:
            return run.run_cell(cell, SEED, 0.2, False,
                                jax.devices()[:cell.chips],
                                time.perf_counter())
    finally:
        run.Program = orig


def run_in_child(faults_, mesh, config="tiny"):
    """:func:`run_faulty` for each fault in one process on ``mesh``'s CPU
    devices; their result lines."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]))
    out = subprocess.run(
        [sys.executable, "-m", "bench.tests.faults", ",".join(faults_), mesh,
         config], env=env, cwd=ROOT, capture_output=True, text=True,
        timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()[-len(faults_):]
    return [json.loads(x) for x in lines]


if __name__ == "__main__":
    for f in sys.argv[1].split(","):
        print(json.dumps(run_faulty(f, *sys.argv[2:])), flush=True)
