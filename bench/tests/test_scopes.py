"""Device time by program scope: the class and label rules on synthetic
operations, the recorded one-chip trace counted by hand, and the scopes
the program's step carries into its instructions' op names."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding

from bench import flops, scopes
from bench import trace_reduce as tr
from bench.tests import faults

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "trace_1x1.xplane.pb")
# the scopes the train step sets besides the collectives' zero.* labels
STEP_SCOPES = ("step.embed", "step.layers", "step.loss", "step.ring_read",
               "step.ring_write", "step.ring_seed", "step.ring_tail",
               "step.residual", "optim.adamw", "optim.clip")


@pytest.mark.parametrize("tf_op,cls", [
    ("", "unattributed"),
    ("jit(local_step)/jvp(step.layers)/while/body/dot_general", "forward"),
    ("jit(local_step)/transpose(jvp(step.layers))/while/body/dot_general",
     "backward"),
    ("jit(local_step)/optim.adamw/optim.clip/sqrt", "optimizer"),
    # the optimizer's class wins over the backward's, the backward's over
    # the forward's
    ("jit(f)/transpose(jvp(optim.adamw))/mul", "optimizer"),
    ("jit(f)/jvp(step.loss)/transpose(jvp())/add", "backward"),
    # a name that merely ends in "optim" is no optim.* scope
    ("jit(f)/myoptim.x/add", "forward"),
    ("jit(local_step)/reduce_sum", "forward"),
])
def test_class_order(tf_op, cls):
    assert scopes.classify(tf_op) == cls


def test_innermost_zero_label():
    assert scopes.label("jit(f)/transpose(jvp(zero.hpz_gather))/"
                        "zero.qgz_reduce/all-to-all") == "zero.qgz_reduce"
    assert scopes.label("jit(f)/jvp(zero.qwz_gather)/pallas_call") == \
        "zero.qwz_gather"
    assert scopes.label("jit(f)/optim.adamw/psum") is None


def _op(start, end, opcode, tf_op):
    return scopes.ScopedOp(tr.Op(f"%{opcode}", start, end, opcode,
                                 f"%x = f32[] {opcode}()"), tf_op)


def test_exposed_time_per_label():
    """A label's collectives count as exposed only where no compute runs;
    times are means over the devices, and a label with no collective in
    the trace reads nothing."""
    fwd = "jit(s)/jvp(step.layers)/dot_general"
    dev0 = [
        _op(0, 100, "fusion", fwd),
        _op(50, 150, "all-to-all", "jit(s)/transpose(jvp(zero.qgz_reduce))"),
        _op(200, 260, "all-gather", "jit(s)/jvp(zero.qwz_gather)"),
        _op(300, 400, "fusion", "jit(s)/optim.adamw/mul"),
        _op(320, 340, "all-gather", "jit(s)/transpose(jvp(zero.hpz_gather))"),
        _op(420, 430, "all-reduce", "jit(s)/optim.adamw/optim.clip/psum"),
    ]
    dev1 = [_op(0, 100, "fusion", fwd),
            _op(90, 110, "all-to-all",
                "jit(s)/transpose(jvp(zero.qgz_reduce))")]
    sp = scopes.split_trace(scopes.Trace(ops={0: dev0, 1: dev1}, spans=[]))
    assert sp.window_ns == 430
    assert sp.exposed_ns == {"zero.qgz_reduce": (50 + 10) / 2,
                             "zero.qwz_gather": 60 / 2,
                             "zero.hpz_gather": 0.0}
    assert sp.collective_ns["zero.hpz_gather"] == 20 / 2
    assert scopes.exposed_ms(sp, "zero.hpz_gather", 1) == 0.0
    assert scopes.exposed_ms(sp, "zero.qgz_reduce", 2) == 30 / 1e6 / 2
    assert scopes.exposed_ms(sp, "zero.baseline_gather", 1) is None
    # the unlabelled psum is the optimizer's, the qwZ gather the forward's
    assert sp.class_ns == {"forward": (160 + 100) / 2,
                           "backward": (120 + 20) / 2,
                           "optimizer": (100 + 10) / 2,
                           "unattributed": 0.0}
    # the classes sum to more than the busy time by what runs under
    # another class: the qgZ reduce under the forward fusion (50, 10) and
    # the hpZ gather under the optimizer's (20)
    assert sp.busy_ns == (320 + 110) / 2
    assert sum(sp.class_ns.values()) - sp.busy_ns == (50 + 20 + 10) / 2


def test_window_drops_operations_outside_the_bench_span():
    """Operations are cut to the window ``trace_reduce.window`` gives: the
    ``bench.window`` span widened to the first and last operation."""
    ops = {0: [_op(0, 10, "fusion", "jit(s)/jvp()/a"),
               _op(10, 30, "fusion", "jit(s)/transpose(jvp())/b")]}
    span = tr.Op(tr.WINDOW_SPAN, 5, 40)
    sp = scopes.split_trace(scopes.Trace(ops=ops, spans=[span]))
    assert sp.window_ns == 40
    assert sp.busy_ns == 30


def test_unnamed_operations_take_their_loops_class():
    """An operation with no op name takes the class that holds the most
    named time in the innermost loop around it that holds any; one that
    no such loop surrounds stays unattributed."""
    bwd = "jit(s)/transpose(jvp(step.layers))/while/body/dot_general"
    fwd = "jit(s)/jvp(step.layers)/while/body/dot_general"
    ops = [
        _op(0, 40, "fusion", fwd),            # forward scan: 0-50
        _op(40, 45, "fusion", ""),            # -> forward
        _op(100, 150, "fusion", bwd),         # backward scan: 100-200
        _op(150, 152, "fusion", fwd),         # a stray forward name
        _op(160, 170, "copy", ""),            # in a copy loop 155-175 with
        #                                       nothing named: -> backward
        _op(180, 190, "reduce", ""),          # -> backward
        _op(300, 310, "broadcast", ""),       # no loop: unattributed
        _op(400, 405, "copy", ""),            # a loop with nothing named
    ]
    loops = [(0, 50), (100, 200), (155, 175), (398, 410)]
    assert scopes.classes(ops, loops) == [
        "forward", "forward", "backward", "forward", "backward", "backward",
        "unattributed", "unattributed"]
    sp = scopes.split_trace(scopes.Trace(ops={0: ops}, spans=[],
                                         loops={0: loops}))
    assert sp.class_ns == {"forward": 40 + 5 + 2, "backward": 50 + 10 + 10,
                           "optimizer": 0.0, "unattributed": 10 + 5}
    assert sp.scope_ns[""] == 5 + 10 + 10 + 10 + 5
    assert sp.unattributed_ns == {"%broadcast": 10, "%copy": 5}


@pytest.fixture(scope="module")
def recorded():
    return scopes.split(TRACE)


def test_recorded_trace_classes_by_hand(recorded):
    """On the recorded gpt-350m one-chip trace (the program before the
    step.*/optim.* scopes), by op name alone: 57 % of the busy time is
    backward, 32 % forward (28 % under jvp( and 3.7 % of unscoped
    top-level arithmetic, the optimizer's) and 11 % carries no op name.
    That 11 % is nearly all inside the layer scans' loops (the ring's
    slot copies and reads that XLA rewrites), so it joins their classes:
    65 % backward, 34 % forward, 0.4 % left unattributed.  The hand count
    sums durations by plain string tests and finds each loop around an
    unnamed operation by brute force; one chip runs one operation at a
    time, so sums and unions agree."""
    trace = scopes.load(TRACE)
    lo, hi = tr.window(trace.spans, {d: [s.op for s in v]
                                     for d, v in trace.ops.items()})
    ops, loops = trace.ops[0], trace.loops[0]

    def by_name(tf_op):
        if "transpose(" in tf_op:
            return "backward"
        return "forward" if tf_op else "unattributed"

    named = [{} for _ in loops]
    for s in ops:
        if s.tf_op:
            for j, (a, b) in enumerate(loops):
                if a <= s.op.start and s.op.end <= b:
                    c = by_name(s.tf_op)
                    named[j][c] = named[j].get(c, 0) + s.op.end - s.op.start
    hand = dict.fromkeys(scopes.CLASSES, 0)
    raw = dict.fromkeys(scopes.CLASSES, 0)
    jvp_only = 0
    for s in ops:
        d = min(s.op.end, hi) - max(s.op.start, lo)
        if d <= 0:
            continue
        cls = raw_cls = by_name(s.tf_op)
        if not s.tf_op:
            around = sorted((b - a, j) for j, (a, b) in enumerate(loops)
                            if a <= s.op.start and s.op.end <= b and named[j])
            if around:
                votes = named[around[0][1]]
                cls = max(votes, key=votes.get)
        hand[cls] += d
        raw[raw_cls] += d
        jvp_only += d * ("jvp(" in s.tf_op and raw_cls == "forward")
    for cls, ns in hand.items():
        assert recorded.class_ns[cls] == pytest.approx(ns, rel=5e-3, abs=1)
    busy = recorded.busy_ns
    assert 100 * raw["backward"] / busy == pytest.approx(57.2, abs=0.5)
    assert 100 * raw["forward"] / busy == pytest.approx(31.8, abs=0.5)
    assert 100 * jvp_only / busy == pytest.approx(28.1, abs=0.5)
    assert 100 * raw["unattributed"] / busy == pytest.approx(11.0, abs=0.5)
    assert 100 * recorded.scope_ns[""] / busy == pytest.approx(11.0, abs=0.5)
    share = {c: 100 * v / busy for c, v in recorded.class_ns.items()}
    assert share["backward"] == pytest.approx(65.3, abs=0.5)
    assert share["forward"] == pytest.approx(34.3, abs=0.5)
    assert share["unattributed"] < 1.0
    assert share["optimizer"] == 0
    assert scopes.class_ms(recorded, "optimizer", 2) is None


def test_recorded_trace_classes_sum_to_busy(recorded):
    """forward + backward + optimizer + unattributed is the busy time of
    ``trace_reduce.summarize``, over the same window."""
    s = tr.summarize(TRACE, [])
    assert recorded.window_ns == s.window_ns
    assert sum(recorded.class_ns.values()) == pytest.approx(
        s.mean("busy_ns"), rel=5e-3)
    assert recorded.busy_ns == s.mean("busy_ns")


def test_recorded_trace_names_the_stack_writes(recorded):
    """The two largest whole-layer writes are the layer scans' 24-layer
    stacks (the backward's f32 gradients, the forward's bf16 residuals);
    the ring's slot copies and reads carry no op name and are counted
    with the scan they run in."""
    writes = {k: v for k, v in recorded.scope_ns.items()
              if k.endswith("while/body/dynamic_update_slice")}
    assert set(writes) >= {
        "jit(local_step)/transpose(jvp())/while/body/dynamic_update_slice",
        "jit(local_step)/jvp()/while/body/dynamic_update_slice"}
    trace = scopes.load(TRACE)
    cls = dict(zip((s.op.name for s in trace.ops[0]),
                   scopes.classes(trace.ops[0], trace.loops[0])))
    ring = {"%reduce.127 = bf16[16779264]": "backward",
            "%reshape_dynamic-update-slice_fusion.2 = bf16[1,1,16779264]":
            "backward",
            "%reshape_dynamic-update-slice_fusion.1 = bf16[1,1,16779264]":
            "forward"}
    for name, want in ring.items():
        assert not next(s.tf_op for s in trace.ops[0] if s.op.name == name)
        assert cls[name] == want, name


def test_cli_prints_per_step_times(recorded, capsys):
    assert scopes.main([TRACE, "--steps", "2"]) == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["backward_ms_per_step"] == pytest.approx(
        recorded.class_ns["backward"] / 2e6)
    assert res["optimizer_ms_per_step"] is None      # no optim.* scope yet
    assert sum(res[f"{c}_ms_per_step"] or 0 for c in scopes.CLASSES) == \
        pytest.approx(res["busy_ms_per_step"], rel=5e-3)
    assert "largest 10 scopes:" in out.err
    assert "(no op_name)" in out.err


def test_scopes_keep_the_kernels_named(recorded):
    """The quantize kernels' HLO names stay what ``bench/flops.py``'s
    patterns find (the scopes name op metadata, not kernels)."""
    s = tr.summarize(TRACE, flops.QUANT_KERNELS)
    assert s.mean("kernel_count") > 0


def test_tiny_step_carries_the_step_scopes():
    """The tiny cell's lowered train step names every phase scope in the
    locations of its operations, which become the op names of its
    instructions (what the device trace reports as tf_op)."""
    from bench import run
    cell = faults.tiny("1x1")
    prog = run.Program(cell, jax.devices()[:1])
    from repro.train.state import ZeroState, state_shapes
    st = ZeroState(prog.model, prog.mesh, prog.opt_cfg)
    ps, os_ = state_shapes(prog.model, prog.opt_cfg)

    def abstract(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    shape = (int(cell.traffic["global_batch"]), int(cell.traffic["seq_len"]))
    batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32,
                                     sharding=NamedSharding(prog.mesh, sp))
             for k, sp in prog.b_specs.items()}
    text = prog.fn.lower(abstract(ps, st.param_shardings()),
                         abstract(os_, st.opt_shardings()),
                         batch).as_text(debug_info=True)
    missing = [s for s in STEP_SCOPES if s not in text]
    assert not missing, missing
    assert "transpose(jvp(step.layers))" in text
    assert "jvp(step.embed)" in text and "jvp(step.loss)" in text
