"""Observability subsystem tests (obs/: metrics, trace, report).

Fast tests run in-process: instrument semantics, jsonl tracer round-trip
and kill-safety (truncated final line), replay dedupe, and the gate math.
The measured-vs-projected comm crosschecks and the telemetry-under-failure
replay run on 8 simulated devices via testing/subproc.py — the same groups
``make obs-smoke`` drives.
"""
import os

import pytest

from repro.obs.metrics import (Counter, Gauge, Histogram, Registry,
                               count_dispatch, get_registry, set_registry)
from repro.obs.report import (GateFailure, comm_gate, overhead_gate,
                              runtime_gate)
from repro.obs.trace import (Tracer, get_tracer, read_events,
                             replay_counters, set_tracer)
from repro.testing.subproc import run_checks


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def test_counter_gauge_basics():
    c = Counter("x")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    c.reset()
    assert c.value == 0
    g = Gauge("y")
    assert g.value is None
    g.set(1)
    g.set(7)
    assert g.value == 7


def test_histogram_window_and_percentiles():
    h = Histogram("h", window=4)
    for v in (1, 2, 3, 4, 100):           # 1 falls out of the window
        h.observe(v)
    assert h.count == 5 and h.min == 1 and h.max == 100
    assert h.percentile(50) == 4 and h.percentile(0) == 2
    assert h.percentile(100) == 100
    s = h.summary()
    assert s["count"] == 5 and s["p99"] == 100
    assert Histogram("e").percentile(50) is None
    assert Histogram("e").mean is None


def test_histogram_quantiles_match_numpy():
    """quantiles() is the nearest-rank export ServeEngine.stats() ships:
    with 101 distinct samples every requested quantile must agree with
    numpy's 'nearest' percentile exactly."""
    import numpy as np
    rng = np.random.default_rng(3)
    xs = rng.permutation(101).astype(float)   # 0..100, shuffled
    h = Histogram("q", window=256)
    for v in xs:
        h.observe(v)
    q = h.quantiles((50, 90, 99))
    assert q["n"] == 101
    for p in (50, 90, 99):
        assert q[f"p{p}"] == np.percentile(xs, p, method="nearest")
    # default keys + empty-histogram shape
    assert set(Histogram("e").quantiles()) == {"p50", "p90", "p99", "n"}
    assert Histogram("e").quantiles()["p50"] is None
    # windowing: quantiles see only the last `window` samples
    hw = Histogram("w", window=4)
    for v in (1, 2, 3, 4, 100):
        hw.observe(v)
    assert hw.quantiles((0,))["p0"] == 2 and hw.quantiles((0,))["n"] == 5


def test_registry_create_on_use_and_snapshot():
    r = Registry()
    r.counter("a.n").inc(3)
    r.gauge("b.g").set(1.5)
    r.gauge("b.unset")                    # never set: omitted
    r.histogram("c.h").observe(2.0)
    snap = r.snapshot()
    assert snap["a.n"] == 3 and snap["b.g"] == 1.5
    assert "b.unset" not in snap
    assert snap["c.h"]["count"] == 1 and snap["c.h"]["p50"] == 2.0
    assert r.counter("a.n") is r.counter("a.n")   # same instrument
    r.reset()
    assert r.snapshot() == {}


def test_set_registry_swaps_process_default():
    mine = Registry()
    old = set_registry(mine)
    try:
        count_dispatch("op", "xla")
        assert mine.counter("kernels.dispatch.op.xla").value == 1
        assert get_registry() is mine
    finally:
        set_registry(old)


def test_kernel_dispatch_counts_routing(tmp_path):
    """The ops.py seam counts the EFFECTIVE route once per dispatch."""
    import jax.numpy as jnp
    from repro.core.quant import QuantConfig
    from repro.kernels import ops
    mine = Registry()
    old = set_registry(mine)
    try:
        with ops.use_backend("xla"):
            x = jnp.ones((256,), jnp.float32)
            ops.quantize_blockwise(x, QuantConfig(bits=8, block_size=64))
        assert mine.counter(
            "kernels.dispatch.quantize_blockwise.xla").value == 1
    finally:
        set_registry(old)


# ---------------------------------------------------------------------------
# tracer + replay
# ---------------------------------------------------------------------------

def test_tracer_roundtrip(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    tr = Tracer(p)
    with tr.span("train.step", step=0, layer=3):
        pass
    tr.event("elastic.restart", attempt=1)
    tr.counter("train.steps", 1, step=0)
    tr.counter("bytes", 10)               # unstepped: summed on replay
    tr.counter("bytes", 5)
    tr.flush()
    tr.close()
    recs = read_events(p)
    kinds = [r["kind"] for r in recs]
    assert kinds == ["span", "event", "counter", "counter", "counter"]
    sp = recs[0]
    assert sp["name"] == "train.step" and sp["step"] == 0
    assert sp["layer"] == 3 and sp["dur_ns"] >= 0
    tot = replay_counters(p)
    assert tot == {"train.steps": 1, "bytes": 15}


def test_tracer_disabled_is_noop(tmp_path):
    p = str(tmp_path / "never.jsonl")
    tr = Tracer(p, enabled=False)
    s1 = tr.span("a")
    s2 = tr.span("b", step=1)
    assert s1 is s2                       # one shared nullcontext
    with s1:
        pass
    tr.event("x")
    tr.counter("c", 1, step=0)
    tr.flush()
    assert not os.path.exists(p)          # nothing ever written


def test_tracer_append_mode_extends(tmp_path):
    """A restart re-opens the same log and EXTENDS it (replay contract)."""
    p = str(tmp_path / "ev.jsonl")
    t1 = Tracer(p)
    t1.counter("train.steps", 1, step=0)
    t1.close()
    t2 = Tracer(p)
    t2.counter("train.steps", 1, step=0)   # re-emitted step: dedupes
    t2.counter("train.steps", 1, step=1)
    t2.close()
    assert len(read_events(p)) == 3
    assert replay_counters(p) == {"train.steps": 2}


def test_read_events_skips_truncated_line(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    tr = Tracer(p)
    tr.counter("n", 1, step=0)
    tr.close()
    with open(p, "a") as fh:
        fh.write('{"kind": "counter", "name": "n", "val')   # sheared write
    assert len(read_events(p)) == 1
    assert replay_counters(p) == {"n": 1}


def test_replay_counters_semantics(tmp_path):
    p = str(tmp_path / "ev.jsonl")
    tr = Tracer(p)
    tr.counter("loss", 5.0, step=0)
    tr.counter("loss", 4.0, step=1)
    tr.counter("loss", 9.9, step=1)       # re-emitted: last wins
    tr.counter("loss", 3.0, step=2)
    tr.close()
    assert replay_counters(p) == {"loss": 5.0 + 9.9 + 3.0}
    assert replay_counters(p, up_to_step=1) == {"loss": 5.0 + 9.9}


def test_set_tracer_restores_disabled():
    tr = Tracer(enabled=True)
    old = set_tracer(tr)
    assert get_tracer() is tr
    set_tracer(None)
    assert not get_tracer().enabled
    set_tracer(old)


# ---------------------------------------------------------------------------
# report: the gate
# ---------------------------------------------------------------------------

def test_comm_gate_tolerance():
    ok = comm_gate({"zero.qwz_gather": 1000.0}, {"zero.qwz_gather": 1005.0})
    assert ok["ok"] and ok["labels"]["zero.qwz_gather"]["pass"]
    bad = comm_gate({"zero.qwz_gather": 1000.0}, {"zero.qwz_gather": 1100.0})
    assert not bad["ok"]
    # 'other' is reported but not gated
    rep = comm_gate({"other": 999.0}, {})
    assert rep["ok"] and not rep["labels"]["other"]["rel"] <= 0.01


def test_comm_gate_missing_label_fails():
    rep = comm_gate({}, {"zero.qgz_reduce": 5000.0})
    assert not rep["ok"]        # projected traffic never measured


def test_overhead_gate_and_runtime_gate_strict():
    ok = overhead_gate([1.0, 1.0, 1.0], [1.01, 1.01, 1.01], tol=0.02)
    assert ok["ok"] and abs(ok["rel_overhead"] - 0.01) < 1e-9
    assert overhead_gate([1.0], [0.9])["ok"]        # faster: trivially ok
    assert not overhead_gate([1.0], [1.5])["ok"]

    with pytest.raises(GateFailure) as ei:
        runtime_gate(measured={"zero.qwz_gather": 1.0},
                     projected={"zero.qwz_gather": 2.0}, strict=True)
    assert "zero.qwz_gather" in str(ei.value)
    rep = runtime_gate(measured={"zero.qwz_gather": 1.0},
                       projected={"zero.qwz_gather": 1.0},
                       enabled_s=[1.0, 1.0], disabled_s=[1.0, 1.0],
                       strict=True)
    assert rep["ok"] and rep["overhead"]["ok"]


# ---------------------------------------------------------------------------
# multi-device: comm crosscheck, failure replay, runtime gate (subprocess)
# ---------------------------------------------------------------------------

def test_obs_comm_crosscheck_dense():
    run_checks(["check_obs_comm_crosscheck"], n_devices=8, timeout=900)


def test_obs_comm_crosscheck_moe():
    run_checks(["check_obs_comm_crosscheck_moe"], n_devices=8, timeout=900)


def test_obs_failure_replay_and_runtime_gate():
    run_checks(["check_obs_telemetry_failure_replay",
                "check_obs_runtime_gate"], n_devices=8, timeout=900)
