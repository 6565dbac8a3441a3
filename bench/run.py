#!/usr/bin/env python3
"""Benchmark of the ZeRO++ training step on TPU chips: one cell, one run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process, in this order:

1. JAX's persistent compile cache goes inside the checkout
   (``repro.launch.train.enable_compile_cache``).
2. Set-up builds the cell's step the way ``build_everything`` does (the
   policy, the model, the jitted shard_map step) from the cell's
   configuration and traffic files, makes the weights on the device from
   the seed, and drives the step through its first ``CHECK_STEPS`` steps
   with the window's own call and feed.  Those steps compile the step and
   give the program's readings for the check.
3. The window runs steps back to back for ``--seconds``: each dispatches
   the step and makes and places the next batch while the device
   computes; losses are read ``AHEAD_S`` seconds of steps late, so the
   device stays fed while the host stalls.  When the time is up nothing
   more is dispatched; the window waits for every dispatched step and
   reads the clock after that wait, so all of them count, over all of
   that time.  Compilations inside it are counted.
4. With ``--trace 1`` the window runs under the profiler, and the trace
   is reduced to the cell's per-layer metrics (``bench/metrics``).
5. The program's state is freed and the plain float32 reference
   (``bench/reference.py``) follows the same steps from the same seed;
   ``bench/check.py`` compares the two against the cell's limits.
6. The last line of stdout is one JSON object: ``correct``, ``attempted``,
   ``failed``, ``metrics``, ``device`` (and ``breakdown`` with a trace),
   with the compared numbers and their limits last, under ``checks``.

Without a TPU, or with fewer chips than the cell asks for, it exits 2 and
prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from contextlib import nullcontext  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from bench import check, flops, reference, spec, tokens, trace_reduce  # noqa: E402,E501
from bench.weights import Packer, key_data  # noqa: E402

CHECK_STEPS = 3          # steps the reference follows
AHEAD_S = 5.0            # seconds of steps dispatched ahead of the one
#                          whose loss the window waits for
AXES = ("data", "model")


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def chips_or_exit(chips: int):
    """The cell's devices; exit 2 without a TPU or with too few chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"bench: no TPU (jax platform {devs[0].platform!r}); this "
            f"benchmark has no fallback")
        sys.exit(2)
    if len(devs) < chips:
        log(f"bench: the cell needs {chips} chips, found {len(devs)}")
        sys.exit(2)
    d = devs[0]
    log(f"platform {d.platform}  device_kind {d.device_kind}  count "
        f"{len(devs)}")
    return devs[:chips]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Program:
    """The cell's training step, built as ``launch/train.build_everything``
    builds it, with the state the benchmark's weights give it."""

    def __init__(self, cell, devices):
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models.model import Model
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from repro.optim.schedule import warmup_cosine
        from repro.train import trainer
        from repro.train.policy import make_policy
        from repro.train.state import ZeroState, state_shapes

        cfg, tr = cell.config, cell.traffic
        self.cell = cell
        self.arch = dataclasses.replace(get_config(cfg["arch"]),
                                        **spec.arch_overrides(cfg))
        self.mesh = make_mesh(cell.mesh, AXES, devices=devices)
        pol = make_policy(self.arch, AXES, tr["variant"])
        _check_precision(cfg["precision"], pol)
        self.model = Model(self.arch, pol.zcfg, world=len(devices))
        o = tr["optimizer"]
        self.opt_cfg = AdamWConfig(
            lr=warmup_cosine(o["lr"], o["warmup"], o["total"],
                             o.get("floor", 0.1)),
            b1=o["b1"], b2=o["b2"], eps=o["eps"],
            weight_decay=o["weight_decay"], grad_clip=o["grad_clip"],
            moments_dtype=pol.moments_dtype)
        self.b1 = o["b1"]
        self.ts = trainer.build_train_step(
            self.model, self.mesh, self.opt_cfg,
            global_batch=int(tr["global_batch"]))
        self.fn = self.ts.fn
        self._place = trainer.place_batch
        self.b_specs = self.ts.in_specs[2]
        self.packer = Packer(self.model, cfg)
        st = ZeroState(self.model, self.mesh, self.opt_cfg)
        p_sh, o_sh = st.param_shardings(), st.opt_shardings()
        pk = self.packer
        self._groups = {
            g: jax.jit(lambda kd, g=g: pk.build(kd, g), out_shardings=p_sh[g])
            for g in pk.shapes}
        shapes = state_shapes(self.model, self.opt_cfg)[0]
        self._opt0 = jax.jit(lambda: init_opt_state(shapes, self.opt_cfg),
                             out_shardings=o_sh)
        rep = NamedSharding(self.mesh, P())

        def rows_of(x, r):
            """Row ``r`` of a group, its rows counted flat."""
            if x.ndim == 1:
                return x
            if x.ndim > 2:
                x = x.reshape(-1, x.shape[-1])
            return jax.lax.dynamic_index_in_dim(x, r, keepdims=False)

        self._sumsq = {
            g: jax.jit(lambda x, g=g: jax.lax.map(
                lambda r: pk.row_sumsq(rows_of(x, r), g),
                jnp.arange(pk.rows(g))), out_shardings=rep)
            for g in pk.shapes}
        self._change = {
            g: jax.jit(lambda x, kd, r, g=g: pk.row_sumsq(
                rows_of(x, r) - pk.row(kd, g, r), g), out_shardings=rep)
            for g in pk.shapes}

    def init(self, kd):
        params = {g: f(kd) for g, f in self._groups.items()}
        return params, self._opt0()

    def place(self, host):
        return self._place(host, self.mesh, self.b_specs)

    def grad_sumsq(self, opt):
        """Per-leaf sums of squares of the first gradient as the optimizer
        took it: Adam's first moment after one step is (1 - b1) g."""
        scale = 1.0 / (1.0 - self.b1) ** 2
        out = {}
        for g, f in self._sumsq.items():
            sums = np.asarray(f(opt["m"][g])) * scale
            out.update(self.packer.to_leaves(g, sums))
        return out

    def change_sumsq(self, params, kd):
        """Per-leaf sums of squares of the change from the seed's weights,
        one row at a time (a row's weights are drawn again on the device)."""
        out = {}
        for g, f in self._change.items():
            sums = np.stack([
                np.asarray(f(params[g], kd, np.int32(r)))
                for r in range(self.packer.rows(g))])
            out.update(self.packer.to_leaves(g, sums))
        return out


def _check_precision(stated, pol):
    """The program has to run as the configuration states.  Unquantized
    gathers and reduces move their wire dtype's bits."""
    z = pol.zcfg

    def bits(dtype):
        return jnp.dtype(dtype).itemsize * 8

    got = {"compute": jnp.dtype(z.compute_dtype).name,
           "moments": jnp.dtype(pol.moments_dtype).name,
           "weight_gather_bits": z.qwz_bits if z.qwz else bits(z.param_dtype),
           "grad_reduce_bits": z.qgz_bits if z.qgz else bits(z.reduce_dtype)}
    want = {k: stated[k] for k in got}
    if got != want:
        raise RuntimeError(f"program runs {got}, configuration states "
                           f"{want}")


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def _step(prog, params, opt, batch, gen, i):
    """One checked step: dispatch, make and place the next batch while the
    device computes, read the loss back."""
    params, opt, m = prog.fn(params, opt, batch)
    nxt = prog.place(gen.batch(i + 1))
    return params, opt, nxt, float(m["loss"])


def _peak_bytes(dev) -> int:
    """Peak device memory in use, as JAX reports it.  The whole of
    ``memory_stats`` goes to stderr (``peak_bytes_reserved`` among it)."""
    m = dev.memory_stats() or {}
    log(f"device {dev.id} memory_stats {m}")
    return m.get("peak_bytes_in_use", 0)


def _metric_reader(name):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


def checked_steps(prog, gen, seed: int):
    """The program's first ``CHECK_STEPS`` steps from the seed's weights,
    through the window's own call and feed: its readings for the check,
    and the state and next batch the window goes on from."""
    kd = key_data(seed)
    params, opt = prog.init(kd)
    batch = prog.place(gen.batch(0))
    losses = []
    for i in range(CHECK_STEPS):
        t = time.perf_counter()
        params, opt, batch, loss = _step(prog, params, opt, batch, gen, i)
        prog.step_s = time.perf_counter() - t     # the last one is warm
        losses.append(loss)
        if i == 0:
            first_grad = prog.grad_sumsq(opt)
    mine = reference.Readings(losses, first_grad,
                              prog.change_sumsq(params, kd))
    return mine, (params, opt, batch)


def window(prog, params, opt, batch, gen, first: int, seconds: float,
           ahead: int, ann):
    """Steps from step ``first`` for ``seconds``, each loss read ``ahead``
    steps after its dispatch; then every dispatched step is waited for.
    Returns the state, the next batch, the losses of all the steps and the
    seconds from the first dispatch to the end of the wait."""
    pending, losses, i = collections.deque(), [], first
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        with ann("bench.dispatch"):
            params, opt, m = prog.fn(params, opt, batch)
        pending.append(m["loss"])
        with ann("bench.make_batch"):
            host = gen.batch(i + 1)
        with ann("bench.place_batch"):
            batch = prog.place(host)
        i += 1
        if len(pending) > ahead:
            with ann("bench.loss_read"):
                losses.append(float(pending.popleft()))
    with ann("bench.drain"):
        jax.block_until_ready((params, opt))
        losses += [float(x) for x in pending]
    return params, opt, batch, losses, time.perf_counter() - t0


def run_cell(cell, seed: int, seconds: float, trace: bool, devices,
             t_start: float):
    """Run one cell; returns the result dict (``checks`` last)."""
    prog = Program(cell, devices)
    gen = tokens.Tokens(cell.traffic, cell.config["vocab"], seed)
    mine, (params, opt, batch) = checked_steps(prog, gen, seed)
    jax.block_until_ready((params, opt, batch))
    setup_s = time.perf_counter() - t_start
    log(f"setup {setup_s:.3f} s; checked losses {mine.losses}")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, d, **_: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    ann = jax.profiler.TraceAnnotation if trace else (lambda _: nullcontext())
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # host spans only: keep the host fast
        jax.profiler.start_trace(tdir, profiler_options=opts)
    n_before = len(compiles)
    ahead = max(1, math.ceil(AHEAD_S / prog.step_s))
    with ann("bench.window"):
        params, opt, batch, losses, elapsed = window(
            prog, params, opt, batch, gen, CHECK_STEPS, seconds, ahead, ann)
    n, bad = len(losses), sum(not math.isfinite(x) for x in losses)
    if trace:
        jax.profiler.stop_trace()
    window_compiles = len(compiles) - n_before
    tokens_per_s = n * cell.tokens_per_step / elapsed
    log(f"window {n} steps in {elapsed:.4f} s: {tokens_per_s:.1f} tokens/s; "
        f"{ahead} dispatched ahead of the loss read; "
        f"last loss {losses[-1]!r}; compiles inside the window "
        f"{window_compiles}")
    if window_compiles:
        log("bench: WARNING the window compiled; set-up missed a shape")

    peak = max(_peak_bytes(d) for d in devices)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": False, "attempted": n, "failed": bad}
    breakdown = None
    if trace:
        ctx = _trace_context(cell, prog, params, opt, batch, tdir, n,
                             elapsed, tokens_per_s, device)
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = ctx["summary"].busy_s
        device["window_s"] = ctx["summary"].window_s
        breakdown = ctx["summary"].breakdown()
        metrics = {}
        for m in cell.per_layer:
            v = _metric_reader(m["name"])(ctx)
            if v is None:
                log(f"metric {m['name']}: nothing to read")
            else:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {"tokens_per_s": {"value": tokens_per_s,
                                    "unit": "tokens/s"},
                   "setup_s": {"value": setup_s, "unit": "s"}}

    del params, opt, batch, prog
    jax.clear_caches()                  # unload the step and its scratch
    gc.collect()
    t_ref = time.perf_counter()
    ref = reference.Reference(cell.config, cell.traffic).run(
        seed, [gen.batch(k) for k in range(CHECK_STEPS)])
    values, where = check.numbers(mine, ref)
    ok, checks = check.judge(values, cell.limits)
    log(f"reference {time.perf_counter() - t_ref:.3f} s; losses program "
        f"{mine.losses} reference {ref.losses}")
    result["correct"] = bool(ok and bad == 0 and n > 0)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r} (at "
            f"{where[k]})")
    result["checks"] = checks
    return result


def _trace_context(cell, prog, params, opt, batch, tdir, steps, elapsed,
                   tokens_per_s, device):
    """What the per-layer readers read: the reduced trace and the
    counts the benchmark computes from the cell's shapes."""
    from repro.launch.jaxpr_analysis import analyze_jaxpr

    path = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                     recursive=True)
    summary = trace_reduce.summarize(
        path[0], flops.QUANT_KERNELS,
        devices=[d.id for d in prog.mesh.devices.flat])
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        (params, opt, batch))
    sizes = dict(zip(prog.mesh.axis_names, prog.mesh.devices.shape))
    wire = analyze_jaxpr(jax.make_jaxpr(prog.fn)(*abstract),
                         sizes)["collectives"]["wire_by_label"]
    peaks = spec.load_json(os.path.join(ROOT, "bench", "peaks.json"))
    if device["kind"] not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {device['kind']!r} in "
                       f"bench/peaks.json")
    return {"cell": cell, "summary": summary, "steps": steps,
            "elapsed_s": elapsed, "tokens_per_s": tokens_per_s,
            "flops_per_token": flops.flops_per_token(
                cell.config, int(cell.traffic["seq_len"])),
            "peak": peaks["devices"][device["kind"]],
            "device": device, "wire_by_label": wire,
            "kernel_calls": flops.quant_kernel_calls(
                prog.model.param_shapes(), len(prog.mesh.devices.flat),
                int(sizes["model"]), prog.model.zcfg.prefetch,
                cfg=cell.config),
            }


def main(argv=None) -> int:
    args = parse(argv)
    cell = spec.cell(args.workload)
    devices = chips_or_exit(cell.chips)
    from repro.launch.train import enable_compile_cache
    log(f"compile cache {enable_compile_cache()}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
