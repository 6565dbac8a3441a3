"""End-to-end training driver: data -> train_step -> checkpoint -> restart.

Production behaviours exercised here (and by tests/examples):
  * deterministic synthetic data pipeline (cursor == step counter)
  * periodic atomic PER-SHARD checkpoints via the ZeroState subsystem
    (train/state.py), optionally INT8 block-quantized (--ckpt-format int8)
  * restart-from-latest on failure (``--simulate-failure-at`` raises mid-run
    to prove it), including ELASTIC restart onto a different device count —
    flat buffers re-fit onto the new world's padding (see state.fit_to)
  * per-step metrics (loss / grad-norm / tokens/s)
  * ``--elastic``: the fault-tolerant supervisor (train/elastic.py) with
    async background checkpoints, SIGTERM grace drain, restart on worker
    death and live ``--reshard`` mid-run; ``--fault-*`` flags inject the
    failure menu from testing/faults.py for the smoke suite

Run on CPU with simulated devices, e.g.:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.train --arch gpt-350m --reduced \
      --mesh 4x2 --steps 20 --batch 16 --seq 64 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Any, Dict, Optional, Tuple

import numpy as np

# the persistent compile cache's home when $JAX_COMPILATION_CACHE_DIR is
# unset: a fixed path inside the checkout (<repo>/src/repro/launch/ -> <repo>)
DEFAULT_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))),
    ".jax_cache")


def enable_compile_cache() -> str:
    """Switch on JAX's persistent compilation cache; returns its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set (JAX reads it itself and
    nothing is set here); otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR`.  Call before the first compile — entry
    points do, importing this module does not.
    """
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    return path


@dataclasses.dataclass
class Built:
    """build_everything's result: unpacks like the legacy 6-tuple
    (``mesh, arch, model, opt_cfg, step, lm = build_everything(...)``)
    while also carrying the resolved policy for --tune consumers."""
    mesh: Any
    arch: Any
    model: Any
    opt_cfg: Any
    step: Any
    lm: Any
    policy: Any = None      # Policy (tune=off) or tune.ResolvedPolicy

    def __iter__(self):
        return iter((self.mesh, self.arch, self.model, self.opt_cfg,
                     self.step, self.lm))


def build_everything(arch_name: str, mesh_shape: Tuple[int, ...],
                     variant: str, reduced: bool, batch: int, seq: int,
                     lr: float, accum: int = 1, moe_chunks: int = 0,
                     tune: str = "off", hbm_gb: float = 16.0) -> "Built":
    """Construct (mesh, model, train_step, data, specs) for a run.

    ``tune``: "off" keeps the static preset table (train/policy.py);
    "static"/"probe" route through ``repro.tune.resolve`` — the committed
    profile or a live mesh probe feeding the prefetch/block/hpZ knobs,
    with the (k+1)-ring HBM ledger charged against ``hbm_gb``.
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticLM
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedule import warmup_cosine
    from repro.train import trainer as trainer_lib
    from repro.train.policy import make_policy

    from repro.launch.mesh import make_mesh
    axes = ("data", "model") if len(mesh_shape) == 2 \
        else ("pod", "data", "model")
    mesh = make_mesh(mesh_shape, axes)
    arch = get_config(arch_name)
    if reduced:
        arch = arch.reduced()
    if moe_chunks:
        arch = dataclasses.replace(arch, expert_chunks=moe_chunks)
    world = int(np.prod(mesh_shape))
    if tune and tune != "off":
        from repro.tune import GB, resolve
        pol = resolve(
            arch, axes, variant, mode=tune,
            mesh=mesh if tune == "probe" else None,
            mesh_sizes=dict(zip(axes, (int(s) for s in mesh_shape))),
            hbm_budget_bytes=int(hbm_gb * GB),
            tokens_per_device=max((batch * seq) // world, 1))
    else:
        pol = make_policy(arch, axes, variant)
    model = Model(arch, pol.zcfg, world=world)
    opt_cfg = AdamWConfig(lr=warmup_cosine(lr, 10, 10_000),
                          moments_dtype=pol.moments_dtype)
    step = trainer_lib.build_train_step(model, mesh, opt_cfg, accum=accum,
                                        global_batch=batch)
    lm = SyntheticLM(vocab=arch.vocab, seq_len=seq, seed=7)
    return Built(mesh, arch, model, opt_cfg, step, lm, policy=pol)


def save_ckpt(ckpt_dir: str, step_i: int, state, meta: Dict,
              fmt: str = "fp32"):
    """Per-shard atomic save of a :class:`repro.train.state.ZeroState`."""
    return state.save(ckpt_dir, step_i, meta=meta, fmt=fmt)


def restore_ckpt(ckpt_dir: str, model, mesh, opt_cfg):
    """Load latest checkpoint and re-shard onto the CURRENT mesh/model
    (elastic: the saved world size/alignment may differ)."""
    from repro.train.state import ZeroState

    st = ZeroState.restore(model, mesh, opt_cfg, ckpt_dir)
    if st is None:
        return None
    return st.step, st.params, st.opt, st.meta


def _setup_telemetry(args):
    """Install the jsonl tracer when ``--metrics-dir`` is given; otherwise
    leave the disabled singleton in place (no-op spans, zero overhead)."""
    from repro.obs.trace import Tracer, get_tracer, set_tracer
    if getattr(args, "metrics_dir", None):
        tracer = Tracer(os.path.join(args.metrics_dir, "events.jsonl"))
        set_tracer(tracer)
        return tracer
    return get_tracer()


def _comm_per_step(ts, mesh, params, opt, batch) -> Dict[str, float]:
    """One-time jaxpr walk of the train step: per-device wire bytes by
    collective label.  Traced once (abstract eval — no execution), then
    folded into host counters every step."""
    import jax
    from repro.launch.jaxpr_analysis import analyze_jaxpr
    cj = jax.make_jaxpr(ts.fn)(params, opt, batch)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return analyze_jaxpr(cj, sizes)["collectives"]["wire_by_label"]


def train_loop(args) -> Dict[str, Any]:
    import jax
    from repro.data.synthetic import make_batch
    from repro.obs.metrics import get_registry
    from repro.train.state import ZeroState
    from repro.train.trainer import place_batch

    mesh_shape = tuple(int(x) for x in args.mesh.split("x"))
    tune = getattr(args, "tune", "off") or "off"
    built = build_everything(
        args.arch, mesh_shape, args.variant, args.reduced, args.batch,
        args.seq, args.lr, args.accum, tune=tune,
        hbm_gb=getattr(args, "hbm_gb", 16.0))
    mesh, arch, model, opt_cfg, ts, lm = built
    pol = built.policy
    if tune != "off":
        print(f"[tune] {pol.explain()}")

    start = 0
    st = None
    if args.ckpt_dir:
        st = ZeroState.restore(model, mesh, opt_cfg, args.ckpt_dir)
    if st is not None:
        start = st.step
        print(f"[train] restored step {start} from {args.ckpt_dir} "
              f"(saved world={st.meta.get('world')}, now={ts.world})")
    else:
        st = ZeroState(model, mesh, opt_cfg).init(
            jax.random.PRNGKey(args.seed))
    params, opt = st.params, st.opt

    b_specs = ts.in_specs[2]
    losses, step_seconds = [], []
    t_start = time.time()
    telemetry = bool(getattr(args, "metrics_dir", None))
    tracer = _setup_telemetry(args)
    trace_steps = int(getattr(args, "trace_steps", 0) or 0)
    reg = get_registry()
    if telemetry:
        # record the chosen policy so dashboards can segment runs by knob
        z = pol.zcfg
        reg.gauge("tune.prefetch").set(z.prefetch)
        reg.gauge("tune.qwz").set(int(z.qwz))
        reg.gauge("tune.hpz").set(int(z.hpz))
        reg.gauge("tune.qgz").set(int(z.qgz))
        reg.gauge("tune.qwz_block").set(z.qwz_block)
        reg.gauge("tune.qgz_block").set(z.qgz_block)
        reg.gauge("tune.mode").set(
            {"off": 0, "static": 1, "probe": 2}.get(tune, 0))
    comm = None   # {label: per-device bytes/step}, filled on first step
    for i in range(start, args.steps):
        if args.simulate_failure_at is not None \
                and i == args.simulate_failure_at:
            raise RuntimeError(f"simulated node failure at step {i}")
        host = make_batch(arch, lm, i, args.batch)
        if args.accum > 1:
            host = {k: v.reshape((args.accum, -1) + v.shape[1:])
                    for k, v in host.items()}
        batch = place_batch(host, mesh, b_specs)
        if telemetry and comm is None:
            comm = _comm_per_step(ts, mesh, params, opt, batch)
            for lbl, b in comm.items():
                reg.gauge(f"comm.{lbl}.bytes_per_step").set(b)
        # profiler annotations only for the first --trace-steps steps (the
        # TraceAnnotation enter/exit is the one per-step cost worth gating)
        tracer.profiler_annotations = (i - start) < trace_steps
        t_step = time.monotonic_ns()
        with tracer.span("train.step", step=i):
            params, opt, metrics = jax.block_until_ready(
                ts.fn(params, opt, batch))
            loss = float(metrics["loss"])
        step_seconds.append((time.monotonic_ns() - t_step) / 1e9)
        losses.append(loss)
        if telemetry:
            reg.histogram("train.step.wall_ms").observe(
                step_seconds[-1] * 1e3)
            reg.counter("train.steps").inc()
            reg.counter("train.tokens").inc(float(metrics["tokens"]))
            for lbl, b in comm.items():
                reg.counter(f"comm.{lbl}.bytes").inc(b)
            tracer.counter("train.steps", 1, step=i)
            tracer.counter("train.tokens", float(metrics["tokens"]), step=i)
            tracer.flush()
        if args.log_every and (i % args.log_every == 0 or i == args.steps - 1):
            dt = time.time() - t_start
            toks = float(metrics["tokens"]) * (i - start + 1)
            print(f"[train] step {i} loss {loss:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"tok/s {toks / max(dt, 1e-9):,.0f}")
        if args.ckpt_dir and args.ckpt_every \
                and (i + 1) % args.ckpt_every == 0:
            st.params, st.opt, st.step = params, opt, i + 1
            save_ckpt(args.ckpt_dir, i + 1, st,
                      {"world": ts.world, "arch": arch.name,
                       "data_cursor": i + 1},
                      fmt=args.ckpt_format)
    gate_report = None
    if telemetry:
        from repro.obs.report import (export_snapshot,
                                      projected_wire_by_label, runtime_gate)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        projected = projected_wire_by_label(model, sizes, accum=args.accum)
        gate_report = runtime_gate(
            measured=comm or {}, projected=projected,
            strict=bool(getattr(args, "obs_gate", False)))
        policy_dict = (pol.as_dict() if hasattr(pol, "as_dict")
                       else {"mode": "off", "prefetch": pol.zcfg.prefetch,
                             "note": pol.note})
        export_snapshot(
            os.path.join(args.metrics_dir, "BENCH_runtime.json"),
            extra={"gate": gate_report,
                   "policy": policy_dict,
                   "config": {"arch": arch.name, "variant": args.variant,
                              "mesh": list(mesh_shape), "tune": tune,
                              "steps": args.steps, "batch": args.batch,
                              "seq": args.seq, "accum": args.accum}})
        tracer.close()
        ok = "PASS" if gate_report["ok"] else "FAIL"
        print(f"[train] obs gate {ok}: comm labels "
              f"{sorted((comm or {}))} vs analytic projection "
              f"(BENCH -> {args.metrics_dir}/BENCH_runtime.json)")
    st.params, st.opt, st.step = params, opt, args.steps
    return {"losses": losses, "entropy_bound": lm.entropy_bound,
            "final_loss": losses[-1] if losses else None,
            "gate": gate_report,
            # host wall time of each step, device work included; the
            # first one of a process also holds the step's compilation
            "step_seconds": step_seconds, "state": st}


def run_elastic(args) -> None:
    """Drive one run under the elastic supervisor (train/elastic.py),
    translating CLI fault/reshard knobs into the injection harness."""
    from repro.train.elastic import ElasticConfig, Supervisor

    faults = None
    plan = {}
    if args.fault_die_at is not None:
        plan[args.fault_die_at] = "die"
    if args.fault_preempt_at is not None:
        plan[args.fault_preempt_at] = "preempt"
    if plan:
        from repro.testing.faults import StepFaults
        faults = StepFaults(plan)
    hooks = []
    if args.fault_slow_write:
        from repro.testing.faults import SlowIO
        hooks.append(SlowIO(args.fault_slow_write))
    if args.fault_flaky_writes:
        from repro.testing.faults import FlakyIO
        hooks.append(FlakyIO(args.fault_flaky_writes))
    io_hooks = None
    if hooks:
        from repro.testing.faults import ChainedHooks
        io_hooks = hooks[0] if len(hooks) == 1 else ChainedHooks(hooks)
    reshard_plan = None
    if args.reshard:
        reshard_plan = {}
        for part in args.reshard.split(","):
            step_s, shape_s = part.split(":")
            reshard_plan[int(step_s)] = tuple(
                int(x) for x in shape_s.split("x"))

    cfg = ElasticConfig(
        arch=args.arch, reduced=args.reduced,
        mesh=tuple(int(x) for x in args.mesh.split("x")),
        variant=args.variant, steps=args.steps, batch=args.batch,
        seq=args.seq, lr=args.lr, accum=args.accum, seed=args.seed,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        ckpt_format=args.ckpt_format, async_ckpt=not args.sync_ckpt,
        retries=args.ckpt_retries, backoff=args.ckpt_backoff,
        grace=args.grace, max_restarts=args.max_restarts,
        metrics_dir=args.metrics_dir)
    sup = Supervisor(cfg, faults=faults, reshard_plan=reshard_plan,
                     io_hooks=io_hooks)
    sup.install_signal_handlers()
    out = sup.run_supervised()
    last = out["losses"].get(out["final_step"] - 1)
    print(f"[elastic] done: status={out['status']} "
          f"final_step={out['final_step']} restarts={out['restarts']} "
          f"resharded={out['resharded']} "
          f"last_loss={last if last is None else f'{last:.4f}'}")


def build_parser() -> argparse.ArgumentParser:
    """The training CLI; ``parse_args([...])`` also gives library callers
    (chip_smoke.py) the CLI's defaults."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt-350m")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU-friendly)")
    ap.add_argument("--mesh", default="2x2", help="e.g. 4x2 or 2x2x2")
    ap.add_argument("--variant", default="zeropp")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--ckpt-format", default="fp32",
                    choices=["fp32", "int8"],
                    help="per-shard payload: exact fp32 (default) or "
                         "qwZ-style block-quantized INT8 + fp16 scales "
                         "(~4x smaller)")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--simulate-failure-at", type=int, default=None)
    ap.add_argument("--max-restarts", type=int, default=2)
    # elastic supervisor mode (train/elastic.py) + its fault-injection knobs
    ap.add_argument("--elastic", action="store_true",
                    help="run under the elastic supervisor: async "
                         "checkpoints, SIGTERM grace drain, restart on "
                         "worker death, live resharding")
    ap.add_argument("--sync-ckpt", action="store_true",
                    help="elastic mode: blocking in-loop saves instead of "
                         "the async background writer")
    ap.add_argument("--grace", type=float, default=30.0,
                    help="seconds between preemption signal and exit")
    ap.add_argument("--ckpt-retries", type=int, default=0)
    ap.add_argument("--ckpt-backoff", type=float, default=0.05)
    ap.add_argument("--reshard", default=None,
                    help="live reshard plan, e.g. '3:2x2,6:4x2'")
    ap.add_argument("--fault-die-at", type=int, default=None,
                    help="inject a worker death at this step")
    ap.add_argument("--fault-preempt-at", type=int, default=None,
                    help="inject a graceful preemption at this step")
    ap.add_argument("--fault-slow-write", type=float, default=None,
                    help="sleep this long inside every shard write")
    ap.add_argument("--fault-flaky-writes", type=int, default=None,
                    help="fail the first N shard writes with OSError")
    # telemetry (obs/): jsonl event log, metrics registry, BENCH export
    ap.add_argument("--metrics-dir", default=None,
                    help="enable telemetry: write events.jsonl + "
                         "BENCH_runtime.json here (default: disabled, "
                         "zero-overhead no-op tracer)")
    ap.add_argument("--trace-steps", type=int, default=0,
                    help="wrap the first N steps in jax.profiler "
                         "TraceAnnotations (requires --metrics-dir)")
    ap.add_argument("--obs-gate", action="store_true",
                    help="assert the measured-vs-projected comm gate "
                         "(1%% per collective label) instead of only "
                         "reporting it")
    ap.add_argument("--tune", default="off",
                    choices=["off", "static", "probe"],
                    help="policy resolution (repro.tune): off = static "
                         "preset table; static = committed probe profile "
                         "(deterministic, CI); probe = time real "
                         "collectives on the live mesh at boot")
    ap.add_argument("--hbm-gb", type=float, default=16.0,
                    help="per-device HBM budget the tune ledger charges "
                         "the (k+1) ring buffers against")
    ap.add_argument("--kernel-backend", default=None,
                    choices=["pallas", "interpret", "xla", "ref"],
                    help="quant-kernel backend (kernels/ops.py); default "
                         "resolves $REPRO_KERNEL_BACKEND then platform "
                         "(pallas on TPU, xla elsewhere)")
    return ap


def main():
    # before any jax import: let the backend's scheduler exploit the
    # prefetched schedule (core/schedule.py, launch/xla_flags.py)
    from repro.launch.xla_flags import enable_overlap_flags
    enable_overlap_flags()
    enable_compile_cache()
    args = build_parser().parse_args()

    if args.kernel_backend is not None:
        from repro.kernels import ops as kops
        kops.set_backend(args.kernel_backend)

    if args.elastic:
        return run_elastic(args)

    # launcher-level fault tolerance: restart from latest checkpoint
    restarts = 0
    while True:
        try:
            out = train_loop(args)
            break
        except RuntimeError as e:
            if "simulated node failure" not in str(e) \
                    or restarts >= args.max_restarts:
                raise
            restarts += 1
            args.simulate_failure_at = None
            print(f"[train] {e} -> restarting from checkpoint "
                  f"({restarts}/{args.max_restarts})")
    print(f"[train] done: final loss {out['final_loss']:.4f} "
          f"(entropy bound {out['entropy_bound']:.4f}, "
          f"restarts={restarts})")


if __name__ == "__main__":
    main()
