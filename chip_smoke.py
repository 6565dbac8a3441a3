#!/usr/bin/env python3
"""Smoke run of the ZeRO++ train step on TPU chips, in one process.

    python chip_smoke.py             # one chip: kernel parity, then training
    python chip_smoke.py --chips 4   # 2x2 mesh: zeropp against the baseline

One chip.  Every hot-path Pallas kernel runs on the device at the
gpt-350m buffer widths a world of 1 and of 4 devices sees, and is checked
against its ``xla`` reference: quantize, dequantize, reorder-quantize and
both qgZ reduce hops bit for bit, the INT8 dequant-GEMM to ``GEMM_TOL``.
Then full-width gpt-350m (variant ``zeropp``, mesh 1x1, batch 8 x seq
1024) trains ``STEPS`` steps through ``repro.launch.train.train_loop``,
with the kernel backend left to resolve by itself: every loss finite, the
last below the first, every quantized op dispatched to ``pallas``.

Four chips.  The same model on a 2x2 ("data", "model") mesh at batch 16:
``zeropp`` (qwZ all-gather, hpZ secondary, qgZ two-hop all-to-all)
against ``baseline`` (ZeRO-3) from the same seed and data.  The two loss
curves agree to ``LOSS_RTOL`` at every step, and each device holds an
equal share of the model state.

Step times and memory printed here are smoke readings, not benchmark
numbers.  The last line of stdout is one JSON object, printed only when
every phase passed; any failure exits non-zero.  Without a TPU the script
exits 2 and prints no result: there is no CPU fallback.  The persistent
compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache`` in the checkout.
"""
import argparse
import importlib.metadata
import json
import math
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "gpt-350m"
SEQ = 1024
STEPS = 8
BATCH = {1: 8, 4: 16}                  # global batch per chip count
MESH = {1: "1x1", 4: "2x2"}            # ("data", "model")
GEMM_TOL = 1e-5     # max|pallas - xla| / max|xla|: fp32 summation order
LOSS_RTOL = 0.02    # |zeropp - baseline| / baseline, at every step
QUANT_OPS = ("quantize_blockwise", "dequantize_blockwise",
             "quantize_reordered", "dequant_reduce_quant", "dequant_reduce")


def _version(pkg):
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


class Failures(list):
    def check(self, ok, what):
        print(("PASS " if ok else "FAIL ") + what, flush=True)
        if not ok:
            self.append(what)


# ---------------------------------------------------------------------------
# kernel parity (one chip)
# ---------------------------------------------------------------------------

def kernel_parity(fails):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from repro.configs import get_config
    from repro.core.zeropp import ZeroConfig
    from repro.kernels import ops as kops
    from repro.models.model import Model

    z = ZeroConfig()
    qw, qg = z.qwz_cfg, z.qgz_cfg
    key = iter(jax.random.split(jax.random.PRNGKey(0), 256))

    def normal(shape, std):
        x = jax.random.normal(next(key), shape, jnp.float32) * std
        return x.at[..., :qw.block_size].set(0.0)   # one all-zero block

    def int8(shape):
        return lax.bitcast_convert_type(
            jax.random.bits(next(key), shape, jnp.uint8), jnp.int8)

    def scales(shape):
        return jax.random.uniform(next(key), shape, jnp.float32, 0.0, 1e-3)

    def run(backend, fn, *args):
        # outputs come back flat, as the train step holds its buffers
        with kops.use_backend(backend):
            return jax.jit(lambda *a: [o.reshape(-1) for o in
                                       jax.tree.leaves(fn(*a))])(*args)

    def bits(a):
        if a.dtype == jnp.float32:
            return lax.bitcast_convert_type(a, jnp.int32)
        if a.dtype == jnp.bfloat16:
            return lax.bitcast_convert_type(a, jnp.int16)
        return a

    def exact(name, fn, *args):
        t = time.perf_counter()
        got, want = run("pallas", fn, *args), run("xla", fn, *args)
        bad = [int(jnp.sum(bits(g) != bits(w))) if g.shape == w.shape
               else -1 for g, w in zip(got, want)]
        fails.check(all(b == 0 for b in bad),
                    f"parity {name}: mismatched elements per output {bad} "
                    f"({time.perf_counter() - t:.1f}s)")

    arch = get_config(ARCH)
    for world, (Y, X) in ((1, (1, 1)), (4, (2, 2))):
        shapes = Model(arch, z, world=world).param_shapes()
        widths = {name: shape[-1] for name, shape in shapes.items()
                  if shape[-1] % (world * qw.block_size) == 0}
        for name, n in widths.items():
            tag = f"w{world} {name} n={n}"
            exact(f"qwZ quantize {tag}",
                  lambda x: kops.quantize_blockwise(x, qw),
                  normal((n // world,), 0.02))
            exact(f"qgZ reorder-quantize {tag}",
                  lambda g: kops.quantize_reordered(g, qg),
                  normal((Y, X, n // world), 1e-3))
            exact(f"qgZ intra-hop reduce+requant {tag}",
                  lambda p, s: kops.dequant_reduce_quant(p, s, qg, qg),
                  int8((X, n // X // 2)),
                  scales((X, n // X // qg.block_size)))
            exact(f"qgZ inter-hop reduce {tag}",
                  lambda p, s: kops.dequant_reduce(p, s, qg),
                  int8((Y, n // world // 2)),
                  scales((Y, n // world // qg.block_size)))
            if world == 1:      # the gathered buffer is whole in any world
                exact(f"qwZ dequantize {tag}",
                      lambda p, s: kops.dequantize_blockwise(
                          p, s, qw, jnp.bfloat16),
                      int8((n,)), scales((n // qw.block_size,)))

    V, d = arch.vocab, arch.d_model
    payload, sc = int8((V, d)), scales((V, d // qw.block_size))
    for tokens in (8, 1024):
        x = jax.random.normal(next(key), (tokens, d), jnp.bfloat16)
        got, want = (run(b, kops.dequant_matmul, x, payload, sc)[0]
                     for b in ("pallas", "xla"))
        err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
        fails.check(err <= GEMM_TOL,
                    f"parity dequant-GEMM head {V}x{d} tokens={tokens}: "
                    f"max err / max|ref| = {err:.3e} (tol {GEMM_TOL:g})")


# ---------------------------------------------------------------------------
# training through the repo's entry point
# ---------------------------------------------------------------------------

def train(variant, chips):
    from repro.launch.train import build_parser, train_loop
    args = build_parser().parse_args([
        "--arch", ARCH, "--mesh", MESH[chips], "--variant", variant,
        "--steps", str(STEPS), "--batch", str(BATCH[chips]),
        "--seq", str(SEQ), "--log-every", "1"])
    print(f"--- train {ARCH} {variant} mesh {args.mesh} batch {args.batch} "
          f"x seq {args.seq}, {args.steps} steps", flush=True)
    return train_loop(args)


def check_dispatch(fails):
    from repro.kernels import ops as kops
    from repro.obs.metrics import get_registry
    counts = {k[len("kernels.dispatch."):]: v
              for k, v in get_registry().snapshot().items()
              if k.startswith("kernels.dispatch.")}
    print(f"kernel backend {kops.backend()}; dispatch counts {counts}")
    fails.check(all(counts.get(f"{op}.pallas", 0) > 0 for op in QUANT_OPS)
                and all(k.endswith(".pallas") for k in counts),
                "every quantized op dispatched to pallas")


def check_losses(fails, out):
    losses = out["losses"]
    fails.check(len(losses) == STEPS and all(map(math.isfinite, losses)),
                f"{len(losses)} losses, all finite")
    fails.check(losses[-1] < losses[0],
                f"loss fell: {losses[0]!r} -> {losses[-1]!r}")


def report_steps(out, tokens):
    s = out["step_seconds"]
    steady = statistics.median(s[1:])
    print(f"first step (compile included) {s[0]:.2f} s; steady steps "
          f"{[round(x, 4) for x in s[1:]]} s, median {steady:.4f} s, "
          f"{tokens / steady:,.0f} tokens/s (smoke reading)")


def state_bytes(state):
    """Bytes of (params, opt) each device holds, by device id."""
    import jax
    held = {}
    for leaf in jax.tree.leaves((state.params, state.opt)):
        for sh in leaf.addressable_shards:
            held[sh.device.id] = held.get(sh.device.id, 0) + sh.data.nbytes
    return held


def one_chip(fails):
    import jax
    from repro.obs.metrics import get_registry
    kernel_parity(fails)
    get_registry().reset()
    out = train("zeropp", 1)
    check_losses(fails, out)
    check_dispatch(fails)
    report_steps(out, BATCH[1] * SEQ)
    stats = jax.devices()[0].memory_stats()
    fails.check(stats is not None, "device reports memory_stats()")
    if stats:
        print(f"peak_bytes_in_use {stats['peak_bytes_in_use']} "
              f"({stats['peak_bytes_in_use'] / 2**30:.2f} GiB); "
              f"memory_stats {stats}")


def four_chips(fails):
    import jax
    from repro.obs.metrics import get_registry
    curves = {}
    for variant in ("zeropp", "baseline"):
        get_registry().reset()
        out = train(variant, 4)
        check_losses(fails, out)
        if variant == "zeropp":
            check_dispatch(fails)
        report_steps(out, BATCH[4] * SEQ)
        held = state_bytes(out["state"])
        stats = [dev.memory_stats() for dev in jax.devices()]
        fails.check(all(stats), "devices report memory_stats()")
        for dev, m in zip(jax.devices(), stats):
            m = m or {}
            print(f"{variant} device {dev.id}: bytes_in_use "
                  f"{m.get('bytes_in_use')} peak_bytes_in_use "
                  f"{m.get('peak_bytes_in_use')} model state "
                  f"{held.get(dev.id)}")
        fails.check(len(held) == 4 and max(held.values())
                    <= 1.01 * min(held.values()),
                    f"{variant}: model state spread evenly over "
                    f"{len(held)} devices")
        curves[variant] = out["losses"]
        del out                         # free this run's state first
    for i, (zl, bl) in enumerate(zip(curves["zeropp"], curves["baseline"])):
        rel = abs(zl - bl) / abs(bl)
        fails.check(rel <= LOSS_RTOL,
                    f"step {i}: zeropp {zl!r} baseline {bl!r} rel "
                    f"{rel:.5f} (tol {LOSS_RTOL})")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    chips = ap.parse_args().chips

    import jax
    import jaxlib
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (jax platform "
              f"{devs[0].platform!r}); this smoke has no CPU fallback",
              file=sys.stderr)
        return 2
    if len(devs) < chips:
        print(f"chip_smoke: --chips {chips} needs {chips} devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no src/repro beside {__file__}; run it from a "
              f"checkout of the repository", file=sys.stderr)
        return 2
    dev = devs[0]
    print(f"platform {dev.platform}  device_kind {dev.device_kind}  "
          f"count {len(devs)}")
    print(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
          f"libtpu {_version('libtpu')}")
    print(f"XLA_FLAGS={os.environ.get('XLA_FLAGS', '')!r}  "
          f"LIBTPU_INIT_ARGS={os.environ.get('LIBTPU_INIT_ARGS', '')!r}  "
          f"REPRO_KERNEL_BACKEND="
          f"{os.environ.get('REPRO_KERNEL_BACKEND', '')!r}")

    from repro.launch.train import enable_compile_cache
    cache = {"dir": enable_compile_cache(), "hits": 0, "misses": 0}

    def on_event(event, **_):
        for k in ("hits", "misses"):
            if event == f"/jax/compilation_cache/cache_{k}":
                cache[k] += 1
    jax.monitoring.register_event_listener(on_event)

    t0 = time.perf_counter()
    fails = Failures()
    (one_chip if chips == 1 else four_chips)(fails)
    print(f"compile cache {cache['dir']}: {cache['hits']} hits, "
          f"{cache['misses']} misses; run {time.perf_counter() - t0:.1f} s")
    if fails:
        print(f"chip_smoke: {len(fails)} check(s) failed: {fails}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
