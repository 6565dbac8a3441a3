"""Multi-device correctness checks, executed via testing.subproc.

Each ``check_*`` function builds a small mesh out of however many host
devices the subprocess was launched with, runs ZeRO++ collectives, and
asserts against single-collective oracles.  They are plain callables so the
benchmark harness can reuse them.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import collectives as cl
from repro.launch.mesh import make_mesh
from repro.core.quant import QuantConfig, quantize_blockwise, dequantize_blockwise


def _mesh2(data: int = None, model: int = 2):
    n = jax.device_count()
    data = data or n // model
    assert data * model == n, f"need data*model == {n}"
    return make_mesh((data, model), ("data", "model"))


def _mesh3(pod: int = 2, model: int = 2):
    n = jax.device_count()
    data = n // (pod * model)
    assert pod * data * model == n
    return make_mesh((pod, data, model), ("pod", "data", "model"))


# ---------------------------------------------------------------------------
# qgZ == reduce-scatter oracle (up to INT4 quantization error)
# ---------------------------------------------------------------------------

def _qgz_vs_oracle(mesh, intra_axis, inter_axes, all_axes, bits, block, n_per_dev):
    world = int(np.prod(list(mesh.shape.values())))
    rng = np.random.default_rng(0)
    g = rng.normal(size=(world * n_per_dev,)).astype(np.float32)
    cfg = QuantConfig(bits=bits, block_size=block)

    f_qgz = jax.jit(jax.shard_map(
        lambda x: cl.qgz_reduce_scatter(x, intra_axis, inter_axes, cfg),
        mesh=mesh, in_specs=P(all_axes), out_specs=P(all_axes)))
    f_ora = jax.jit(jax.shard_map(
        lambda x: cl.baseline_reduce_scatter(x.astype(jnp.float32), all_axes),
        mesh=mesh, in_specs=P(all_axes), out_specs=P(all_axes)))

    got = np.asarray(f_qgz(jnp.asarray(g)))
    want = np.asarray(f_ora(jnp.asarray(g)))

    # error bound: each of the two quant steps contributes <= scale/2 per
    # element; intra stage sums X quantized slices, inter stage sums Y
    # requantized partials whose magnitude grew by ~X.
    X = mesh.shape[intra_axis]
    Y = world // X
    amax = np.abs(g).max()
    qmax = 7.0 if bits == 4 else 127.0
    bound = (X * (amax / qmax) / 2) + Y * (X * amax / qmax) / 2
    err = np.abs(got - want).max()
    assert err <= bound * 1.1 + 1e-6, f"qgz err {err} > bound {bound}"
    # correlation ~1 (placement breakage would give ~0); exact placement is
    # separately proven by check_qgz_exact_when_representable
    c = np.corrcoef(got, want)[0, 1]
    assert c > 0.97, f"qgz placement broken, corr={c}"
    return err


def check_qgz_matches_reduce_scatter():
    mesh = _mesh2(model=2)
    _qgz_vs_oracle(mesh, "model", ("data",), ("data", "model"), 4, 64, 64 * 8)
    _qgz_vs_oracle(mesh, "model", ("data",), ("data", "model"), 8, 32, 32 * 8)


def check_qgz_multipod():
    mesh = _mesh3(pod=2, model=2)
    _qgz_vs_oracle(mesh, "model", ("pod", "data"), ("pod", "data", "model"),
                   4, 64, 64 * 8)


def check_qgz_exact_when_representable():
    """Placement/reordering correctness isolated from quantization error.

    Every device's local gradient is (rank+1)·P for a shared integer pattern
    P whose per-block absmax is exactly 7.  Then every block seen by either
    quantization stage is (integer)·P, its scale is that integer, and
    quantization is the identity — so qgZ must match reduce-scatter EXACTLY.
    Any slice-reordering bug scrambles P and fails loudly.
    """
    mesh = _mesh2(model=2)
    world = jax.device_count()
    cfg = QuantConfig(bits=4, block_size=32)
    n_per_dev = world * cfg.block_size  # L = block_size per destination
    rng = np.random.default_rng(1)
    pattern = rng.integers(-7, 8, size=(n_per_dev,)).astype(np.float32)
    pattern.reshape(-1, cfg.block_size)[:, 0] = 7.0  # pin block absmax
    ranks = (np.arange(world, dtype=np.float32) + 1.0)[:, None]
    g = (ranks * pattern[None, :]).reshape(-1)  # device d shard = (d+1)*P

    f_qgz = jax.jit(jax.shard_map(
        lambda x: cl.qgz_reduce_scatter(x, "model", ("data",), cfg),
        mesh=mesh, in_specs=P(("data", "model")), out_specs=P(("data", "model"))))
    f_ora = jax.jit(jax.shard_map(
        lambda x: cl.baseline_reduce_scatter(x, ("data", "model")),
        mesh=mesh, in_specs=P(("data", "model")), out_specs=P(("data", "model"))))
    got = np.asarray(f_qgz(jnp.asarray(g)))
    want = np.asarray(f_ora(jnp.asarray(g)))
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-3)


def check_qgz_1hop_and_ring():
    mesh = _mesh2(model=2)
    world = jax.device_count()
    cfg = QuantConfig(bits=8, block_size=32)
    rng = np.random.default_rng(2)
    g = rng.normal(size=(world * 32 * world,)).astype(np.float32)
    spec = P(("data", "model"))
    f1 = jax.jit(jax.shard_map(lambda x: cl.qgz_reduce_scatter_1hop(x, ("data", "model"), cfg),
                               mesh=mesh, in_specs=spec, out_specs=spec))
    fr = jax.jit(jax.shard_map(lambda x: cl.qgz_quantized_ring_reduce_scatter(x, ("data", "model"), cfg),
                               mesh=mesh, in_specs=spec, out_specs=spec))
    fo = jax.jit(jax.shard_map(lambda x: cl.baseline_reduce_scatter(x, ("data", "model")),
                               mesh=mesh, in_specs=spec, out_specs=spec))
    want = np.asarray(fo(jnp.asarray(g)))
    got1 = np.asarray(f1(jnp.asarray(g)))
    gotr = np.asarray(fr(jnp.asarray(g)))
    amax = np.abs(g).max()
    assert np.abs(got1 - want).max() < world * amax / 127, "1-hop wrong"
    # ring compounds error once per hop -> looser bound, but placement exact
    assert np.corrcoef(gotr, want)[0, 1] > 0.99, "ring placement broken"
    e1 = np.abs(got1 - want).max()
    er = np.abs(gotr - want).max()
    assert er >= e1 * 0.5, (
        f"expected ring error ({er}) to be no better than 1-hop ({e1})")


# ---------------------------------------------------------------------------
# qwZ / hpZ
# ---------------------------------------------------------------------------

def check_qwz_all_gather():
    mesh = _mesh2(model=2)
    world = jax.device_count()
    cfg = QuantConfig(bits=8, block_size=64)
    rng = np.random.default_rng(3)
    w = (rng.normal(size=(world * 256,)) * 0.02).astype(np.float32)
    spec = P(("data", "model"))
    f = jax.jit(jax.shard_map(
        lambda x: cl.qwz_all_gather(x, ("data", "model"), cfg, out_dtype=jnp.float32),
        mesh=mesh, in_specs=spec, out_specs=P(None), check_vma=False))
    got = np.asarray(f(jnp.asarray(w)))
    scale_bound = np.abs(w).max() / 127.0
    assert got.shape == w.shape
    assert np.abs(got - w).max() <= scale_bound * 0.51 + 1e-8
    # blocked must beat non-blocked on heterogeneous-scale data (Fig. 2)
    w2 = w.copy()
    w2[: world * 8] *= 100.0  # outlier block
    fn = jax.jit(jax.shard_map(
        lambda x: cl.qwz_all_gather(x, ("data", "model"), cfg,
                                    out_dtype=jnp.float32, blocked=False),
        mesh=mesh, in_specs=spec, out_specs=P(None), check_vma=False))
    eb = np.abs(np.asarray(f(jnp.asarray(w2))) - w2).max()
    en = np.abs(np.asarray(fn(jnp.asarray(w2))) - w2).max()
    assert eb < en, f"blocked ({eb}) should beat non-blocked ({en})"


def check_hpz_roundtrip():
    """fwd global gather -> slice secondary -> intra-only gather == original."""
    mesh = _mesh2(model=2)
    world = jax.device_count()
    rng = np.random.default_rng(4)
    w = rng.normal(size=(world * 64,)).astype(np.float32)
    spec = P(("data", "model"))

    def f(shard):
        full = cl.baseline_all_gather(shard, ("data", "model"))
        sec = cl.slice_secondary(full, "model")
        full2 = cl.hpz_all_gather(sec, "model")
        return full2

    got = np.asarray(jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                           out_specs=P(None),
                                           check_vma=False))(jnp.asarray(w)))
    np.testing.assert_allclose(got, w, rtol=0, atol=0)


ALL_CHECKS = [n for n in dir() if n.startswith("check_")]


# ---------------------------------------------------------------------------
# ZeRO++ engine: distributed grads == single-device grads
# ---------------------------------------------------------------------------

def _engine_setup():
    from repro.core.zeropp import ZeroConfig, zero_apply
    from repro.core.partition import ParamSpec

    d_in, d_h = 16, 32
    spec = ParamSpec((("w1", (d_in, d_h)), ("w2", (d_h, d_in))))

    def layer_f(wflat, x):
        w = spec.unpack(wflat.astype(jnp.float32))
        h = jnp.tanh(x @ w["w1"])
        return x + h @ w["w2"]

    def loss_of(apply_fn, pshard, x, n_global):
        h = apply_fn(pshard, x)
        return jnp.sum(h ** 2) / n_global

    return spec, layer_f, loss_of


def _engine_grads(mesh, zcfg, w_flat, x, spec, layer_f, loss_of):
    from repro.core.zeropp import zero_apply
    world = int(np.prod(list(mesh.shape.values())))
    n_global = x.shape[0] * x.shape[1]

    def step(pshard, xs):
        ap = zero_apply(layer_f, zcfg)
        def lf(p):
            return loss_of(ap, p, xs, n_global)
        l, g = jax.value_and_grad(lf)(pshard)
        return lax.psum(l, zcfg.dp_axes), g

    fstep = jax.jit(jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(("data", "model")), P(("data", "model"), None, None)),
        out_specs=(P(), P(("data", "model")))))
    return fstep(w_flat, x)


def check_engine_baseline_matches_local():
    """ZeRO-3 baseline engine grads == single-device jax.grad exactly
    (fp32 end-to-end, bf16 reduce disabled via reduce_dtype=f32)."""
    from repro.core.zeropp import ZeroConfig
    mesh = _mesh2(model=2)
    world = jax.device_count()
    spec, layer_f, loss_of = _engine_setup()
    align = world * 2
    padded = ((spec.size + align - 1) // align) * align
    spec = spec.with_align(align)

    rng = np.random.default_rng(0)
    w = (rng.normal(size=(padded,)) * 0.3).astype(np.float32)
    x = rng.normal(size=(world, 4, 16)).astype(np.float32)

    zcfg = ZeroConfig.baseline(param_dtype=jnp.float32,
                               compute_dtype=jnp.float32,
                               reduce_dtype=jnp.float32)
    l_d, g_d = _engine_grads(mesh, zcfg, jnp.asarray(w), jnp.asarray(x),
                             spec, layer_f, loss_of)

    # single-device oracle
    def local_loss(wf):
        h = layer_f(wf, jnp.asarray(x.reshape(-1, 16)))
        return jnp.sum(h ** 2) / (world * 4)
    l_o, g_o = jax.value_and_grad(local_loss)(jnp.asarray(w))
    np.testing.assert_allclose(float(l_d), float(l_o), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(g_d), np.asarray(g_o),
                               rtol=2e-4, atol=2e-5)


def check_engine_zeropp_close_to_local():
    """Full ZeRO++ (qwZ int8 + hpZ + qgZ int4) grads are close to exact
    grads: relative L2 error small, structure preserved."""
    from repro.core.zeropp import ZeroConfig
    mesh = _mesh2(model=2)
    world = jax.device_count()
    spec, layer_f, loss_of = _engine_setup()
    align = world * 64
    padded = ((spec.size + align - 1) // align) * align
    spec = spec.with_align(align)

    rng = np.random.default_rng(1)
    w = (rng.normal(size=(padded,)) * 0.3).astype(np.float32)
    x = rng.normal(size=(world, 4, 16)).astype(np.float32)

    zcfg = ZeroConfig(qwz_block=64, qgz_block=64,
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    l_d, g_d = _engine_grads(mesh, zcfg, jnp.asarray(w), jnp.asarray(x),
                             spec, layer_f, loss_of)

    def local_loss(wf):
        h = layer_f(wf, jnp.asarray(x.reshape(-1, 16)))
        return jnp.sum(h ** 2) / (world * 4)
    l_o, g_o = jax.value_and_grad(local_loss)(jnp.asarray(w))

    # loss uses int8-quantized weights -> close but not exact
    assert abs(float(l_d) - float(l_o)) / abs(float(l_o)) < 0.05
    gd, go = np.asarray(g_d), np.asarray(g_o)
    rel = np.linalg.norm(gd - go) / (np.linalg.norm(go) + 1e-9)
    assert rel < 0.2, f"zero++ grad rel err {rel}"
    # direction must agree strongly (what matters for SGD)
    cos = (gd * go).sum() / (np.linalg.norm(gd) * np.linalg.norm(go) + 1e-9)
    assert cos > 0.98, f"cosine {cos}"


def check_engine_hpz_consistency():
    """hpZ on vs off (with qwZ+qgZ off) must give IDENTICAL loss and grads:
    the secondary gather must reconstruct exactly the forward weights."""
    from repro.core.zeropp import ZeroConfig
    mesh = _mesh2(model=2)
    world = jax.device_count()
    spec, layer_f, loss_of = _engine_setup()
    align = world * 2
    padded = ((spec.size + align - 1) // align) * align
    rng = np.random.default_rng(2)
    w = (rng.normal(size=(padded,)) * 0.3).astype(np.float32)
    x = rng.normal(size=(world, 4, 16)).astype(np.float32)

    common = dict(qwz=False, qgz=False, param_dtype=jnp.float32,
                  compute_dtype=jnp.float32, reduce_dtype=jnp.float32)
    l1, g1 = _engine_grads(mesh, ZeroConfig(hpz=True, **common),
                           jnp.asarray(w), jnp.asarray(x), spec, layer_f, loss_of)
    l2, g2 = _engine_grads(mesh, ZeroConfig(hpz=False, **common),
                           jnp.asarray(w), jnp.asarray(x), spec, layer_f, loss_of)
    np.testing.assert_allclose(float(l1), float(l2), rtol=0, atol=0)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# system-level checks: trainer / serve / checkpoint / dry-run machinery
# ---------------------------------------------------------------------------

def _train_setup(mesh_shape=(4, 2), arch_name="gpt-350m", variant="zeropp",
                 batch=16, seq=64, accum=1):
    from repro.launch.train import build_everything
    return build_everything(arch_name, mesh_shape, variant, True, batch,
                            seq, 3e-3, accum=accum)


def _run_steps(mesh, arch, model, opt_cfg, ts, lm, steps, batch, start=0,
               params=None, opt=None):
    import jax
    from repro.data.synthetic import make_batch
    from repro.train.trainer import init_state, place_batch
    if params is None:
        params, opt = init_state(model, mesh, opt_cfg, jax.random.PRNGKey(0))
    losses = []
    for i in range(start, start + steps):
        host = make_batch(arch, lm, i, batch)
        b = place_batch(host, mesh, ts.in_specs[2])
        params, opt, metrics = ts.fn(params, opt, b)
        losses.append(float(metrics["loss"]))
    return params, opt, losses


def check_trainer_loss_decreases():
    """ZeRO++ end-to-end training on 8 simulated devices learns."""
    env = _train_setup()
    mesh, arch, model, opt_cfg, ts, lm = env
    _, _, losses = _run_steps(mesh, arch, model, opt_cfg, ts, lm, 8, 16)
    assert losses[-1] < losses[0] * 0.9, losses


def check_trainer_zeropp_tracks_baseline():
    """ZeRO++ loss curve stays close to the ZeRO-3 baseline curve (paper
    Fig. 14 in miniature)."""
    lcurves = {}
    for variant in ("baseline", "zeropp"):
        mesh, arch, model, opt_cfg, ts, lm = _train_setup(variant=variant)
        _, _, losses = _run_steps(mesh, arch, model, opt_cfg, ts, lm, 8, 16)
        lcurves[variant] = losses
    import numpy as np
    b = np.array(lcurves["baseline"])
    z = np.array(lcurves["zeropp"])
    rel = np.abs(b - z) / np.abs(b)
    assert rel.max() < 0.05, (b, z)


def check_trainer_grad_accumulation():
    """accum=2 with half microbatches ~= single step with full batch."""
    import numpy as np
    mesh, arch, model, opt_cfg, ts1, lm = _train_setup(
        variant="baseline", batch=16, accum=1)
    _, _, l1 = _run_steps(mesh, arch, model, opt_cfg, ts1, lm, 4, 16)

    from repro.train import trainer as trainer_lib
    ts2 = trainer_lib.build_train_step(model, mesh, opt_cfg, accum=2,
                                       global_batch=8)
    import jax
    from repro.data.synthetic import make_batch
    from repro.train.trainer import init_state, place_batch
    params, opt = init_state(model, mesh, opt_cfg, jax.random.PRNGKey(0))
    l2 = []
    for i in range(4):
        host = make_batch(arch, lm, i, 16)
        host = {k: v.reshape((2, 8) + v.shape[1:]) for k, v in host.items()}
        b = place_batch(host, mesh, ts2.in_specs[2])
        params, opt, metrics = ts2.fn(params, opt, b)
        l2.append(float(metrics["loss"]))
    rel = np.abs(np.array(l1) - np.array(l2)) / np.abs(np.array(l1))
    assert rel.max() < 0.02, (l1, l2)


def check_checkpoint_elastic_restart():
    """Save on world=8, restore on world=4: training continues and the
    restored loss matches the uninterrupted curve closely."""
    import tempfile
    import numpy as np
    import jax
    from repro.launch.train import restore_ckpt, save_ckpt
    from repro.train.state import ZeroState

    d = tempfile.mkdtemp(prefix="ckpt_elastic_")
    mesh8, arch, model8, opt_cfg, ts8, lm = _train_setup(mesh_shape=(4, 2))
    p8, o8, l_first = _run_steps(mesh8, arch, model8, opt_cfg, ts8, lm, 3, 16)
    save_ckpt(d, 3, ZeroState(model8, mesh8, opt_cfg, params=p8, opt=o8),
              {"world": 8})
    # uninterrupted reference: continue to step 5 on the same mesh
    _, _, l_ref = _run_steps(mesh8, arch, model8, opt_cfg, ts8, lm, 2, 16,
                             start=3, params=p8, opt=o8)

    # elastic: restore on a 2x2 mesh (uses 4 of the 8 devices)
    mesh4, arch4, model4, opt_cfg4, ts4, lm4 = _train_setup(mesh_shape=(2, 2))
    got = restore_ckpt(d, model4, mesh4, opt_cfg4)
    assert got is not None
    step_i, p4, o4, meta = got
    assert step_i == 3 and meta["world"] == 8
    _, _, l_new = _run_steps(mesh4, arch4, model4, opt_cfg4, ts4, lm4, 2, 16,
                             start=3, params=p4, opt=o4)
    rel = np.abs(np.array(l_ref) - np.array(l_new)) / np.abs(np.array(l_ref))
    assert rel.max() < 0.02, (l_ref, l_new)


# ---------------------------------------------------------------------------
# ZeroState subsystem: per-shard / quantized / elastic checkpointing
# ---------------------------------------------------------------------------

def _logical_equal(got: "np.ndarray", want: "np.ndarray"):
    """Bit-exact over the common (logical + shorter padding) trailing
    prefix; anything past it must be zero padding on both sides."""
    import numpy as np
    n = min(got.shape[-1], want.shape[-1])
    np.testing.assert_array_equal(got[..., :n], want[..., :n])
    if got.shape[-1] > n:
        assert not np.asarray(got[..., n:]).any()
    if want.shape[-1] > n:
        assert not np.asarray(want[..., n:]).any()


def check_state_elastic_restore():
    """Per-shard fp32 save at world=8, elastic restore at world=4 AND
    world=2 (three different paddings/alignments):

      * restored buffers are bit-exact against the saved state over the
        logical region, with zero padding beyond it;
      * one train step from the checkpoint is BIT-EXACT against the same
        step from a direct in-memory reshard of the world-8 state (the
        checkpoint roundtrip adds nothing);
      * the loss curve continues the uninterrupted world-8 curve closely
        (worlds differ, so reduction orders — not the state — differ).
    """
    import tempfile
    import numpy as np
    import jax
    from repro.data.synthetic import make_batch
    from repro.train.state import ZeroState, read_manifest
    from repro.train.trainer import place_batch

    d = tempfile.mkdtemp(prefix="ckpt_state_elastic_")
    mesh8, arch, model8, opt_cfg, ts8, lm = _train_setup(mesh_shape=(4, 2))
    p8, o8, _ = _run_steps(mesh8, arch, model8, opt_cfg, ts8, lm, 3, 16)
    p8_host = jax.device_get(p8)      # GLOBAL host state: the oracle input
    o8_host = jax.device_get(o8)
    path = ZeroState(model8, mesh8, opt_cfg, params=p8, opt=o8).save(
        d, 3, meta={"world": 8})
    man = read_manifest(path)
    assert man["world"] == 8 and man["format"] == "fp32"
    assert man["step"] == 3 and "blocks" in man["param_layout"]
    # uninterrupted reference (donates p8/o8 — everything saved above)
    _, _, l_ref = _run_steps(mesh8, arch, model8, opt_cfg, ts8, lm, 2, 16,
                             start=3, params=p8, opt=o8)

    for mesh_shape in ((2, 2), (1, 2)):
        meshW, archW, modelW, opt_cfgW, tsW, lmW = _train_setup(
            mesh_shape=mesh_shape)
        stW = ZeroState.restore(modelW, meshW, opt_cfgW, d)
        assert stW is not None and stW.step == 3
        assert stW.meta["world"] == 8
        for k, arr in stW.params.items():
            _logical_equal(np.asarray(jax.device_get(arr)), p8_host[k])
        for mom in ("m", "v"):
            for k, arr in stW.opt[mom].items():
                _logical_equal(np.asarray(jax.device_get(arr)),
                               o8_host[mom][k])

        # oracle: the same world-8 state resharded in memory (no files)
        stD = ZeroState(modelW, meshW, opt_cfgW).place_global(p8_host,
                                                              o8_host)
        host = make_batch(archW, lmW, 3, 16)
        bW = place_batch(host, meshW, tsW.in_specs[2])
        pa, oa, ma = tsW.fn(stW.params, stW.opt, bW)
        pb, ob, mb = tsW.fn(stD.params, stD.opt, bW)
        assert float(ma["loss"]) == float(mb["loss"]), mesh_shape
        for k in pa:
            np.testing.assert_array_equal(np.asarray(jax.device_get(pa[k])),
                                          np.asarray(jax.device_get(pb[k])))

        # loss continuity vs the uninterrupted world-8 curve
        host2 = make_batch(archW, lmW, 4, 16)
        b2 = place_batch(host2, meshW, tsW.in_specs[2])
        _, _, m2 = tsW.fn(pa, oa, b2)
        l_new = [float(ma["loss"]), float(m2["loss"])]
        rel = np.abs(np.array(l_ref) - np.array(l_new)) \
            / np.abs(np.array(l_ref))
        assert rel.max() < 0.02, (mesh_shape, l_ref, l_new)


def check_state_quantized_roundtrip():
    """INT8 block-quantized per-shard checkpoints: the roundtrip error of
    every buffer is inside the blockwise QuantConfig bound (absmax/127 per
    block, + fp16 scale storage), the files are ~4x smaller than fp32, and
    an elastic 8->4 restore from the quantized payload continues training
    with losses close to the fp32-restored run."""
    import os
    import tempfile
    import numpy as np
    import jax
    from repro.train.state import ZeroState, read_manifest

    d8 = tempfile.mkdtemp(prefix="ckpt_state_q8_")
    d32 = tempfile.mkdtemp(prefix="ckpt_state_f32_")
    mesh8, arch, model8, opt_cfg, ts8, lm = _train_setup(mesh_shape=(4, 2))
    p8, o8, _ = _run_steps(mesh8, arch, model8, opt_cfg, ts8, lm, 2, 16)
    p8_host = jax.device_get(p8)
    st8 = ZeroState(model8, mesh8, opt_cfg, params=p8, opt=o8)
    path8 = st8.save(d8, 2, fmt="int8", meta={"world": 8})
    path32 = st8.save(d32, 2, fmt="fp32", meta={"world": 8})
    man = read_manifest(path8)
    block = man["quant_block"]
    assert man["format"] == "int8_blockwise" and block
    assert all(v["quantized"] for k, v in man["layout"].items()
               if not v["replicated"])

    def _dir_bytes(p):
        return sum(os.path.getsize(os.path.join(p, f))
                   for f in os.listdir(p))
    sz8, sz32 = _dir_bytes(path8), _dir_bytes(path32)
    assert sz8 < 0.35 * sz32, (sz8, sz32)

    # elastic restore of the quantized payload onto world=4
    mesh4, arch4, model4, opt_cfg4, ts4, lm4 = _train_setup(mesh_shape=(2, 2))
    st4 = ZeroState.restore(model4, mesh4, opt_cfg4, d8)
    assert st4 is not None and st4.step == 2
    for k, arr in st4.params.items():
        got = np.asarray(jax.device_get(arr))
        want = p8_host[k]
        n = min(got.shape[-1], want.shape[-1])
        assert n % block == 0, (k, n, block)
        wb = want[..., :n].reshape(*want.shape[:-1], n // block, block)
        # per-block bound: scale/2 rounding + fp16 scale storage (2^-11
        # relative on a value of magnitude <= 127*scale => +0.062*scale)
        bound = np.abs(wb).max(axis=-1, keepdims=True) / 127.0 * 0.6 + 1e-8
        err = np.abs(got[..., :n].reshape(wb.shape) - wb)
        assert (err <= bound).all(), \
            (k, float(err.max()), float(bound.max()))

    # training continues; losses track the exact-fp32 restore closely
    st4f = ZeroState.restore(model4, mesh4, opt_cfg4, d32)
    _, _, l_q = _run_steps(mesh4, arch4, model4, opt_cfg4, ts4, lm4, 2, 16,
                           start=2, params=st4.params, opt=st4.opt)
    _, _, l_f = _run_steps(mesh4, arch4, model4, opt_cfg4, ts4, lm4, 2, 16,
                           start=2, params=st4f.params, opt=st4f.opt)
    rel = np.abs(np.array(l_q) - np.array(l_f)) / np.abs(np.array(l_f))
    assert rel.max() < 0.05, (l_q, l_f)


def check_state_serving_load():
    """bf16 params-only serving load path: a params-only INT8 checkpoint
    saved at world=8 loads onto a world=4 mesh as bf16 with the serving
    shardings, matching bf16(dequantized global) exactly."""
    import tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.train.state import (ZeroState, load_global, load_serving_params,
                                   fit_to)

    d = tempfile.mkdtemp(prefix="ckpt_state_serve_")
    mesh8, arch, model8, opt_cfg, ts8, lm = _train_setup(mesh_shape=(4, 2))
    from repro.train.trainer import init_state
    p8, _ = init_state(model8, mesh8, opt_cfg, jax.random.PRNGKey(5))
    st = ZeroState(model8, mesh8, opt_cfg, params=p8)   # params-only
    path = st.save(d, 0, fmt="int8")

    mesh4, arch4, model4, opt_cfg4, ts4, lm4 = _train_setup(mesh_shape=(2, 2))
    params = load_serving_params(model4, mesh4, d, dtype=jnp.bfloat16)
    _, tree, _ = load_global(path)
    want_shapes = model4.param_shapes()
    bf16 = np.dtype(jnp.bfloat16)
    for k, arr in params.items():
        assert arr.dtype == jnp.bfloat16, (k, arr.dtype)
        assert tuple(arr.shape) == tuple(want_shapes[k])
        want = fit_to(np.asarray(tree["params"][k]),
                      want_shapes[k]).astype(bf16)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(arr)).view(np.uint16),
            want.view(np.uint16))


def check_serve_prefill_decode_consistency(arch_name="qwen3-0.6b"):
    """prefill(P) + decode steps == prefill(P+n) teacher forcing."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.core.zeropp import ZeroConfig
    from repro.models.model import Model
    from repro.train import serve as serve_lib
    from repro.train.policy import make_policy

    mesh = _mesh2(model=2)
    world = jax.device_count()
    arch = get_config(arch_name).reduced()
    # f32 compute: this check proves PATH equivalence (prefill+decode ==
    # teacher forcing); bf16 reduction-order noise is not the subject
    pol = make_policy(arch, tuple(mesh.axis_names),
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    model = Model(arch, pol.zcfg, world=world)
    params = model.init_params(jax.random.PRNGKey(1), dtype=jnp.float32)
    from repro.train.state import param_specs
    p_specs = param_specs(model, tuple(mesh.axis_names))
    params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
              for k, v in params.items()}

    B, Pn, extra = 2, 14, 2
    cap = Pn + extra
    rng = np.random.default_rng(3)
    toks = rng.integers(0, arch.vocab, size=(B, cap)).astype(np.int32)

    batch_axes, kv_axes = ("data",), ("model",)
    ps = serve_lib.build_prefill_step(model, mesh, batch_axes, ("model",))
    ds = serve_lib.build_decode_step(model, mesh, batch_axes, kv_axes,
                                     donate=False)

    def put_batch(d, specs):
        return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
                for k, v in d.items()}

    # reference: prefill over the full P+extra prompt
    ref_logits, _ = ps.fn(params, put_batch(
        {"tokens": toks}, ps.in_specs[1]))
    ref = np.asarray(ref_logits)

    # prefill P, then decode the remaining tokens one at a time
    logits, caches = ps.fn(params, put_batch(
        {"tokens": toks[:, :Pn]}, ps.in_specs[1]))
    caches = serve_lib.pad_prefill_caches(model, caches, cap)
    c_specs = serve_lib.cache_specs(model, batch_axes, kv_axes)
    caches = jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
        caches, c_specs)
    got = None
    for t in range(Pn, cap):
        b = put_batch({"tokens": toks[:, t:t + 1]}, ds.in_specs[2])
        # per-sequence cache_pos vector (all rows at the same position here)
        got, caches = ds.fn(params, caches, b, jnp.full((B,), t, jnp.int32))
    got = np.asarray(got)
    err = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
    assert err < 2e-2, f"prefill/decode mismatch rel {err}"
    # argmax token must agree
    assert (got.argmax(-1) == ref.argmax(-1)).all()


def check_serve_consistency_ssm():
    check_serve_prefill_decode_consistency("mamba2-130m")


def check_serve_consistency_hybrid():
    check_serve_prefill_decode_consistency("recurrentgemma-2b")


def check_serve_consistency_moe():
    check_serve_prefill_decode_consistency("deepseek-moe-16b")


def check_dryrun_smoke_cell():
    """Exercise the dry-run machinery end-to-end on the tiny 2x2x2 mesh:
    lower, compile, memory/cost analysis, loop-aware collective parse."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch import dryrun as dr
    from repro.launch.mesh import make_test_mesh
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.train import trainer as trainer_lib
    from repro.train.policy import make_policy

    mesh = make_test_mesh((2, 2, 2), ("pod", "data", "model"))
    axes = tuple(mesh.axis_names)
    arch = get_config("qwen3-0.6b").reduced()
    pol = make_policy(arch, axes)
    model = Model(arch, pol.zcfg, world=8)
    opt_cfg = AdamWConfig(moments_dtype=pol.moments_dtype)
    ts = trainer_lib.build_train_step(model, mesh, opt_cfg, donate=False,
                                      global_batch=8)
    p_sh, o_sh = trainer_lib.state_shapes(model, opt_cfg)
    params = dr._abstract(p_sh, mesh, ts.in_specs[0])
    opt = dr._abstract(o_sh, mesh, ts.in_specs[1])
    import dataclasses as dc
    shape = dc.replace(
        __import__("repro.configs.base", fromlist=["SHAPES"]).SHAPES["train_4k"],
        seq_len=32, global_batch=8)
    batch = dr._abstract(dr.train_batch_shapes(model, shape), mesh,
                         ts.in_specs[2])
    lowered = ts.fn.lower(params, opt, batch)
    info = {"world": 8, "n_params": model.n_params(),
            "n_active": model.n_active_params(), "tokens_per_step": 8 * 32}
    info = dr.analyze(lowered, info, multi_pod=True)
    assert info["memory"].get("peak_bytes_per_device", 0) > 0
    assert info["cost"]["flops"] > 0
    assert info["collectives"]["count"] > 0
    assert info["collectives"]["wire_bytes"] > 0
    r = info["roofline"]
    assert r["dominant"] in ("compute_s", "memory_s", "collective_s")
    # analytic floor: at least the forward matmul flops must be counted
    floor = 2 * model.n_active_params() * (8 * 32) / 8
    assert info["cost"]["flops"] >= floor, (info["cost"], floor)


# ---------------------------------------------------------------------------
# prefetched schedule (core/schedule.py): equality, ordering, HLO overlap
# ---------------------------------------------------------------------------

def _prefetch_env(prefetch: int, variant: str = "zeropp", batch: int = 16,
                  arch_name: str = "gpt-350m", n_layers: int = 0):
    import jax
    from repro.configs import get_config
    from repro.data.synthetic import SyntheticLM
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.optim.schedule import warmup_cosine
    from repro.train import trainer as trainer_lib
    from repro.train.policy import make_policy

    mesh = _mesh2(model=2)
    axes = tuple(mesh.axis_names)
    # n_layers>0 deepens the stack beyond the 2-layer reduced default so
    # ring depths >= 2 are real (effective_prefetch clamps to n-1)
    arch = get_config(arch_name).reduced(
        **({"n_layers": n_layers} if n_layers else {}))
    pol = make_policy(arch, axes, variant, prefetch=prefetch)
    model = Model(arch, pol.zcfg, world=jax.device_count())
    opt_cfg = AdamWConfig(lr=warmup_cosine(3e-3, 10, 10_000),
                          moments_dtype=pol.moments_dtype)
    ts = trainer_lib.build_train_step(model, mesh, opt_cfg,
                                      global_batch=batch)
    lm = SyntheticLM(vocab=arch.vocab, seq_len=64, seed=7)
    return mesh, arch, model, opt_cfg, ts, lm


def _abstract_tree(tree, mesh, specs):
    """ShapeDtypeStructs with shardings (dryrun._abstract, duplicated here
    because importing launch.dryrun pins XLA_FLAGS to 512 devices)."""
    from jax.sharding import NamedSharding

    def mk(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(mk, tree, specs)


def _prefetch_abstract_args(pf: int, arch_name: str = "gpt-350m",
                            n_layers: int = 0):
    """(ts, abstract (params, opt, batch)) for a prefetch setting."""
    from repro.train import trainer as trainer_lib
    mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(
        pf, arch_name=arch_name, n_layers=n_layers)
    p_sh, o_sh = trainer_lib.state_shapes(model, opt_cfg)
    params = _abstract_tree(p_sh, mesh, ts.in_specs[0])
    opt = _abstract_tree(o_sh, mesh, ts.in_specs[1])
    bsh = {"tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
           "targets": jax.ShapeDtypeStruct((16, 64), jnp.int32)}
    batch = _abstract_tree(bsh, mesh, ts.in_specs[2])
    return ts, (params, opt, batch)


def check_prefetch_matches_sync():
    """prefetch=1 (double-buffered overlap schedule) and prefetch=0
    (synchronous) must produce IDENTICAL loss curves on the smoke model:
    the schedule reorders collectives relative to compute, not the math.

    Covers both the hpZ backward branch (zeropp) and the re-gather-primary
    branch (baseline, hpz=False) of the prefetched custom vjp."""
    for variant in ("zeropp", "baseline"):
        curves = {}
        for pf in (0, 1):
            mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(
                pf, variant=variant)
            _, _, losses = _run_steps(mesh, arch, model, opt_cfg, ts, lm,
                                      4, 16)
            curves[pf] = losses
        assert curves[0] == curves[1], (variant, curves[0], curves[1])


def _scan_bodies(jaxpr, out=None, seen=None):
    """All scan body jaxprs reachable from ``jaxpr`` (recursive)."""
    from repro.launch.jaxpr_analysis import _sub_jaxprs
    out = [] if out is None else out
    seen = set() if seen is None else seen
    if id(jaxpr) in seen:
        return out
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(eqn.params["jaxpr"].jaxpr)
        for sub, _ in _sub_jaxprs(eqn):
            _scan_bodies(sub, out, seen)
    return out


def _contains_dot(eqn, depth=0) -> bool:
    from repro.launch.jaxpr_analysis import _sub_jaxprs
    if eqn.primitive.name in ("dot_general", "conv_general_dilated"):
        return True
    if depth > 8:
        return False
    return any(_contains_dot(e, depth + 1)
               for sub, _ in _sub_jaxprs(eqn) for e in sub.eqns)


def _gather_dot_relation(body):
    """(first_gather_idx, first_dot_idx, any_gather_feeds_dot) for one scan
    body jaxpr, or None if it lacks gathers or dots."""
    eqns = body.eqns
    gathers = [i for i, e in enumerate(eqns)
               if e.primitive.name == "all_gather"]
    dots = [i for i, e in enumerate(eqns) if _contains_dot(e)]
    if not gathers or not dots:
        return None
    tainted = set()
    for g in gathers:
        tainted.update(id(v) for v in eqns[g].outvars)
    feeds = False
    for i, e in enumerate(eqns):
        if any(id(v) in tainted for v in e.invars):
            if _contains_dot(e):
                feeds = True
            tainted.update(id(v) for v in e.outvars)
    return min(gathers), min(dots), feeds


def _prefill_scan_relations(pf: int):
    from jax.sharding import NamedSharding
    from repro.train import serve as serve_lib

    mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(pf)
    ps = serve_lib.build_prefill_step(model, mesh, ("data",), ("model",))
    p_sh = {k: jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for k, s in model.param_shapes().items()}

    def mk(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    params = jax.tree.map(mk, p_sh, ps.in_specs[0])
    batch = jax.tree.map(
        mk, {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32)},
        ps.in_specs[1])
    cj = jax.make_jaxpr(ps.fn)(params, batch)
    return [r for r in map(_gather_dot_relation, _scan_bodies(cj.jaxpr))
            if r]


def check_prefetch_jaxpr_ordering():
    """Double-buffering in the traced program, two granularities:

    * prefill (directly traced, trace order == jaxpr order): with
      prefetch=1 the block scan issues layer i+1's gather BEFORE layer i's
      matmuls, and no matmul consumes it; with prefetch=0 the gather feeds
      the matmuls (synchronous).
    * train step (AD partial-eval may reorder jaxpr text, so only the
      dependence property is meaningful): prefetch=1 yields independent
      (overlappable) gather bodies for BOTH the forward and backward block
      scans; prefetch=0 yields none.
    """
    # --- prefill: ordering + independence -------------------------------
    rels = {pf: _prefill_scan_relations(pf) for pf in (0, 1)}
    assert rels[0] and all(feeds for _, _, feeds in rels[0]), rels[0]
    free = [(g, d) for g, d, feeds in rels[1] if not feeds]
    assert free, f"no double-buffered prefill scan body: {rels[1]}"
    assert all(g < d for g, d in free), \
        f"prefetch gather not issued before the matmuls: {free}"

    # --- train step: independence in fwd AND bwd scans ------------------
    trels = {}
    for pf in (0, 1):
        ts, args = _prefetch_abstract_args(pf)
        cj = jax.make_jaxpr(ts.fn)(*args)
        trels[pf] = [r for r in map(_gather_dot_relation,
                                    _scan_bodies(cj.jaxpr)) if r]
    assert trels[0] and all(feeds for _, _, feeds in trels[0]), trels[0]
    tfree = [r for r in trels[1] if not r[2]]
    assert len(tfree) >= 2, \
        f"expected fwd+bwd double-buffered scan bodies, got {trels[1]}"


def check_prefetch_overlap_fraction():
    """Compiled-HLO verification (the acceptance criterion): with
    prefetch=1 the block-scan collectives are schedulable under compute
    (overlap_fraction > 0); with prefetch=0 nothing is."""
    from repro.launch.hlo_analysis import analyze_overlap

    ov = {}
    for pf in (0, 1):
        ts, args = _prefetch_abstract_args(pf)
        txt = ts.fn.lower(*args).compile().as_text()
        ov[pf] = analyze_overlap(txt)
    # 0.8 pins the measured value benchmarks/throughput_model.py projects
    # from (MEASURED_OVERLAP = 0.89): if the schedule regresses, this
    # fails before the benchmark silently misreports the prefetch win
    assert ov[1]["overlap_fraction"] > 0.8, ov[1]
    # fwd qwZ gather (payload+scales) + bwd hpZ gather + qgZ a2a pipeline
    assert ov[1]["overlappable_collectives"] >= 5, ov[1]
    assert ov[0]["overlap_fraction"] == 0.0, ov[0]
    assert ov[0]["overlappable_collectives"] == 0, ov[0]


def _stack_loss_and_grads(pf: int, arch_name: str, n_layers: int = 0):
    """(psum loss, grad pytree as numpy) for a tiny stack at one prefetch
    setting — fresh init, fixed seed, one fixed batch."""
    import jax
    from repro.data.synthetic import make_batch
    from repro.train.trainer import init_state, place_batch

    mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(
        pf, arch_name=arch_name, n_layers=n_layers)
    params, _ = init_state(model, mesh, opt_cfg, jax.random.PRNGKey(0))
    host = make_batch(arch, lm, 0, 16)
    b = place_batch(host, mesh, ts.in_specs[2])
    z = model.zcfg

    def gf(p, batch):
        def lf(pp):
            loss, _ = model.loss_fn(pp, batch, ts.run_spec, ts.world)
            return loss

        l, g = jax.value_and_grad(lf)(p)
        return lax.psum(l, z.dp_axes), g

    sm = jax.shard_map(gf, mesh=mesh,
                       in_specs=(ts.in_specs[0], ts.in_specs[2]),
                       out_specs=(P(), ts.in_specs[0]), check_vma=False)
    loss, grads = jax.jit(sm)(params, b)
    return float(loss), {k: np.asarray(v) for k, v in grads.items()}


def _moe_loss_and_grads(pf: int):
    return _stack_loss_and_grads(pf, "deepseek-moe-16b")


def _assert_depth_sweep(arch_name: str, depths, n_layers: int = 4):
    """Losses AND gradients at every ring depth must be bit-identical to
    the synchronous (prefetch=0) reference."""
    l0, g0 = _stack_loss_and_grads(0, arch_name, n_layers)
    for pf in depths:
        l, g = _stack_loss_and_grads(pf, arch_name, n_layers)
        assert l == l0, (arch_name, pf, l, l0)
        for k in g0:
            assert np.array_equal(g0[k], g[k]), (
                f"{arch_name} prefetch={pf}: grad {k} differs from the "
                f"synchronous reference, max abs diff "
                f"{np.abs(g0[k].astype(np.float64) - g[k].astype(np.float64)).max()}")


def check_prefetch_depth_sweep():
    """Dense 4-layer stack: the depth-k ring is bit-exact to the
    synchronous reference at every depth — including 8 > n_layers, which
    must clamp to the ring's n-1 maximum rather than lap itself."""
    _assert_depth_sweep("gpt-350m", (1, 2, 3, 8))


def check_moe_prefetch_depth_sweep():
    """MoE 4-layer stack (chunk + layer rings, routing-ahead speculative
    chunk-0 gather, hpZ-residual nested recompute): bit-exact to the
    synchronous reference at every ring depth, including one beyond the
    layer count (clamp case)."""
    _assert_depth_sweep("deepseek-moe-16b", (1, 2, 3, 8))


def check_ring_overlap_depth():
    """The ring acceptance check, from compiled HLO on 4-layer stacks:

      * prefetch=2 yields strictly higher depth-credited
        (effective_overlap) overlap than prefetch=1 on BOTH the dense and
        the MoE stack at the canonical low-bandwidth operating point
        (hlo_analysis.RING_OPERATING_POINT), with the structural fraction
        no lower and ring slack 2 visible in the HLO;
      * the MoE nested-remat expert re-gather is no longer exposed: every
        loop body holding collectives also holds compute (the gather-only
        loop the old qwZ-tier recompute left behind is gone), and the
        structural MoE fraction clears the pre-hpZ-recompute 0.63.
    """
    from repro.launch.hlo_analysis import (RING_OPERATING_POINT,
                                           analyze_overlap,
                                           effective_overlap)

    for arch in ("gpt-350m", "deepseek-moe-16b"):
        ov = {}
        for pf in (1, 2):
            ts, args = _prefetch_abstract_args(pf, arch_name=arch,
                                               n_layers=4)
            txt = ts.fn.lower(*args).compile().as_text()
            ov[pf] = analyze_overlap(txt)
        assert ov[2]["overlap_fraction"] >= ov[1]["overlap_fraction"], \
            (arch, ov[1]["overlap_fraction"], ov[2]["overlap_fraction"])
        e1 = effective_overlap(ov[1], **RING_OPERATING_POINT)
        e2 = effective_overlap(ov[2], **RING_OPERATING_POINT)
        f1 = e1["effective_overlap_fraction"]
        f2 = e2["effective_overlap_fraction"]
        assert f2 > f1 > 0.0, (arch, f1, f2)
        slack2 = max(l["max_slack_iters"] for l in ov[2]["per_loop"].values())
        assert slack2 >= 2, (arch, slack2)
        for pf in (1, 2):
            for name, loop in ov[pf]["per_loop"].items():
                assert loop["has_compute"], (
                    f"{arch} prefetch={pf}: loop {name} holds collectives "
                    f"with no compute to hide behind (exposed re-gather)")
        if arch == "deepseek-moe-16b":
            assert ov[1]["overlap_fraction"] > 0.7, ov[1]["overlap_fraction"]


def check_moe_prefetch_matches_sync():
    """MoE stack (deepseek-style shared+routed experts, chunked): the
    chunk/layer double-buffered schedule (prefetch=1) and the synchronous
    reference (prefetch=0) must produce BIT-IDENTICAL loss curves AND
    gradients — the schedule reorders collectives against compute at two
    granularities, never the math."""
    curves = {}
    for pf in (0, 1):
        mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(
            pf, arch_name="deepseek-moe-16b")
        _, _, losses = _run_steps(mesh, arch, model, opt_cfg, ts, lm, 4, 16)
        curves[pf] = losses
    assert curves[0] == curves[1], (curves[0], curves[1])

    l0, g0 = _moe_loss_and_grads(0)
    l1, g1 = _moe_loss_and_grads(1)
    assert l0 == l1, (l0, l1)
    for k in g0:
        assert np.array_equal(g0[k], g1[k]), (
            f"grad {k} differs between schedules: max abs diff "
            f"{np.abs(g0[k].astype(np.float64) - g1[k].astype(np.float64)).max()}")


def check_moe_prefetch_overlap_fraction():
    """Compiled-HLO verification of the MoE schedule (acceptance
    criterion): with prefetch=1 the layer-scan shared gathers AND the
    nested expert-chunk gathers/reduces are schedulable under compute
    (overlap_fraction > 0.7 — the hpZ-residual recompute removed the
    exposed backward expert re-gather loop, so every in-loop collective
    body now holds compute); with prefetch=0 every in-loop collective
    stays on the critical path."""
    from repro.launch.hlo_analysis import analyze_overlap

    ov = {}
    for pf in (0, 1):
        ts, args = _prefetch_abstract_args(pf, arch_name="deepseek-moe-16b")
        txt = ts.fn.lower(*args).compile().as_text()
        ov[pf] = analyze_overlap(txt)
    assert ov[1]["overlap_fraction"] > 0.7, ov[1]
    # nested chunk loops must be seen as loops (layer scan + chunk scans)
    assert len(ov[1]["per_loop"]) >= 2, ov[1]["per_loop"]
    # the nested-remat expert re-gather no longer shows up as a
    # gather-only loop of exposed slow-tier bytes
    for name, loop in ov[1]["per_loop"].items():
        assert loop["has_compute"], (name, loop)
    assert ov[0]["overlap_fraction"] == 0.0, ov[0]
    assert ov[0]["overlappable_collectives"] == 0, ov[0]


def check_qgz_1hop_rejects_misaligned():
    """qgz_reduce_scatter_1hop must raise (not silently truncate) when the
    gradient length is not a multiple of world*block."""
    mesh = _mesh2(model=2)
    world = jax.device_count()
    cfg = QuantConfig(bits=8, block_size=32)
    spec = P(("data", "model"))
    n_bad = world * (world * 32 + 8)  # local len not divisible by world*32
    g = jnp.ones((n_bad,), jnp.float32)
    f = jax.jit(jax.shard_map(
        lambda x: cl.qgz_reduce_scatter_1hop(x, ("data", "model"), cfg),
        mesh=mesh, in_specs=spec, out_specs=spec))
    try:
        f(g)
    except ValueError as e:
        assert "multiple of world*block" in str(e), e
        return
    raise AssertionError("qgz_reduce_scatter_1hop accepted misaligned input")


def check_serve_engine_continuous_batching():
    """Continuous-batching engine on an 8-device (2,4) mesh, batch-sharded
    slots, INT8 per-shard checkpoint boot: greedy engine output for every
    request (mixed prompt lengths, staggered admission over 4 slots) must
    equal running that request alone through the raw prefill+decode steps
    with the SAME restored weights."""
    import tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import ServeEngine, steps
    from repro.train.policy import make_policy
    from repro.train.state import ZeroState, param_specs

    mesh = _mesh2(model=4)                      # (data=2, model=4)
    world = jax.device_count()
    arch = get_config("qwen3-0.6b").reduced()
    pol = make_policy(arch, tuple(mesh.axis_names))
    model = Model(arch, pol.zcfg, world=world)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    p_specs = param_specs(model, tuple(mesh.axis_names))
    params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
              for k, v in params.items()}

    with tempfile.TemporaryDirectory(prefix="zeropp_serve8_") as d:
        st = ZeroState(model, mesh, opt_cfg=None, params=params,
                       meta={"arch": arch.name})
        st.save(d, 0, fmt="int8")
        kv_len = 32
        eng = ServeEngine.from_checkpoint(
            model, mesh, d, n_slots=4, kv_len=kv_len,
            batch_axes=("data",), kv_axes=("model",))

    jobs = [(5, 6), (11, 4), (8, 5), (16, 3), (3, 7), (9, 4)]  # 6 req, 4 slots
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, arch.vocab, p).astype(np.int32)
               for p, _ in jobs]
    uids = [eng.submit(pr, max_new_tokens=n)
            for pr, (_, n) in zip(prompts, jobs)]
    res = eng.run(max_steps=200)
    # slot recycling really happened: more requests than slots
    assert len(set(eng.slot_history.values())) <= 4
    assert len(eng.slot_history) == len(jobs)

    # oracle: each request alone through the raw steps, same INT8 weights
    ps = steps.build_prefill_step(model, mesh, (), ())
    ds = steps.build_decode_step(model, mesh, (), ("model",), donate=False)
    for uid, pr, (P_, n) in zip(uids, prompts, jobs):
        logits, caches = ps.fn(eng.params, {"tokens": pr[None, :]})
        caches = steps.pad_prefill_caches(model, caches, kv_len)
        want = [int(jnp.argmax(logits[0, -1]))]
        for i in range(1, n):
            logits, caches = ds.fn(
                eng.params, caches,
                {"tokens": jnp.array([[want[-1]]], jnp.int32)},
                jnp.full((1,), P_ + i - 1, jnp.int32))
            want.append(int(jnp.argmax(logits[0, -1])))
        assert res[uid] == want, (uid, res[uid], want)


def check_serve_engine_paged():
    """Paged engine on a sharded mesh ((n//4, 4); runs at 4 AND 8 devices),
    INT8 per-shard checkpoint boot: the page-table engine (chunked prefill,
    prefix cache, page-granularity admission) must emit token streams
    identical to the whole-slot slab engine on the same request set — and
    a second wave resubmitting shared-prefix prompts must actually HIT the
    prefix cache while staying identical."""
    import tempfile
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import ServeEngine
    from repro.train.policy import make_policy
    from repro.train.state import ZeroState, param_specs

    mesh = _mesh2(model=4)                      # (n//4, model=4)
    world = jax.device_count()
    arch = get_config("qwen3-0.6b").reduced()
    pol = make_policy(arch, tuple(mesh.axis_names))
    model = Model(arch, pol.zcfg, world=world)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    p_specs = param_specs(model, tuple(mesh.axis_names))
    params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
              for k, v in params.items()}

    kv_len = 32
    with tempfile.TemporaryDirectory(prefix="zeropp_paged_") as d:
        st = ZeroState(model, mesh, opt_cfg=None, params=params,
                       meta={"arch": arch.name})
        st.save(d, 0, fmt="int8")
        paged = ServeEngine.from_checkpoint(
            model, mesh, d, n_slots=4, kv_len=kv_len,
            kv_axes=("model",), pool="paged", page_size=8, chunk_size=8)
        slab = ServeEngine.from_checkpoint(
            model, mesh, d, n_slots=4, kv_len=kv_len, kv_axes=("model",))

    # 6 requests over 4 slots; prompts span 1..3 chunks and three of them
    # share a full-page 16-token prefix
    rng = np.random.default_rng(7)
    shared = rng.integers(0, arch.vocab, 16).astype(np.int32)
    jobs = [
        (rng.integers(0, arch.vocab, 5).astype(np.int32), 6),
        (np.concatenate([shared, rng.integers(0, arch.vocab, 3)
                         .astype(np.int32)]), 4),
        (rng.integers(0, arch.vocab, 11).astype(np.int32), 4),
        (np.concatenate([shared, rng.integers(0, arch.vocab, 6)
                         .astype(np.int32)]), 3),
        (rng.integers(0, arch.vocab, 21).astype(np.int32), 3),
        (np.concatenate([shared, rng.integers(0, arch.vocab, 1)
                         .astype(np.int32)]), 5),
    ]

    def run(eng):
        uids = [eng.submit(pr, max_new_tokens=n) for pr, n in jobs]
        res = eng.run(max_steps=300)
        return [res[u] for u in uids]

    want = run(slab)
    got = run(paged)
    assert got == want, (got, want)
    u = paged.pool.utilization()
    # the 2nd/3rd shared-prefix requests land after the 1st registered it
    assert u["prefix_hits"] >= 1 and u["prefix_tokens_reused"] >= 16, u
    assert paged.pool.n_free == 4 and (paged.pool.refcount == 0).all()


def check_serve_engine_speculative():
    """Speculative decoding on a sharded mesh: (a) an INDEPENDENT drafter
    (same arch, different init — a bad drafter) still yields token streams
    identical to plain paged greedy decode; (b) self-draft (perfect
    drafter) accepts > 1 token per verify step."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.configs import get_config
    from repro.models.model import Model
    from repro.serve import ServeEngine
    from repro.train.policy import make_policy
    from repro.train.state import param_specs

    mesh = _mesh2(model=4)
    world = jax.device_count()
    arch = get_config("qwen3-0.6b").reduced()
    pol = make_policy(arch, tuple(mesh.axis_names),
                      param_dtype=jnp.float32, compute_dtype=jnp.float32)
    model = Model(arch, pol.zcfg, world=world)
    p_specs = param_specs(model, tuple(mesh.axis_names))

    def put(p):
        return {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
                for k, v in p.items()}

    params = put(model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32))
    drafter = put(model.init_params(jax.random.PRNGKey(1), dtype=jnp.float32))

    kv_len, jobs = 32, [(5, 6), (11, 4), (8, 5), (3, 7)]
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, arch.vocab, p).astype(np.int32)
               for p, _ in jobs]

    def run(**kw):
        eng = ServeEngine(model, mesh, params, n_slots=4, kv_len=kv_len,
                          kv_axes=("model",), pool="paged", page_size=8,
                          chunk_size=8, cache_dtype=jnp.float32, **kw)
        uids = [eng.submit(pr, max_new_tokens=n)
                for pr, (_, n) in zip(prompts, jobs)]
        res = eng.run(max_steps=300)
        return [res[u] for u in uids], eng

    want, _ = run()
    got_bad, eng_bad = run(draft=(model, drafter), spec_tokens=4)
    assert got_bad == want, (got_bad, want)
    got_self, eng_self = run(draft=(model, params), spec_tokens=4)
    assert got_self == want, (got_self, want)
    acc = eng_self.stats()["spec_accepted"]
    assert acc["mean"] is not None and acc["mean"] > 1.0, acc
    # the bad drafter is still correct, just slower (fewer accepted)
    bad = eng_bad.stats()["spec_accepted"]
    assert bad["mean"] <= acc["mean"], (bad, acc)


# ---------------------------------------------------------------------------
# elastic runtime: async checkpoints, faults, live resharding (DESIGN.md §6)
# ---------------------------------------------------------------------------

def check_elastic_async_overlap():
    """The async writer genuinely overlaps: with every shard write slowed,
    train steps still complete WHILE a write is in flight, every submitted
    snapshot commits, and the committed manifest carries checksums."""
    import os
    import tempfile
    from repro.testing.faults import SlowIO
    from repro.train.elastic import ElasticConfig, Supervisor
    from repro.train.state import latest_checkpoint, read_manifest

    d = tempfile.mkdtemp(prefix="elastic_overlap_")
    slow = SlowIO(0.5)
    out = Supervisor(ElasticConfig(steps=8, ckpt_dir=d, ckpt_every=2),
                     io_hooks=slow).run_supervised()
    ws = out["writer_stats"]
    assert out["status"] == "complete" and out["final_step"] == 8
    assert ws["submitted"] == 4 and ws["completed"] == 4, ws
    assert ws["failed"] == 0 and ws["abandoned"] == 0, ws
    assert ws["steps_overlapped"] > 0, ws     # steps ran during writes
    assert slow.calls == 4
    path = latest_checkpoint(d)
    assert path is not None and os.path.basename(path) == "ckpt_8"
    man = read_manifest(path)
    assert man["step"] == 8 and man["checksums"], man.keys()


def check_elastic_kill_resume():
    """Worker death at step 5 (checkpoints every 2): the supervisor
    restarts, resumes from the step-4 async checkpoint, and every
    post-resume loss is BIT-IDENTICAL to the uninterrupted oracle run."""
    import tempfile
    from repro.testing.faults import StepFaults
    from repro.train.elastic import ElasticConfig, Supervisor

    oracle = Supervisor(ElasticConfig(steps=8)).run_supervised()
    d = tempfile.mkdtemp(prefix="elastic_kill_")
    sup = Supervisor(ElasticConfig(steps=8, ckpt_dir=d, ckpt_every=2),
                     faults=StepFaults({5: "die"}))
    out = sup.run_supervised()
    assert out["status"] == "complete" and out["final_step"] == 8
    assert out["restarts"] == 1 and out["fired"] == [(5, "die")]
    assert set(out["losses"]) == set(range(8))
    for i in range(8):          # includes replayed steps 4..7: bit-exact
        assert out["losses"][i] == oracle["losses"][i], \
            (i, out["losses"][i], oracle["losses"][i])


def check_elastic_live_reshard():
    """Live 8 -> 4 -> 8 resharding mid-run with NO checkpoint dir: the
    state moves through host memory only.  Steps before the first reshard
    are bit-exact vs the fixed-world oracle; the whole curve stays within
    rel 2e-2 (different worlds reduce in different orders)."""
    import numpy as np
    from repro.train.elastic import ElasticConfig, Supervisor

    oracle = Supervisor(ElasticConfig(steps=9)).run_supervised()
    sup = Supervisor(ElasticConfig(steps=9),
                     reshard_plan={3: (2, 2), 6: (4, 2)})
    out = sup.run_supervised()
    assert out["resharded"] == [(3, 8, 4), (6, 4, 8)], out["resharded"]
    for i in range(3):                       # same world so far: bit-exact
        assert out["losses"][i] == oracle["losses"][i], i
    l_ref = np.array([oracle["losses"][i] for i in range(9)])
    l_new = np.array([out["losses"][i] for i in range(9)])
    rel = np.abs(l_ref - l_new) / np.abs(l_ref)
    assert rel.max() < 0.02, (l_ref, l_new)


def check_elastic_crash_during_write():
    """A REAL SIGKILL lands mid async write (each shard write slowed to
    5s): the staging dir is left behind WITHOUT a manifest, so
    ``latest_checkpoint`` still selects the previous committed step; the
    relaunch resumes from it, sweeps the debris on the re-save, and
    completes."""
    import os
    import signal
    import tempfile
    from repro.testing.faults import kill_on_marker, run_train
    from repro.train.state import MANIFEST, latest_checkpoint

    d = tempfile.mkdtemp(prefix="elastic_crash_")
    args = ["--elastic", "--reduced", "--mesh", "4x2", "--steps", "8",
            "--ckpt-dir", d, "--ckpt-every", "2", "--fault-slow-write", "5"]
    rc, lines = kill_on_marker(args, "committed step 2",
                               sig=signal.SIGKILL, delay=1.5)
    assert rc != 0
    staging = os.path.join(d, "ckpt_4.tmp")
    assert os.path.isdir(staging), os.listdir(d)      # genuine debris
    assert not os.path.exists(os.path.join(staging, MANIFEST))
    latest = latest_checkpoint(d)
    assert latest is not None and os.path.basename(latest) == "ckpt_2", \
        os.listdir(d)

    lines2 = run_train(["--elastic", "--reduced", "--mesh", "4x2",
                        "--steps", "8", "--ckpt-dir", d, "--ckpt-every",
                        "2"])
    txt = "\n".join(lines2)
    assert "resumed from step 2" in txt, txt[-2000:]
    assert "status=complete" in txt and "final_step=8" in txt
    assert os.path.basename(latest_checkpoint(d)) == "ckpt_8"
    assert not os.path.isdir(staging)       # re-save swept the stale dir


def check_elastic_sigterm_grace():
    """Graceful preemption, both ways in: a REAL SIGTERM mid-run and an
    injected in-process preempt.  Both must drain the in-flight write,
    cut a final synchronous checkpoint, exit cleanly, and resume."""
    import os
    import signal
    import tempfile
    from repro.testing.faults import StepFaults, kill_on_marker, run_train
    from repro.train.elastic import ElasticConfig, Supervisor
    from repro.train.state import latest_checkpoint, read_manifest

    # real signal, subprocess
    d = tempfile.mkdtemp(prefix="elastic_term_")
    args = ["--elastic", "--reduced", "--mesh", "4x2", "--steps", "12",
            "--ckpt-dir", d, "--ckpt-every", "2", "--grace", "30"]
    rc, lines = kill_on_marker(args, "step 4 loss", sig=signal.SIGTERM)
    txt = "\n".join(lines)
    assert rc == 0, txt[-2000:]
    assert "preemption requested" in txt and "preempted at step" in txt
    assert "status=preempted" in txt
    path = latest_checkpoint(d)
    assert path is not None
    stop = read_manifest(path)["step"]
    assert 4 < stop < 12                   # stopped early, but checkpointed
    txt2 = "\n".join(run_train(
        ["--elastic", "--reduced", "--mesh", "4x2", "--steps", "12",
         "--ckpt-dir", d, "--ckpt-every", "2"]))
    assert f"resumed from step {stop}" in txt2, txt2[-2000:]
    assert "status=complete" in txt2 and "final_step=12" in txt2

    # injected preempt, in-process
    d2 = tempfile.mkdtemp(prefix="elastic_term2_")
    out = Supervisor(ElasticConfig(steps=12, ckpt_dir=d2, ckpt_every=2),
                     faults=StepFaults({5: "preempt"})).run_supervised()
    assert out["status"] == "preempted" and out["final_step"] == 5
    assert os.path.basename(latest_checkpoint(d2)) == "ckpt_5"
    out2 = Supervisor(ElasticConfig(steps=12, ckpt_dir=d2,
                                    ckpt_every=2)).run_supervised()
    assert out2["status"] == "complete" and out2["final_step"] == 12


def check_elastic_corrupt_fallback():
    """Quarantine-and-fall-back: with the two newest checkpoints damaged
    (bit-rot in one, truncation in the other), ``restore_resilient``
    quarantines both and restores the oldest intact one; when EVERY
    checkpoint is damaged it returns None instead of raising."""
    import os
    import tempfile
    from repro.testing.faults import corrupt_shard, truncate_shard
    from repro.train.elastic import ElasticConfig, Supervisor
    from repro.train.state import ZeroState

    d = tempfile.mkdtemp(prefix="elastic_corrupt_")
    Supervisor(ElasticConfig(steps=6, ckpt_dir=d,
                             ckpt_every=2)).run_supervised()
    corrupt_shard(os.path.join(d, "ckpt_6"))     # crc catches bit-rot
    truncate_shard(os.path.join(d, "ckpt_4"))    # short read
    mesh, arch, model, opt_cfg, ts, lm = _train_setup()
    st = ZeroState.restore_resilient(model, mesh, opt_cfg, d)
    assert st is not None and int(st.step) == 2
    assert os.path.isdir(os.path.join(d, "ckpt_6.corrupt"))
    assert os.path.isdir(os.path.join(d, "ckpt_4.corrupt"))
    corrupt_shard(os.path.join(d, "ckpt_2"))
    assert ZeroState.restore_resilient(model, mesh, opt_cfg, d) is None
    # and the supervisor on an all-quarantined dir starts from scratch
    out = Supervisor(ElasticConfig(steps=2, ckpt_dir=d,
                                   ckpt_every=2)).run_supervised()
    assert out["status"] == "complete" and 0 in out["losses"]


def check_elastic_flaky_io_retry():
    """Transient write errors: the first two shard writes fail with
    OSError; with retries=3 the async writer absorbs them (retry with
    exponential backoff) and every snapshot still commits."""
    import os
    import tempfile
    from repro.testing.faults import FlakyIO
    from repro.train.elastic import ElasticConfig, Supervisor
    from repro.train.state import latest_checkpoint

    d = tempfile.mkdtemp(prefix="elastic_flaky_")
    flaky = FlakyIO(2)
    out = Supervisor(ElasticConfig(steps=4, ckpt_dir=d, ckpt_every=2,
                                   retries=3, backoff=0.01),
                     io_hooks=flaky).run_supervised()
    ws = out["writer_stats"]
    assert out["status"] == "complete"
    assert ws["completed"] == 2 and ws["failed"] == 0, ws
    assert flaky.remaining == 0 and flaky.calls >= 3   # 2 fails + retries
    assert os.path.basename(latest_checkpoint(d)) == "ckpt_4"


# ---------------------------------------------------------------------------
# kernel backend seam (kernels/ops.py + kernels/platform.py) — DESIGN.md §7
# ---------------------------------------------------------------------------

def check_kernel_backend_depth_sweep():
    """The prefetch ring composes with kernel-backed quant: with the
    backend forced to `interpret` (the real Pallas kernel bodies, run
    through the interpreter), the dense depth sweep stays bit-identical
    in losses AND gradients to the synchronous reference — same assertion
    as check_prefetch_depth_sweep, different quant implementation."""
    from repro.kernels import ops
    with ops.use_backend("interpret"):
        assert ops.backend() == "interpret"
        _assert_depth_sweep("gpt-350m", (1, 2, 3))


def check_kernel_backend_serve_engine():
    """The serve-engine bit-identity check (engine output == raw
    per-request prefill+decode, INT8 checkpoint boot) passes unchanged
    with the kernel backend forced to `interpret` — covering the fused
    INT8 dequant-GEMM serving head, which both sides dispatch through
    kernels/ops.py."""
    from repro.kernels import ops
    with ops.use_backend("interpret"):
        check_serve_engine_continuous_batching()


def check_kernel_backend_train_bitexact():
    """Switching the quant backend must not move the training trajectory:
    `interpret` (Pallas kernel bodies) and `xla` (pure-jnp reference)
    loss curves are bit-identical — the kernels ARE the reference math
    (quantize/dequant/fused-reduce parity is exact, not approximate)."""
    from repro.kernels import ops
    curves = {}
    for be in ("xla", "interpret"):
        with ops.use_backend(be):
            mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(1)
            _, _, losses = _run_steps(mesh, arch, model, opt_cfg, ts, lm,
                                      3, 16)
            curves[be] = losses
    assert curves["xla"] == curves["interpret"], curves


def check_qwz_gemm_head_matches_staged():
    """The fused INT8 dequant-GEMM serving head (qwz_gemm=True: the decode
    GEMM eats the gathered INT8 payload, scales applied per weight
    tile) must produce the same logits as the staged
    gather-dequant-einsum head (qwz_gemm=False) — tight allclose (fp32
    accumulation-order only) and identical argmax, under both the xla
    and interpret backends."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from repro.configs import get_config
    from repro.kernels import ops
    from repro.models.model import Model
    from repro.train import serve as serve_lib
    from repro.train.policy import make_policy
    from repro.train.state import param_specs

    mesh = _mesh2(model=2)
    world = jax.device_count()
    arch = get_config("qwen3-0.6b").reduced()
    rng = np.random.default_rng(5)
    toks = rng.integers(0, arch.vocab, size=(2, 12)).astype(np.int32)

    outs = {}
    for fused in (True, False):
        pol = make_policy(arch, tuple(mesh.axis_names), qwz_gemm=fused)
        model = Model(arch, pol.zcfg, world=world)
        params = model.init_params(jax.random.PRNGKey(2), dtype=jnp.float32)
        p_specs = param_specs(model, tuple(mesh.axis_names))
        params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
                  for k, v in params.items()}
        for be in ("xla", "interpret"):
            with ops.use_backend(be):
                ps = serve_lib.build_prefill_step(model, mesh, (),
                                                  ("model",))
                batch = {"tokens": jax.device_put(
                    toks, NamedSharding(mesh, ps.in_specs[1]["tokens"]))}
                logits, _ = ps.fn(params, batch)
                outs[(fused, be)] = np.asarray(logits)

    want = outs[(False, "xla")]                  # the staged reference head
    for k, got in outs.items():
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5,
                                   err_msg=str(k))
        assert (got.argmax(-1) == want.argmax(-1)).all(), k


# ---------------------------------------------------------------------------
# observability: measured-vs-projected comm, telemetry replay, runtime gate
# (DESIGN.md §8, obs/)
# ---------------------------------------------------------------------------

def _obs_crosscheck(variant: str, arch_name: str, n_layers: int = 4):
    """Per-label wire bytes from the traced step's jaxpr must match the
    analytic event model to 1% (in practice: to the byte) at every ring
    depth.  Labels come from the ``zero.*`` named scopes in
    core/collectives.py; the projection from ``Model.comm_events`` folded
    through ``zeropp.step_wire_by_label``."""
    from repro.launch.jaxpr_analysis import analyze_jaxpr
    from repro.obs.report import GateFailure, runtime_gate
    from repro.core.zeropp import step_wire_by_label
    from repro.train import trainer as trainer_lib

    for pf in (0, 1, 2):
        mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(
            pf, variant=variant, arch_name=arch_name, n_layers=n_layers)
        p_sh, o_sh = trainer_lib.state_shapes(model, opt_cfg)
        params = _abstract_tree(p_sh, mesh, ts.in_specs[0])
        opt = _abstract_tree(o_sh, mesh, ts.in_specs[1])
        bsh = {"tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
               "targets": jax.ShapeDtypeStruct((16, 64), jnp.int32)}
        batch = _abstract_tree(bsh, mesh, ts.in_specs[2])
        cj = jax.make_jaxpr(ts.fn)(params, opt, batch)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        measured = analyze_jaxpr(cj, sizes)["collectives"]["wire_by_label"]
        projected = step_wire_by_label(model.comm_events(), model.zcfg,
                                       sizes)
        # unlabeled collectives (loss psums) carry no parameter traffic
        assert measured.get("other", 0.0) == 0.0, measured
        try:
            runtime_gate(measured=measured, projected=projected,
                         strict=True)
        except GateFailure as e:
            raise AssertionError(
                f"{variant}/{arch_name} pf={pf}: {e}") from e


def check_obs_comm_crosscheck():
    """Dense stack, zeropp + baseline, prefetch depths 0/1/2 (satellite:
    runtime counters vs comm_volume analytics must agree per label)."""
    _obs_crosscheck("zeropp", "gpt-350m")
    _obs_crosscheck("baseline", "gpt-350m")


def check_obs_comm_crosscheck_moe():
    """MoE stack (chunked experts, spec ring, hpZ recompute gathers):
    the event enumeration must track the real schedule at every depth —
    this is where qgZ scale bytes and per-chunk recompute gathers are
    easiest to drop on either side."""
    _obs_crosscheck("zeropp", "deepseek-moe-16b")


def check_obs_telemetry_failure_replay():
    """Telemetry under failure: a run killed at step 5 and restarted from
    the step-4 checkpoint re-emits steps 4.. into the SAME append-mode
    jsonl; ``replay_counters`` must dedupe the re-emitted steps so the
    interrupted log replays to totals identical to an uninterrupted
    oracle — and, truncated at the kill step, to the oracle's prefix."""
    import os
    import tempfile
    from repro.obs.trace import read_events, replay_counters
    from repro.testing.faults import StepFaults
    from repro.train.elastic import ElasticConfig, Supervisor

    d_o = tempfile.mkdtemp(prefix="obs_oracle_")
    oracle = Supervisor(
        ElasticConfig(steps=8, metrics_dir=d_o)).run_supervised()
    assert oracle["status"] == "complete"
    log_o = os.path.join(d_o, "events.jsonl")
    tot_o = replay_counters(log_o)
    assert tot_o["train.steps"] == 8, tot_o

    d_i = tempfile.mkdtemp(prefix="obs_interrupted_")
    ck = tempfile.mkdtemp(prefix="obs_ckpt_")
    out = Supervisor(
        ElasticConfig(steps=8, ckpt_dir=ck, ckpt_every=2,
                      metrics_dir=d_i),
        faults=StepFaults({5: "die"})).run_supervised()
    assert out["restarts"] == 1 and out["final_step"] == 8
    log_i = os.path.join(d_i, "events.jsonl")
    tot_i = replay_counters(log_i)

    # steps 4,5 were emitted twice (pre-kill + replay) yet count once
    raw_step_recs = [r for r in read_events(log_i)
                     if r.get("kind") == "counter"
                     and r["name"] == "train.steps"]
    assert len(raw_step_recs) > 8, len(raw_step_recs)
    for key in ("train.steps", "train.tokens", "train.loss"):
        assert tot_i[key] == tot_o[key], (key, tot_i[key], tot_o[key])

    # prefix property: truncating the replay at the kill step matches the
    # oracle truncated at the same step
    pre_i = replay_counters(log_i, up_to_step=4)
    pre_o = replay_counters(log_o, up_to_step=4)
    for key in ("train.steps", "train.tokens", "train.loss"):
        assert pre_i[key] == pre_o[key], (key, pre_i, pre_o)

    # restart itself was recorded exactly once
    evs = [r["name"] for r in read_events(log_i)
           if r.get("kind") == "event"]
    assert evs.count("elastic.restart") == 1, evs


def check_obs_runtime_gate():
    """The full measured-vs-projected gate on a REAL train run, plus the
    disabled-telemetry overhead bound: alternate plain steps with steps
    under the no-op tracer + guard and compare medians (alternation puts
    machine noise on both sides)."""
    import os
    import tempfile
    import time as _time
    from repro.obs.metrics import Registry, set_registry
    from repro.obs.report import runtime_gate
    from repro.obs.trace import Tracer, set_tracer
    from repro.launch.jaxpr_analysis import analyze_jaxpr
    from repro.core.zeropp import step_wire_by_label
    from repro.data.synthetic import make_batch
    from repro.train.state import ZeroState
    from repro.train.trainer import place_batch

    mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(1)
    st = ZeroState(model, mesh, opt_cfg).init(jax.random.PRNGKey(0))
    params, opt = st.params, st.opt
    reg = Registry()
    old_reg = set_registry(reg)
    d = tempfile.mkdtemp(prefix="obs_gate_")
    tracer = Tracer(os.path.join(d, "events.jsonl"))
    off = Tracer(enabled=False)
    old_tr = set_tracer(tracer)
    try:
        host = make_batch(arch, lm, 0, 16)
        batch = place_batch(host, mesh, ts.in_specs[2])
        cj = jax.make_jaxpr(ts.fn)(params, opt, batch)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        comm = analyze_jaxpr(cj, sizes)["collectives"]["wire_by_label"]
        params, opt, m = ts.fn(params, opt, batch)       # compile once
        jax.block_until_ready(m["loss"])

        # -- overhead: interleave plain steps with telemetry-DISABLED
        # steps (no-op span + False guard — the production off path) in
        # ABBA order (off, plain, plain, off, ...) so neither side always
        # runs first; medians over interleaved samples cancel machine drift.
        # 256 samples a side: with 8 or 24, the medians of two identical
        # ~10 ms CPU steps differ by more than 2 % in a quarter to a half
        # of runs on a loaded host
        plain_s, off_s = [], []
        telemetry = False
        n_timed = 512
        for i in range(1, n_timed + 1):
            host = make_batch(arch, lm, i, 16)
            batch = place_batch(host, mesh, ts.in_specs[2])
            t0 = _time.monotonic()
            if i % 4 in (0, 1):
                with off.span("train.step", step=i):
                    params, opt, m = ts.fn(params, opt, batch)
                    jax.block_until_ready(m["loss"])
                if telemetry:       # pragma: no cover — the off guard
                    reg.counter("train.steps").inc()
                off_s.append(_time.monotonic() - t0)
            else:
                params, opt, m = ts.fn(params, opt, batch)
                jax.block_until_ready(m["loss"])
                plain_s.append(_time.monotonic() - t0)

        # -- a couple of fully-ENABLED steps: counters must accumulate
        # exactly measured-per-step * n_steps
        n_enabled = 2
        for i in range(n_timed + 1, n_timed + 1 + n_enabled):
            host = make_batch(arch, lm, i, 16)
            batch = place_batch(host, mesh, ts.in_specs[2])
            with tracer.span("train.step", step=i):
                params, opt, m = ts.fn(params, opt, batch)
                jax.block_until_ready(m["loss"])
            for lbl, b in comm.items():
                reg.counter(f"comm.{lbl}.bytes").inc(b)
            tracer.counter("train.steps", 1, step=i)
            tracer.flush()
        for lbl, b in comm.items():
            got = reg.counter(f"comm.{lbl}.bytes").value
            assert got == b * n_enabled, (lbl, got, b, n_enabled)

        projected = step_wire_by_label(model.comm_events(), model.zcfg,
                                       sizes)
        report = runtime_gate(measured=comm, projected=projected,
                              enabled_s=plain_s, disabled_s=off_s,
                              overhead_tol=0.02, strict=True)
        assert report["ok"], report
    finally:
        set_registry(old_reg)
        set_tracer(old_tr)
        tracer.close()


# ---------------------------------------------------------------------------
# tuner (repro/tune): (k+1) HBM ledger vs the live schedule, boot path
# (DESIGN.md §9)
# ---------------------------------------------------------------------------

def _scan_carry_ring_depths(jaxpr, width, out=None, seen=None):
    """Max leading dim per dtype over scan CARRY avals shaped (d, width)
    reachable from ``jaxpr`` (recursive) — the prefetch rings.

    The forward ring rides the scan carry as a stacked (k, P) buffer (P =
    padded per-layer flat size); xs/consts never have that shape, so the
    (d, width) carry filter isolates the rings exactly.
    """
    from repro.launch.jaxpr_analysis import _sub_jaxprs
    out = {} if out is None else out
    seen = set() if seen is None else seen
    if id(jaxpr) in seen:
        return out
    seen.add(id(jaxpr))
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            nc = eqn.params["num_consts"]
            ncar = eqn.params["num_carry"]
            for v in eqn.invars[nc:nc + ncar]:
                a = v.aval
                if getattr(a, "ndim", 0) == 2 and a.shape[1] == width:
                    key = str(a.dtype)
                    out[key] = max(out.get(key, 0), int(a.shape[0]))
        for sub, _ in _sub_jaxprs(eqn):
            _scan_carry_ring_depths(sub, width, out, seen)
    return out


def check_tune_ledger_live_buffers():
    """ISSUE 9 acceptance: the ledger's (k+1) ring charge must match the
    MEASURED live gathered-buffer count of the traced train step for
    prefetch 0..3 — counted from the scan carries, not assumed.

    measured = (bf16 (k, P) carry ring leading dim) + 1: k slots ride the
    carry and ``_ring_read`` materializes one more copy for the consuming
    layer; prefetch=0 has no ring carry but still computes with a single
    gathered buffer.  The backward pass carries a second, fp32 (k, P)
    ring of unreduced gradients — its depth must match ring_grads_bwd.
    """
    from repro.train import trainer as trainer_lib
    from repro.tune import train_ledger

    for pf in (0, 1, 2, 3):
        # 6 layers so effective_prefetch(n_periods) == pf for every depth
        mesh, arch, model, opt_cfg, ts, lm = _prefetch_env(pf, n_layers=6)
        k_eff = model.zcfg.effective_prefetch(model.n_periods)
        assert k_eff == pf, (k_eff, pf, model.n_periods)

        p_sh, o_sh = trainer_lib.state_shapes(model, opt_cfg)
        params = _abstract_tree(p_sh, mesh, ts.in_specs[0])
        opt = _abstract_tree(o_sh, mesh, ts.in_specs[1])
        bsh = {"tokens": jax.ShapeDtypeStruct((16, 64), jnp.int32),
               "targets": jax.ShapeDtypeStruct((16, 64), jnp.int32)}
        batch = _abstract_tree(bsh, mesh, ts.in_specs[2])
        cj = jax.make_jaxpr(ts.fn)(params, opt, batch)

        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        led = train_ledger(model, sizes)
        ring = dict(led.ring_buffers)
        assert ring["layers"] == pf + 1, (pf, ring)

        P = model.period_spec.padded_size
        depths = _scan_carry_ring_depths(cj.jaxpr, P)
        measured_w = depths.get("bfloat16", 0) + 1   # slots + read copy
        assert measured_w == ring["layers"], (pf, depths, ring)
        measured_g = depths.get("float32", 0)        # bwd unreduced grads
        assert measured_g == pf, (pf, depths)
        assert led.line("ring_grads_bwd") == pf * 2 * P, led.as_dict()


def check_tune_static_resolve_boot():
    """--tune=static boots through repro.tune end to end on a live mesh:
    build_everything carries the frozen ResolvedPolicy, the boot-path
    resolution equals a direct ``resolve`` call with the same inputs
    (deterministic by the committed-profile contract), the ledger's ring
    count honors the policy's own effective depth, and the tuned step
    trains to finite loss."""
    from repro.launch.train import build_everything
    from repro.tune import GB, resolve

    built = build_everything("gpt-350m", (4, 2), "zeropp", reduced=True,
                             batch=16, seq=64, lr=3e-3, tune="static",
                             hbm_gb=16.0)
    pol = built.policy
    assert pol is not None and pol.mode == "static", pol
    assert pol.ledger is not None and pol.ledger.fits, pol.ledger.as_dict()
    again = resolve(built.arch, ("data", "model"), "zeropp", mode="static",
                    mesh_sizes={"data": 4, "model": 2},
                    hbm_budget_bytes=16 * GB,
                    tokens_per_device=16 * 64 // 8)
    assert pol == again, (pol, again)
    k_eff = pol.zcfg.effective_prefetch(built.model.n_periods)
    assert dict(pol.ledger.ring_buffers)["layers"] == k_eff + 1
    mesh, arch, model, opt_cfg, ts, lm = built
    _, _, losses = _run_steps(mesh, arch, model, opt_cfg, ts, lm, 2, 16)
    assert np.isfinite(losses).all(), losses
