"""ZeroState subsystem tests (train/state.py).

Fast tests run in the main single-device pytest process: spec ownership,
checkpoint discovery hardening, the quantized payload math, and a full
world=1 save/restore roundtrip in both formats.  Multi-device elastic
behaviour (8 -> 4 -> 2, bit-exactness, quantization bounds, serving load)
runs in 8-device subprocesses via testing/subproc.py.
"""
import json
import os

import numpy as np
import pytest

from repro.testing.subproc import run_checks


# ---------------------------------------------------------------------------
# fast: ParamSpec.offsets memoization
# ---------------------------------------------------------------------------

def test_param_spec_offsets_memoized():
    from repro.core.partition import ParamSpec

    spec = ParamSpec((("a", (4, 8)), ("b", (16,)), ("c", ())), align=64)
    first = spec.offsets
    assert spec.offsets is first          # cached per instance
    assert first == {"a": (0, 32), "b": (32, 16), "c": (48, 1)}
    # a derived instance gets a fresh (and different) cache
    spec2 = spec.with_align(128)
    assert spec2.offsets is not first
    assert spec2.offsets == first
    # pack/unpack still roundtrip through the cached offsets
    import jax.numpy as jnp
    tensors = {"a": jnp.arange(32.0).reshape(4, 8),
               "b": jnp.arange(16.0), "c": jnp.float32(7)}
    flat = spec.pack(tensors)
    assert flat.shape == (spec.padded_size,)
    out = spec.unpack(flat)
    for k in tensors:
        np.testing.assert_array_equal(np.asarray(out[k]),
                                      np.asarray(tensors[k]))


# ---------------------------------------------------------------------------
# fast: checkpoint discovery hardening
# ---------------------------------------------------------------------------

def test_latest_skips_foreign_files(tmp_path):
    from repro.train import checkpoint as ckpt

    d = tmp_path / "ckpts"
    d.mkdir()
    # foreign / malformed names that used to crash the int() sort
    for name in ("ckpt_final.npz", "ckpt_.npz", "ckpt_12abc.npz",
                 "notes.txt", "ckpt_5.tmp"):
        (d / name).write_bytes(b"x")
    (d / "ckpt_3.npz").write_bytes(b"x")
    (d / "ckpt_10.npz").write_bytes(b"x")
    assert ckpt.latest(str(d)) == str(d / "ckpt_10.npz")

    # a per-shard dir wins if newer; one without a manifest is incomplete
    (d / "ckpt_11").mkdir()
    assert ckpt.latest(str(d)) == str(d / "ckpt_10.npz")
    (d / "ckpt_11" / "manifest.json").write_text("{}")
    assert ckpt.latest(str(d)) == str(d / "ckpt_11")

    assert ckpt.latest(str(tmp_path / "missing")) is None


def test_latest_ignores_staging_and_quarantine(tmp_path):
    """A crash can leave a fully-populated staging dir — shards AND
    manifest, killed between the manifest fsync and the atomic rename.
    ``latest()`` must never select it (nor a quarantined checkpoint),
    even when its step number is the highest in the directory."""
    from repro.train import checkpoint as ckpt

    d = tmp_path / "c"
    d.mkdir()
    good = d / "ckpt_4"
    good.mkdir()
    (good / "manifest.json").write_text("{}")
    staging = d / "ckpt_9.tmp"            # crash-left, manifest included
    staging.mkdir()
    (staging / "shard_00000.npz").write_bytes(b"x")
    (staging / "manifest.json").write_text("{}")
    quarantined = d / "ckpt_12.corrupt"
    quarantined.mkdir()
    (quarantined / "manifest.json").write_text("{}")
    assert ckpt.latest(str(d)) == str(good)


# ---------------------------------------------------------------------------
# fast: quantized payload math
# ---------------------------------------------------------------------------

def test_quantize_shard_roundtrip_bound():
    from repro.train.state import dequantize_shard, quantize_shard

    rng = np.random.default_rng(0)
    block = 64
    x = (rng.normal(size=(3, 8 * block)) * 0.05).astype(np.float32)
    x[0, :block] *= 100.0          # outlier block must not poison others
    q, s = quantize_shard(x, block)
    assert q.dtype == np.int8 and s.dtype == np.float16
    assert q.shape == x.shape and s.shape == (3, 8)
    back = dequantize_shard(q, s, block)
    xb = x.reshape(3, 8, block)
    bound = np.abs(xb).max(axis=-1, keepdims=True) / 127.0 * 0.6 + 1e-8
    assert (np.abs(back.reshape(xb.shape) - xb) <= bound).all()
    # zero blocks (checkpoint padding) roundtrip to exact zeros
    q0, s0 = quantize_shard(np.zeros((2 * block,), np.float32), block)
    assert not q0.any() and not s0.astype(np.float32).any()
    assert not dequantize_shard(q0, s0, block).any()


def test_quantize_shard_sqrt_never_underestimates():
    """The second-moment encoder's core invariant: v_hat >= v for every
    element, at EVERY magnitude — including blocks whose fp32 scale is
    below the fp16 normal/subnormal range (a plain fp16 cast flushes the
    scale to zero and restores v_hat = 0, detonating Adam's division)."""
    from repro.train.state import dequantize_shard_sqrt, quantize_shard_sqrt

    rng = np.random.default_rng(1)
    block = 64
    for mag in (1.0, 1e-4, 1e-8, 1e-12, 1e-16):
        v = (rng.uniform(0, 1, size=(4 * block,)) * mag).astype(np.float32)
        q, s = quantize_shard_sqrt(v, block)
        assert q.dtype == np.uint8 and s.dtype == np.float16
        back = dequantize_shard_sqrt(q, s, block)
        assert (back >= v).all(), (mag, float((v - back).max()))
        nonzero = v > 0
        assert back[nonzero].min() > 0, mag   # no flush-to-zero
    # symmetric encoder: tiny blocks must not flush to zero scale either
    from repro.train.state import dequantize_shard, quantize_shard
    x = (rng.normal(size=(2 * block,)) * 1e-7).astype(np.float32)
    q, s = quantize_shard(x, block)
    assert s.astype(np.float32).min() > 0
    back = dequantize_shard(q, s, block)
    assert np.isfinite(back).all()
    # stored-scale bound: |x - x_hat| <= s/2 everywhere
    s32 = np.repeat(s.astype(np.float32), block)
    assert (np.abs(back - x) <= s32 / 2 + 1e-12).all()


# ---------------------------------------------------------------------------
# fast: world=1 end-to-end roundtrip (both formats + legacy fallback)
# ---------------------------------------------------------------------------

def _tiny_state():
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_mesh
    from repro.models.model import Model
    from repro.optim.adamw import AdamWConfig
    from repro.train.policy import make_policy
    from repro.train.state import ZeroState

    mesh = make_mesh((1, 1), ("data", "model"))
    arch = get_config("gpt-350m").reduced()
    pol = make_policy(arch, tuple(mesh.axis_names))
    model = Model(arch, pol.zcfg, world=1)
    opt_cfg = AdamWConfig()
    st = ZeroState(model, mesh, opt_cfg).init(jax.random.PRNGKey(0))
    return mesh, model, opt_cfg, st


def test_zero_state_roundtrip_single_device(tmp_path):
    import jax
    from repro.train.state import MANIFEST, ZeroState, read_manifest

    mesh, model, opt_cfg, st = _tiny_state()
    p_host = jax.device_get(st.params)

    path = st.save(str(tmp_path), 7, meta={"arch": "tiny"})
    assert os.path.basename(path) == "ckpt_7"
    man = read_manifest(path)
    assert man["world"] == 1 and man["step"] == 7
    assert man["meta"]["arch"] == "tiny"
    assert set(man["param_layout"]) >= {"blocks", "head", "unemb"}

    st2 = ZeroState.restore(model, mesh, opt_cfg, str(tmp_path))
    assert st2 is not None and st2.step == 7
    for k, v in st2.params.items():
        np.testing.assert_array_equal(np.asarray(jax.device_get(v)),
                                      np.asarray(p_host[k]))

    # INT8 format: bounded error, strictly smaller files
    path8 = st.save(str(tmp_path / "q"), 7, fmt="int8")
    st3 = ZeroState.restore(model, mesh, opt_cfg, str(tmp_path / "q"))
    for k, v in st3.params.items():
        want = np.asarray(p_host[k])
        err = np.abs(np.asarray(jax.device_get(v)) - want).max()
        assert err <= np.abs(want).max() / 127.0 * 0.6 + 1e-8, (k, err)

    def size(p):
        return sum(os.path.getsize(os.path.join(p, f))
                   for f in os.listdir(p) if f != MANIFEST)
    assert size(path8) < 0.35 * size(path)


def test_legacy_npz_compat(tmp_path):
    """checkpoint.py's legacy single-file API still works and ZeroState
    restores from it transparently."""
    import jax
    from repro.train import checkpoint as ckpt
    from repro.train.state import ZeroState

    mesh, model, opt_cfg, st = _tiny_state()
    p_host = jax.device_get(st.params)
    path = str(tmp_path / "ckpt_4.npz")
    ckpt.save(path, 4, {"params": jax.device_get(st.params),
                        "opt": jax.device_get(st.opt)}, {"world": 1})
    step, tree, meta = ckpt.load(path)
    assert step == 4 and meta["world"] == 1
    st2 = ZeroState.restore(model, mesh, opt_cfg, str(tmp_path))
    assert st2 is not None and st2.step == 4
    for k, v in st2.params.items():
        np.testing.assert_array_equal(np.asarray(jax.device_get(v)),
                                      np.asarray(p_host[k]))


def test_restore_corrupt_checkpoint_fallback(tmp_path):
    """Bit-rot in the newest checkpoint: the checksum catches it with a
    clear error, and ``restore_resilient`` quarantines the damaged dir
    and falls back to the previous intact one."""
    import jax
    from repro.testing.faults import corrupt_shard
    from repro.train.state import (CheckpointCorruptError, ZeroState,
                                   load_global)

    mesh, model, opt_cfg, st = _tiny_state()
    p_host = jax.device_get(st.params)
    st.save(str(tmp_path), 1)
    st.save(str(tmp_path), 2)
    corrupt_shard(str(tmp_path / "ckpt_2"))
    with pytest.raises(CheckpointCorruptError, match="checksum mismatch"):
        load_global(str(tmp_path / "ckpt_2"))
    st2 = ZeroState.restore_resilient(model, mesh, opt_cfg, str(tmp_path))
    assert st2 is not None and st2.step == 1
    assert (tmp_path / "ckpt_2.corrupt").is_dir()   # quarantined aside
    for k, v in st2.params.items():
        np.testing.assert_array_equal(np.asarray(jax.device_get(v)),
                                      np.asarray(p_host[k]))


def test_restore_truncated_shard_exhausts_to_none(tmp_path):
    """A truncated shard (interrupted write) raises a clear corrupt error
    rather than a numpy stack trace; with EVERY checkpoint damaged,
    ``restore_resilient`` returns None (fresh start) instead of raising."""
    from repro.testing.faults import truncate_shard
    from repro.train.state import (CheckpointCorruptError, ZeroState,
                                   load_global)

    mesh, model, opt_cfg, st = _tiny_state()
    st.save(str(tmp_path), 3)
    truncate_shard(str(tmp_path / "ckpt_3"))
    with pytest.raises(CheckpointCorruptError):
        load_global(str(tmp_path / "ckpt_3"))
    assert ZeroState.restore_resilient(model, mesh, opt_cfg,
                                       str(tmp_path)) is None
    assert (tmp_path / "ckpt_3.corrupt").is_dir()
    # plain restore on the now-empty dir is also a clean None
    assert ZeroState.restore(model, mesh, opt_cfg, str(tmp_path)) is None


def test_serve_imports_nothing_from_trainer():
    """The layering fix: serving must not depend on the training stack."""
    import inspect
    from repro.train import serve

    src = inspect.getsource(serve)
    assert "from repro.train.trainer" not in src
    assert "import trainer" not in src


# ---------------------------------------------------------------------------
# slow: multi-device elastic / quantized / serving behaviour
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_state_elastic_restore_multidevice():
    run_checks(["check_state_elastic_restore"], n_devices=8, timeout=1200)


@pytest.mark.slow
def test_state_quantized_and_serving():
    run_checks(["check_state_quantized_roundtrip",
                "check_state_serving_load"], n_devices=8, timeout=1200)
