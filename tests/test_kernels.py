"""Pallas kernel validation: interpret-mode kernels vs pure-jnp oracles.

Per the spec, each kernel is swept over shapes/dtypes and checked with
assert_allclose against ref.py.  Round-to-nearest ties are the only
permitted divergence source (jnp.round is ties-to-even in both paths, so in
practice the match is exact).
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # only the @given property tests need hypothesis
    from repro.testing.hypothesis_stub import given, settings, st

from repro.core.quant import QuantConfig, dequantize_blockwise
from repro.kernels import ref
from repro.kernels.quant_block import (
    dequantize_pallas,
    quantize_pallas,
    quantize_reordered_pallas,
    slab,
)
from repro.kernels.fused_dequant_reduce_quant import (
    dequant_reduce_pallas,
    dequant_reduce_quant_pallas,
)

INTERP = dict(interpret=True)


def _jit(fn, **kw):
    """Jit with static kwargs.  Kernel and ref are BOTH compared under jit:
    eager XLA and jitted XLA may differ by 1 ulp in division fusion, which
    flips round-to-nearest ties; inside jit the two paths are bit-equal."""
    import functools
    return jax.jit(functools.partial(fn, **kw))


def _rand(shape, dtype, seed=0, scale=1.0):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(shape) * scale).astype(np.float32)
    return jnp.asarray(x, dtype=dtype)


SWEEP = [
    # (rows, cols, block, bits, dtype)
    (1, 256, 256, 8, jnp.float32),
    (8, 512, 128, 8, jnp.float32),
    (16, 1024, 256, 4, jnp.float32),
    (3, 384, 128, 4, jnp.bfloat16),
    (7, 768, 256, 8, jnp.bfloat16),
    (32, 2048, 512, 4, jnp.float32),
    (2, 8192, 1024, 8, jnp.bfloat16),
]


@pytest.mark.parametrize("rows,cols,block,bits,dtype", SWEEP)
def test_quantize_matches_ref(rows, cols, block, bits, dtype):
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((rows, cols), dtype, seed=rows * cols)
    p_k, s_k = _jit(quantize_pallas, cfg=cfg, **INTERP)(x)
    p_r, s_r = _jit(ref.quantize_ref, cfg=cfg)(x)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("rows,cols,block,bits,dtype", SWEEP)
def test_dequantize_matches_ref(rows, cols, block, bits, dtype):
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((rows, cols), dtype, seed=rows + cols)
    p, s = ref.quantize_ref(x, cfg)
    got = _jit(dequantize_pallas, cfg=cfg, out_dtype=jnp.float32, **INTERP)(p, s)
    want = _jit(ref.dequantize_ref, cfg=cfg, out_dtype=jnp.float32)(p, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@pytest.mark.parametrize("rows,cols,block,bits,dtype", SWEEP[:4])
def test_quant_roundtrip_error_bound(rows, cols, block, bits, dtype):
    """|dequant(quant(x)) - x| <= scale/2 per block (symmetric quant)."""
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((rows, cols), dtype, seed=5)
    p, s = _jit(quantize_pallas, cfg=cfg, **INTERP)(x)
    rt = _jit(dequantize_pallas, cfg=cfg, out_dtype=jnp.float32, **INTERP)(p, s)
    err = np.abs(np.asarray(rt) - np.asarray(x, dtype=np.float32))
    bound = np.repeat(np.asarray(s), block, axis=-1) / 2 + 1e-7
    assert (err <= bound * 1.001).all()


@pytest.mark.parametrize("Y,X,L,block,bits", [
    (2, 2, 256, 128, 4),
    (4, 2, 512, 256, 4),
    (3, 5, 1024, 256, 8),
    (16, 2, 256, 128, 4),
])
def test_quantize_reordered_matches_ref(Y, X, L, block, bits):
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((Y, X, L), jnp.float32, seed=Y * X)
    p_k, s_k = _jit(quantize_reordered_pallas, cfg=cfg, **INTERP)(x)
    p_r, s_r = _jit(ref.quantize_reordered_ref, cfg=cfg)(x)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("N,C,block,bits", [
    (2, 256, 128, 4),
    (8, 512, 256, 4),
    (16, 1024, 256, 8),
    (4, 4096, 512, 4),
])
def test_dequant_reduce_matches_ref(N, C, block, bits):
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((N, C), jnp.float32, seed=N * C)
    p, s = ref.quantize_ref(x, cfg)
    got = _jit(dequant_reduce_pallas, cfg=cfg, **INTERP)(p, s)
    want = _jit(ref.dequant_reduce_ref, cfg=cfg)(p, s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("N,C,block,bits_in,bits_out", [
    (2, 256, 128, 4, 4),
    (8, 512, 256, 4, 4),
    (4, 1024, 256, 8, 4),
    (16, 512, 128, 4, 8),
])
def test_dequant_reduce_quant_matches_ref(N, C, block, bits_in, bits_out):
    cfg_in = QuantConfig(bits=bits_in, block_size=block)
    cfg_out = QuantConfig(bits=bits_out, block_size=block)
    x = _rand((N, C), jnp.float32, seed=N + C)
    p, s = ref.quantize_ref(x, cfg_in)
    p_k, s_k = _jit(dequant_reduce_quant_pallas, cfg_in=cfg_in, cfg_out=cfg_out, **INTERP)(p, s)
    p_r, s_r = _jit(ref.dequant_reduce_quant_ref, cfg_in=cfg_in, cfg_out=cfg_out)(p, s)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)


# ---------------------------------------------------------------------------
# property-based sweeps (hypothesis)
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(
    rows=st.integers(1, 12),
    nblocks=st.integers(1, 6),
    block_pow=st.integers(5, 9),        # block 32..512
    bits=st.sampled_from([4, 8]),
    seed=st.integers(0, 2 ** 16),
)
def test_prop_kernel_equals_ref(rows, nblocks, block_pow, bits, seed):
    block = 2 ** block_pow
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((rows, nblocks * block), jnp.float32, seed=seed, scale=3.0)
    p_k, s_k = _jit(quantize_pallas, cfg=cfg, **INTERP)(x)
    p_r, s_r = _jit(ref.quantize_ref, cfg=cfg)(x)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    got = _jit(dequantize_pallas, cfg=cfg, out_dtype=jnp.float32, **INTERP)(p_k, s_k)
    want = _jit(ref.dequantize_ref, cfg=cfg, out_dtype=jnp.float32)(p_r, s_r)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(
    n=st.integers(1, 16),
    nblocks=st.integers(1, 4),
    seed=st.integers(0, 2 ** 16),
)
def test_prop_fused_reduce_is_fp32_exact(n, nblocks, seed):
    """The fused kernel's reduction must be bit-identical to an fp32 sum of
    the individually dequantized contributions (the paper's accuracy
    argument hinges on full-precision reduction)."""
    cfg = QuantConfig(bits=4, block_size=128)
    x = _rand((n, nblocks * 128), jnp.float32, seed=seed)
    p, s = ref.quantize_ref(x, cfg)
    got = _jit(dequant_reduce_pallas, cfg=cfg, **INTERP)(p, s)
    want = _jit(lambda p, s: jnp.sum(dequantize_blockwise(p, s, cfg, jnp.float32), axis=0))(p, s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pick_tiles_divides():
    """The slab view tiles by the TPU rule: width W divides the buffer and
    holds whole quant blocks (and whole 256-lane int4 pack groups where the
    length allows); the row tile is a multiple of 32 or every row."""
    for n, block, depth in [(256, 256, 1), (13 * 512, 128, 1),
                            (64 * 8192, 1024, 1), (5 * 640, 64, 1),
                            (4194816, 256, 1), (16779264, 256, 1),
                            (8389632, 256, 2), (3 * 384, 128, 16)]:
        rows, w, rt = slab(n, block, depth)
        assert rows * w == n and w % block == 0
        if n % 256 == 0:
            assert w % 256 == 0
        assert rt == rows or (rt % 32 == 0 and rt < rows)


def test_ops_dispatch_ref_equals_interpret():
    """ops.py must produce identical results whichever path it picks."""
    from repro.kernels import ops
    cfg = QuantConfig(bits=4, block_size=128)
    x = _rand((4, 512), jnp.float32, seed=11)
    old = ops.FORCE
    try:
        ops.FORCE = "ref"
        p1, s1 = ops.quantize_blockwise(x, cfg)
        ops.FORCE = "interpret"
        p2, s2 = ops.quantize_blockwise(x, cfg)
    finally:
        ops.FORCE = old
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)


# ---------------------------------------------------------------------------
# fused INT8 dequant-GEMM (kernels/dequant_matmul.py)
# ---------------------------------------------------------------------------

from repro.kernels.dequant_matmul import dequant_matmul_pallas

GEMM_SWEEP = [
    # (T, N, K, n_scale_groups, x_dtype) — single and several token/row
    # tiles, per-row scale groups and the one-scale-per-row broadcast layout
    (8, 64, 256, 2, jnp.float32),
    (4, 128, 64, 1, jnp.bfloat16),
    (16, 96, 384, 3, jnp.float32),
    (3, 40, 512, 4, jnp.bfloat16),
    (16, 128, 2048, 8, jnp.float32),   # wide contraction
    (5, 64, 1536, 12, jnp.float32),    # odd row count
    (300, 640, 2048, 8, jnp.bfloat16),  # partial last token and row tiles
]


@pytest.mark.parametrize("T,N,K,nb,xdtype", GEMM_SWEEP)
def test_dequant_matmul_matches_ref(T, N, K, nb, xdtype):
    """Kernel vs staged oracle.  Tolerance is fp32 accumulation ORDER only;
    the elementwise dequant math is identical."""
    cfg = QuantConfig(bits=8, block_size=K // nb)
    x = _rand((T, K), xdtype, seed=T * K)
    w = _rand((N, K), jnp.float32, seed=N + K)
    p, s = ref.quantize_ref(w, cfg)
    got = _jit(dequant_matmul_pallas, **INTERP)(x, p, s)
    want = _jit(ref.dequant_matmul_ref)(x, p, s)
    scale = np.abs(np.asarray(want)).max() + 1e-9
    np.testing.assert_allclose(np.asarray(got) / scale,
                               np.asarray(want) / scale, atol=2e-6)


def test_dequant_matmul_ref_is_staged_math():
    """The oracle == dequantize_blockwise + einsum, bit for bit: the `xla`
    dispatch path must be indistinguishable from the pre-fusion staged
    serving head."""
    cfg = QuantConfig(bits=8, block_size=128)
    x = _rand((6, 512), jnp.float32, seed=3)
    w = _rand((32, 512), jnp.float32, seed=4)
    p, s = ref.quantize_ref(w, cfg)

    def staged(x, p, s):
        wd = dequantize_blockwise(p, s, cfg, jnp.bfloat16)
        return jnp.einsum("tk,nk->tn", x, wd,
                          preferred_element_type=jnp.float32)

    got = _jit(ref.dequant_matmul_ref)(x, p, s)
    want = _jit(staged)(x, p, s)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ops_dequant_matmul_dispatch():
    from repro.kernels import ops
    cfg = QuantConfig(bits=8, block_size=256)
    x = _rand((4, 1024), jnp.float32, seed=9)
    w = _rand((16, 1024), jnp.float32, seed=10)
    p, s = ref.quantize_ref(w, cfg)
    with ops.use_backend("xla"):
        a = ops.dequant_matmul(x, p, s)
    with ops.use_backend("interpret"):
        b = ops.dequant_matmul(x, p, s)
    scale = np.abs(np.asarray(a)).max() + 1e-9
    np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale,
                               atol=2e-6)


# ---------------------------------------------------------------------------
# backend seam (kernels/platform.py + ops.py resolution)
# ---------------------------------------------------------------------------

def test_platform_resolution_order(monkeypatch):
    from repro.kernels import platform
    monkeypatch.delenv(platform.ENV_VAR, raising=False)
    assert platform.resolve() == "xla"                 # CPU default
    monkeypatch.setenv(platform.ENV_VAR, "interpret")
    assert platform.resolve() == "interpret"           # env beats default
    assert platform.resolve("xla") == "xla"            # force beats env
    assert platform.resolve("ref") == "xla"            # alias
    with pytest.raises(ValueError):
        platform.resolve("cuda")


def test_platform_pallas_off_tpu_raises(monkeypatch):
    """'pallas' off-TPU is a hard error at every entry point — forced,
    via env, and at ops.set_backend configuration time."""
    from repro.kernels import ops, platform
    assert not platform.is_tpu()
    with pytest.raises(RuntimeError, match="requires a TPU"):
        platform.resolve("pallas")
    monkeypatch.setenv(platform.ENV_VAR, "pallas")
    with pytest.raises(RuntimeError, match="requires a TPU"):
        platform.resolve()
    monkeypatch.delenv(platform.ENV_VAR)
    with pytest.raises(RuntimeError, match="requires a TPU"):
        ops.set_backend("pallas")
    assert ops.FORCE is None                           # rejected, not stored


def test_use_backend_scoping():
    from repro.kernels import ops
    assert ops.FORCE is None
    with ops.use_backend("interpret"):
        assert ops.backend() == "interpret"
        with ops.use_backend("ref"):
            assert ops.backend() == "xla"
        assert ops.backend() == "interpret"
    assert ops.FORCE is None


def test_flash_ops_shares_platform_probe(monkeypatch):
    """flash_ops and ops must answer 'interpret?' through the SAME probe:
    env settings reach both, and a bad env fails loudly in both."""
    from repro.kernels import flash_ops, platform
    monkeypatch.delenv(platform.ENV_VAR, raising=False)
    assert flash_ops._interpret() is True              # CPU: never compile
    monkeypatch.setenv(platform.ENV_VAR, "pallas")
    with pytest.raises(RuntimeError, match="requires a TPU"):
        flash_ops._interpret()


# ---------------------------------------------------------------------------
# stochastic rounding through the dispatch seam
# ---------------------------------------------------------------------------

def test_stochastic_dispatch_determinism():
    """Stochastic rounding now THREADS the PRNG key through the kernel
    path: the uniform field is drawn outside the pallas_call (exactly as
    the reference draws it) and compared inside the kernel, so a fixed
    key gives bit-identical payloads on every backend — with the
    interpret backend actually running the kernel, not the xla ref."""
    from repro.kernels import ops
    from repro.obs.metrics import get_registry
    cfg = QuantConfig(bits=4, block_size=128, stochastic=True)
    x = _rand((4, 512), jnp.float32, seed=21)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    outs = {}
    for be in ("xla", "interpret"):
        with ops.use_backend(be):
            before = get_registry().counter(
                f"kernels.dispatch.quantize_blockwise.{be}").value
            # jit both backends: eager vs traced XLA differ by 1 ulp in
            # the scale division (see _jit's note), which is not what
            # this test is about
            outs[be] = jax.jit(
                lambda a, k: ops.quantize_blockwise(a, cfg, k))(x, k1)
            after = get_registry().counter(
                f"kernels.dispatch.quantize_blockwise.{be}").value
            assert after == before + 1, (be, before, after)
    np.testing.assert_array_equal(np.asarray(outs["xla"][0]),
                                  np.asarray(outs["interpret"][0]))
    np.testing.assert_array_equal(np.asarray(outs["xla"][1]),
                                  np.asarray(outs["interpret"][1]))
    with ops.use_backend("interpret"):
        again = jax.jit(
            lambda a, k: ops.quantize_blockwise(a, cfg, k))(x, k1)
        other = jax.jit(
            lambda a, k: ops.quantize_blockwise(a, cfg, k))(x, k2)
    np.testing.assert_array_equal(np.asarray(outs["interpret"][0]),
                                  np.asarray(again[0]))
    assert not np.array_equal(np.asarray(again[0]), np.asarray(other[0]))


@pytest.mark.parametrize("bits_in,bits_out", [(4, 4), (4, 8), (8, 4)])
def test_stochastic_fused_dequant_reduce_quant(bits_in, bits_out):
    """The fused qgZ intra-hop op now threads stochastic rounding through
    the kernel path too: the uniform field is drawn on the reference's
    flat (C,) segmentation and requantization happens in-kernel, so a
    fixed key gives bit-identical payloads AND scales across backends —
    this closed the last stochastic xla fallback."""
    from repro.kernels import ops
    from repro.obs.metrics import get_registry
    cfg_in = QuantConfig(bits=bits_in, block_size=64)
    cfg_out = QuantConfig(bits=bits_out, block_size=64, stochastic=True)
    x = _rand((4, 512), jnp.float32, seed=11)
    p, s = ref.quantize_ref(x, cfg_in)
    k1, k2 = jax.random.split(jax.random.PRNGKey(7))
    outs = {}
    for be in ("xla", "interpret"):
        with ops.use_backend(be):
            before = get_registry().counter(
                f"kernels.dispatch.dequant_reduce_quant.{be}").value
            outs[be] = jax.jit(lambda pp, ss, k: ops.dequant_reduce_quant(
                pp, ss, cfg_in, cfg_out, k))(p, s, k1)
            after = get_registry().counter(
                f"kernels.dispatch.dequant_reduce_quant.{be}").value
            # the interpret dispatch must NOT fall back to xla any more
            assert after == before + 1, (be, before, after)
    np.testing.assert_array_equal(np.asarray(outs["xla"][0]),
                                  np.asarray(outs["interpret"][0]))
    np.testing.assert_array_equal(np.asarray(outs["xla"][1]),
                                  np.asarray(outs["interpret"][1]))
    with ops.use_backend("interpret"):
        again = jax.jit(lambda pp, ss, k: ops.dequant_reduce_quant(
            pp, ss, cfg_in, cfg_out, k))(p, s, k1)
        other = jax.jit(lambda pp, ss, k: ops.dequant_reduce_quant(
            pp, ss, cfg_in, cfg_out, k))(p, s, k2)
    np.testing.assert_array_equal(np.asarray(outs["interpret"][0]),
                                  np.asarray(again[0]))
    assert not np.array_equal(np.asarray(again[0]), np.asarray(other[0]))


# ---------------------------------------------------------------------------
# multi-segment shapes + tile-boundary-crossing blocks
# ---------------------------------------------------------------------------

def test_multiseg_ref_parity(monkeypatch):
    """Force the reference onto its lax.map segmentation path and check
    the (unsegmented, tile-streaming) kernel still matches bit-for-bit —
    segmentation is a memory layout choice, never a numerics one."""
    from repro.core import quant as quant_mod
    monkeypatch.setattr(quant_mod, "_SEG_ELEMS", 1 << 10)
    cfg = QuantConfig(bits=8, block_size=128)
    x = _rand((4, 2048), jnp.float32, seed=13)         # 8192 elems > 1024
    p_r, s_r = _jit(ref.quantize_ref, cfg=cfg)(x)
    p_k, s_k = _jit(quantize_pallas, cfg=cfg, **INTERP)(x)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("rows,cols,block,bits", [
    (2, 16384, 8192, 8),    # block > the 4096-col VMEM tile cap
    (5, 8192, 4096, 4),     # block == cap, odd rows, int4 packing
])
def test_block_crossing_tile_cap(rows, cols, block, bits):
    cfg = QuantConfig(bits=bits, block_size=block)
    x = _rand((rows, cols), jnp.float32, seed=rows)
    p_k, s_k = _jit(quantize_pallas, cfg=cfg, **INTERP)(x)
    p_r, s_r = _jit(ref.quantize_ref, cfg=cfg)(x)
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    got = _jit(dequantize_pallas, cfg=cfg, out_dtype=jnp.bfloat16, **INTERP)(p_k, s_k)
    want = _jit(ref.dequantize_ref, cfg=cfg, out_dtype=jnp.bfloat16)(p_r, s_r)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# schedule/serve composition with the kernel backend on (8-dev subprocess)
# ---------------------------------------------------------------------------

from repro.testing.subproc import run_checks

_INTERP_ENV = {"REPRO_KERNEL_BACKEND": "interpret"}


def test_depth_sweep_kernel_backend_8dev():
    """The dense depth sweep stays bit-exact with the kernel backend
    forced to interpret (same assertions as check_prefetch_depth_sweep;
    `make kernel-smoke` additionally runs that check unchanged under
    $REPRO_KERNEL_BACKEND=interpret)."""
    run_checks(["check_kernel_backend_depth_sweep"], n_devices=8,
               timeout=2400)


def test_serve_engine_kernel_backend_8dev():
    """Acceptance: the serve-engine bit-identity check passes unchanged
    with the kernel backend forced to interpret (fused INT8 head active)."""
    run_checks(["check_serve_engine_continuous_batching"], n_devices=8,
               timeout=1800, extra_env=_INTERP_ENV)


def test_train_bitexact_across_backends_8dev():
    run_checks(["check_kernel_backend_train_bitexact"], n_devices=8,
               timeout=1800)


def test_qwz_gemm_head_matches_staged_8dev():
    run_checks(["check_qwz_gemm_head_matches_staged"], n_devices=8,
               timeout=1800)


def test_kernels_first_import_order():
    """Regression: importing repro.kernels.ops BEFORE repro.core (the
    --kernel-backend CLI path does exactly this) must not trip the
    kernels<->core import cycle.  core.collectives binds the ops module,
    not its names, so resolution happens at call time."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    for first in ("repro.kernels.ops", "repro.kernels.ref"):
        r = subprocess.run(
            [sys.executable, "-c",
             f"import {first}; import repro.core.collectives as c; "
             "import repro.kernels.ops as o; "
             "assert callable(c.quantize_blockwise); print(o.backend())"],
            env=env, capture_output=True, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert r.returncode == 0, f"{first} first: {r.stderr}"
