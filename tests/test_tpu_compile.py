"""Compile the hot-path Pallas kernels for a described TPU v5e chip.

Interpret mode (tests/test_kernels.py) checks the kernels' numbers; it
cannot see the TPU's tiling rule or its VMEM limit.  Here each kernel of
the ZeRO++ train step (qwZ quantize/dequantize, the qgZ reorder-quantize
and both fused reduce hops) and the INT8 serving-head GEMM is lowered and
compiled by the TPU compiler for one chip of a ``v5e:2x2`` topology, at the
gpt-350m buffer widths a world of 1 and of 4 devices sees.  Nothing runs:
a described chip holds no arrays.

The topology is described inside a module fixture (only one process may
load the TPU library at a time, so never at import), and the persistent
compilation cache is off around these compiles: entries written for a
described chip cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.quant import QuantConfig
from repro.kernels.dequant_matmul import dequant_matmul_pallas
from repro.kernels.fused_dequant_reduce_quant import (
    dequant_reduce_pallas,
    dequant_reduce_quant_pallas,
)
from repro.kernels.quant_block import (
    dequantize_pallas,
    quantize_pallas,
    quantize_reordered_pallas,
)

QWZ = QuantConfig(bits=8, block_size=256)
QGZ = QuantConfig(bits=4, block_size=256)

# gpt-350m flat buffers (Model.param_shapes): per-layer blocks, the
# embedding, one of the 4 unembedding chunks
BUFFERS = {"blocks": 16779264, "embed": 51511296, "unemb": 12877824}
# world -> (intra X = 'model' size, inter Y = 'data' size) of its mesh
MESHES = {1: (1, 1), 4: (2, 2)}
CASES = [(w, b) for w in MESHES for b in BUFFERS]
IDS = [f"w{w}-{b}" for w, b in CASES]


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2 host, with the compile cache off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _assert_kernel(chip, fn, *flat):
    """Compile ``fn`` between flat 1-D operands of the given (length,
    dtype) and flat outputs, as the train step holds its buffers, and check
    the kernel is in it."""
    args = [jax.ShapeDtypeStruct((n,), d, sharding=chip) for n, d in flat]
    flat_fn = jax.jit(lambda *a: [o.reshape(-1) for o in
                                  jax.tree.leaves(fn(*a))])
    text = flat_fn.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("world,buf", CASES, ids=IDS)
def test_qwz_quantize_compiles(chip, world, buf):
    shard = BUFFERS[buf] // world
    _assert_kernel(chip, lambda x: quantize_pallas(x[None], QWZ),
                   (shard, jnp.float32))


@pytest.mark.parametrize("buf", list(BUFFERS))
def test_qwz_dequantize_compiles(chip, buf):
    # the gathered buffer is the whole one, whatever the world
    n = BUFFERS[buf]
    _assert_kernel(
        chip,
        lambda p, s: dequantize_pallas(p[None], s[None], QWZ, jnp.bfloat16),
        (n, jnp.int8), (n // 256, jnp.float32))


@pytest.mark.parametrize("world,buf", CASES, ids=IDS)
def test_qgz_reorder_quantize_compiles(chip, world, buf):
    X, Y = MESHES[world]
    n = BUFFERS[buf]             # the full local gradient
    _assert_kernel(
        chip, lambda g: quantize_reordered_pallas(g.reshape(Y, X, -1), QGZ),
        (n, jnp.float32))


@pytest.mark.parametrize("world,buf", CASES, ids=IDS)
def test_qgz_intra_hop_reduce_quant_compiles(chip, world, buf):
    X, Y = MESHES[world]
    n = BUFFERS[buf]             # X contributions of n / X each
    _assert_kernel(
        chip, lambda p, s: dequant_reduce_quant_pallas(
            p.reshape(X, -1), s.reshape(X, -1), QGZ, QGZ),
        (n // 2, jnp.int8), (n // 256, jnp.float32))


@pytest.mark.parametrize("world,buf", CASES, ids=IDS)
def test_qgz_inter_hop_reduce_compiles(chip, world, buf):
    X, Y = MESHES[world]
    n = BUFFERS[buf] // X        # Y contributions of n / world each
    _assert_kernel(
        chip, lambda p, s: dequant_reduce_pallas(
            p.reshape(Y, -1), s.reshape(Y, -1), QGZ),
        (n // 2, jnp.int8), (n // 256, jnp.float32))


@pytest.mark.parametrize("tokens", [8, 1024])
def test_dequant_matmul_head_compiles(chip, tokens):
    V, d = 50304, 1024
    _assert_kernel(
        chip, lambda x, p, s: dequant_matmul_pallas(
            x.reshape(tokens, d), p.reshape(V, d), s.reshape(V, -1)),
        (tokens * d, jnp.bfloat16), (V * d, jnp.int8),
        (V * d // 256, jnp.float32))
