"""Prefetched-schedule tests (core/schedule.py + launch overlap analysis).

Multi-device assertions live in repro.testing.checks and run in
subprocesses (see testing/subproc.py); the analyze_overlap unit tests run
in-process on synthetic HLO text.
"""
import pytest

from repro.launch.hlo_analysis import analyze_overlap
from repro.testing.subproc import run_checks


@pytest.mark.slow
def test_prefetch_loss_equality():
    """prefetch=1 == prefetch=0 losses, bit-exact, on the smoke model."""
    run_checks(["check_prefetch_matches_sync"], n_devices=8, timeout=900)


@pytest.mark.slow
def test_prefetch_jaxpr_ordering():
    """Layer i+1's gather is issued before layer i's matmuls and is not
    consumed by them (prefetch=1); prefetch=0 is synchronous."""
    run_checks(["check_prefetch_jaxpr_ordering"], n_devices=8, timeout=900)


@pytest.mark.slow
def test_prefetch_overlap_hlo():
    """Compiled HLO: overlap_fraction > 0 with prefetch=1, == 0 without."""
    run_checks(["check_prefetch_overlap_fraction"], n_devices=8, timeout=900)


@pytest.mark.slow
def test_qgz_1hop_validates_input():
    run_checks(["check_qgz_1hop_rejects_misaligned"], n_devices=8,
               timeout=900)


# ---------------------------------------------------------------------------
# MoE chunk/layer schedule (the prefetched expert path)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_moe_prefetch_loss_and_grads_8dev():
    """MoE prefetch=1 (layer gathers + chunk pipeline double-buffered) ==
    prefetch=0 (synchronous): bit-exact losses AND gradients, 8 devices."""
    run_checks(["check_moe_prefetch_matches_sync"], n_devices=8,
               timeout=1200)


@pytest.mark.slow
def test_moe_prefetch_loss_and_grads_4dev():
    """Same bit-exactness on the smaller 2x2 mesh (different shard and
    secondary-group sizes exercise the alignment paths)."""
    run_checks(["check_moe_prefetch_matches_sync"], n_devices=4,
               timeout=1200)


@pytest.mark.slow
def test_moe_prefetch_overlap_hlo():
    """Compiled HLO: MoE overlap_fraction > 0.7 with prefetch=1 (layer
    scan + nested chunk scans, no gather-only remat loop left), == 0 with
    prefetch=0."""
    run_checks(["check_moe_prefetch_overlap_fraction"], n_devices=8,
               timeout=1200)


# ---------------------------------------------------------------------------
# depth-k prefetch ring (ring schedule, routing-ahead, hpZ nested recompute)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_depth_sweep_dense_8dev():
    """Dense 4-layer stack on 8 devices: losses AND gradients bit-exact
    to the synchronous reference at prefetch ∈ {1,2,3} and at 8 >
    n_layers (ring clamp)."""
    run_checks(["check_prefetch_depth_sweep"], n_devices=8, timeout=2400)


@pytest.mark.slow
def test_depth_sweep_dense_4dev():
    run_checks(["check_prefetch_depth_sweep"], n_devices=4, timeout=2400)


@pytest.mark.slow
def test_depth_sweep_moe_8dev():
    """MoE 4-layer stack (chunk+layer rings, speculative chunk-0 gather,
    hpZ-residual nested recompute): bit-exact across the same sweep."""
    run_checks(["check_moe_prefetch_depth_sweep"], n_devices=8,
               timeout=3600)


@pytest.mark.slow
def test_depth_sweep_moe_4dev():
    run_checks(["check_moe_prefetch_depth_sweep"], n_devices=4,
               timeout=3600)


@pytest.mark.slow
def test_ring_overlap_depth():
    """Acceptance: prefetch=2 strictly beats prefetch=1 in depth-credited
    overlap on dense AND MoE stacks; the MoE nested-remat re-gather is no
    longer exposed."""
    run_checks(["check_ring_overlap_depth"], n_devices=8, timeout=2400)


def test_zeroconfig_prefetch_validation():
    """Negative ring depths are rejected; effective_prefetch clamps to
    n-1 and degenerates to synchronous for local/single-layer scans."""
    import jax.numpy as jnp
    from repro.core.zeropp import ZeroConfig

    with pytest.raises(ValueError):
        ZeroConfig(prefetch=-1)
    z = ZeroConfig(prefetch=3)
    assert z.effective_prefetch(8) == 3
    assert z.effective_prefetch(4) == 3
    assert z.effective_prefetch(2) == 1      # clamp to n-1
    assert z.effective_prefetch(1) == 0      # single step: synchronous
    assert ZeroConfig.local(prefetch=3).effective_prefetch(8) == 0
    assert ZeroConfig(prefetch=0).effective_prefetch(8) == 0


# ---------------------------------------------------------------------------
# stack layout: lane-dense rows and the flat fallback, bit-exact at depth
# ---------------------------------------------------------------------------

N_LAYERS = 4
# per-layer flat lengths: a multiple of 128 (rows carried [N/128, 128])
# and one that is not (rows kept flat); quant blocks of 64 divide both
LENGTHS = {"lane-dense": 384, "flat": 320}


def _ring_case(scan: str, length: int, prefetch: int):
    """(loss, grads) of a toy stack through one ring scan at ``prefetch``
    on a one-device ZeRO++ mesh (qwZ, hpZ, qgZ all on)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core.schedule import (zero_apply_scan, zero_chunk_scan,
                                     zero_chunk_scan_hpz)
    from repro.core.zeropp import ZeroConfig
    from repro.launch.mesh import make_mesh

    z = ZeroConfig(prefetch=prefetch, qwz_block=64, qgz_block=64)
    mesh = make_mesh((1, 1), z.dp_axes, devices=jax.devices()[:1])
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    stacked = jax.random.normal(ks[0], (N_LAYERS, length), jnp.float32)
    h0 = jax.random.normal(ks[1], (length,), jnp.float32)
    xs = jax.random.normal(ks[2], (N_LAYERS, length), jnp.float32)

    def body(W, h, x):
        h2 = jnp.tanh(h * W.astype(jnp.float32) + x)
        return h2, jnp.sum(h2 * h2)

    def loss(stacked, h0, xs):
        if scan == "dense":
            h, ys = zero_apply_scan(body, z)(stacked, h0, xs)
            return jnp.sum(h) + jnp.sum(ys)
        # chunks: no carry, h0 rides as a broadcast argument
        def f(W, x, h):
            return body(W, h, x)[0]

        if scan == "moe-chunk":
            ys = zero_chunk_scan(f, z)(stacked, xs, h0)
        else:  # the nested hpZ recompute, from the saved secondary shards
            _, sec = zero_chunk_scan(f, z, collect_secondary=True)(
                stacked, xs, h0)
            ys = zero_chunk_scan_hpz(f, z)(stacked, sec, xs, h0)
        return jnp.sum(ys * ys)

    fn = jax.shard_map(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                       mesh=mesh, in_specs=(P(), P(), P()),
                       out_specs=(P(), (P(), P(), P())), check_vma=False)
    val, grads = jax.jit(fn)(stacked, h0, xs)
    return float(val), [np.asarray(g) for g in grads]


@pytest.mark.parametrize("layout", list(LENGTHS))
@pytest.mark.parametrize("scan", ["dense", "moe-chunk", "moe-chunk-hpz"])
def test_ring_layout_bit_exact(scan, layout):
    """Every ring depth gives the synchronous reference's loss and every
    gradient leaf bit for bit, with the stacks lane-dense (length a
    multiple of 128) and in the flat fallback."""
    import numpy as np

    l0, g0 = _ring_case(scan, LENGTHS[layout], 0)
    for pf in (1, 2):
        l, g = _ring_case(scan, LENGTHS[layout], pf)
        assert l == l0, (scan, layout, pf, l, l0)
        for a, b in zip(g0, g):
            assert a.shape == b.shape and np.array_equal(a, b), \
                (scan, layout, pf, float(np.abs(a - b).max()))


# ---------------------------------------------------------------------------
# analyze_overlap unit tests (synthetic HLO, no devices)
# ---------------------------------------------------------------------------

_SYNC_HLO = """
HloModule sync

%cond (p: (s32[], f32[8], f32[64])) -> pred[] {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

%body (p: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %w = f32[8]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=1
  %h = f32[64]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=2
  %g = f32[64]{0} all-gather(f32[8]{0} %w), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %wm = f32[8,8]{1,0} reshape(f32[64]{0} %g)
  %hm = f32[8,8]{1,0} reshape(f32[64]{0} %h)
  %mm = f32[8,8]{1,0} dot(f32[8,8]{1,0} %hm, f32[8,8]{1,0} %wm), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %h2 = f32[64]{0} reshape(f32[8,8]{1,0} %mm)
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %out = (s32[], f32[8], f32[64]) tuple(s32[] %i2, f32[8]{0} %w, f32[64]{0} %h2)
}

ENTRY %main (a: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %a = (s32[], f32[8], f32[64]) parameter(0)
  ROOT %w0 = (s32[], f32[8], f32[64]) while((s32[], f32[8], f32[64]) %a), condition=%cond, body=%body
}
"""

# prefetched: the gather consumes a carried shard and feeds only the carry;
# the dot consumes the PREVIOUS iteration's gathered weights (also carried)
_PREFETCH_HLO = _SYNC_HLO.replace("HloModule sync", "HloModule prefetch") \
    .replace(
        "%g = f32[64]{0} all-gather(f32[8]{0} %w), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n"
        "  %wm = f32[8,8]{1,0} reshape(f32[64]{0} %g)\n"
        "  %hm = f32[8,8]{1,0} reshape(f32[64]{0} %h)",
        "%g = f32[64]{0} all-gather(f32[8]{0} %w), "
        "replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}\n"
        "  %wm = f32[8,8]{1,0} reshape(f32[64]{0} %h)\n"
        "  %hm = f32[8,8]{1,0} reshape(f32[64]{0} %h)") \
    .replace(
        "ROOT %out = (s32[], f32[8], f32[64]) tuple(s32[] %i2, "
        "f32[8]{0} %w, f32[64]{0} %h2)",
        "%keep = f32[8]{0} slice(f32[64]{0} %g), slice={[0:8]}\n"
        "  ROOT %out = (s32[], f32[8], f32[64]) tuple(s32[] %i2, "
        "f32[8]{0} %keep, f32[64]{0} %h2)")


def test_analyze_overlap_sync_exposed():
    ov = analyze_overlap(_SYNC_HLO)
    assert ov["in_loop_collectives"] == 1
    assert ov["overlappable_collectives"] == 0
    assert ov["overlap_fraction"] == 0.0


def test_analyze_overlap_prefetch_detected():
    ov = analyze_overlap(_PREFETCH_HLO)
    assert ov["in_loop_collectives"] == 1
    assert ov["overlappable_collectives"] == 1
    assert ov["overlap_fraction"] == 1.0
    # trip count parsed from the loop condition constant
    (loop,) = ov["per_loop"].values()
    assert loop["trip_count"] == 4


# nested loops: a 3-trip inner (chunk) loop inside a 4-trip outer (layer)
# loop — the inner loop's wire bytes must be weighted by the outer trips
_NESTED_HLO = """
HloModule nested

%icond (p: (s32[], f32[8], f32[64])) -> pred[] {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

%ibody (p: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %w = f32[8]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=1
  %h = f32[64]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=2
  %g = f32[64]{0} all-gather(f32[8]{0} %w), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %wm = f32[8,8]{1,0} reshape(f32[64]{0} %g)
  %hm = f32[8,8]{1,0} reshape(f32[64]{0} %h)
  %mm = f32[8,8]{1,0} dot(f32[8,8]{1,0} %hm, f32[8,8]{1,0} %wm), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %h2 = f32[64]{0} reshape(f32[8,8]{1,0} %mm)
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %out = (s32[], f32[8], f32[64]) tuple(s32[] %i2, f32[8]{0} %w, f32[64]{0} %h2)
}

%ocond (p: (s32[], f32[8], f32[64])) -> pred[] {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %j = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %m = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %j, s32[] %m), direction=LT
}

%obody (p: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %j = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %w = f32[8]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=1
  %h = f32[64]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=2
  %zero = s32[] constant(0)
  %it = (s32[], f32[8], f32[64]) tuple(s32[] %zero, f32[8]{0} %w, f32[64]{0} %h)
  %iw = (s32[], f32[8], f32[64]) while((s32[], f32[8], f32[64]) %it), condition=%icond, body=%ibody
  %h3 = f32[64]{0} get-tuple-element((s32[], f32[8], f32[64]) %iw), index=2
  %one = s32[] constant(1)
  %j2 = s32[] add(s32[] %j, s32[] %one)
  ROOT %out = (s32[], f32[8], f32[64]) tuple(s32[] %j2, f32[8]{0} %w, f32[64]{0} %h3)
}

ENTRY %main (a: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %a = (s32[], f32[8], f32[64]) parameter(0)
  ROOT %w0 = (s32[], f32[8], f32[64]) while((s32[], f32[8], f32[64]) %a), condition=%ocond, body=%obody
}
"""


def test_analyze_overlap_nested_loop_multiplier():
    ov = analyze_overlap(_NESTED_HLO)
    (loop,) = ov["per_loop"].values()          # only the inner body gathers
    assert loop["trip_count"] == 3
    assert loop["outer_mult"] == 4.0
    # gather moves 64-8=56 f32 = 224 bytes, x3 trips x4 outer iterations
    assert ov["in_loop_wire_bytes"] == 224 * 3 * 4
    assert ov["overlap_fraction"] == 0.0       # sync: gather feeds the dot


# a gather-only loop (what XLA leaves of a remat whose recomputed GEMMs are
# dead): nothing to overlap with inside the iteration -> exposed
_GATHER_ONLY_HLO = """
HloModule gatheronly

%cond (p: (s32[], f32[8], f32[64])) -> pred[] {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %n = s32[] constant(2)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

%body (p: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %p = (s32[], f32[8], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[64]) %p), index=0
  %w = f32[8]{0} get-tuple-element((s32[], f32[8], f32[64]) %p), index=1
  %g = f32[64]{0} all-gather(f32[8]{0} %w), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %out = (s32[], f32[8], f32[64]) tuple(s32[] %i2, f32[8]{0} %w, f32[64]{0} %g)
}

ENTRY %main (a: (s32[], f32[8], f32[64])) -> (s32[], f32[8], f32[64]) {
  %a = (s32[], f32[8], f32[64]) parameter(0)
  ROOT %w0 = (s32[], f32[8], f32[64]) while((s32[], f32[8], f32[64]) %a), condition=%cond, body=%body
}
"""


def test_analyze_overlap_gather_only_loop_exposed():
    ov = analyze_overlap(_GATHER_ONLY_HLO)
    assert ov["in_loop_collectives"] == 1
    assert ov["overlappable_collectives"] == 0
    assert ov["overlap_fraction"] == 0.0


_ASYNC_HLO = """
HloModule asyncpair

ENTRY %main (w: f32[8], h: f32[8,8]) -> f32[8,8] {
  %w = f32[8]{0} parameter(0)
  %h = f32[8,8]{1,0} parameter(1)
  %ags = (f32[8], f32[64]) all-gather-start(f32[8]{0} %w), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %mm = f32[8,8]{1,0} dot(f32[8,8]{1,0} %h, f32[8,8]{1,0} %h), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %agd = f32[64]{0} all-gather-done((f32[8], f32[64]) %ags)
  %wm = f32[8,8]{1,0} reshape(f32[64]{0} %agd)
  ROOT %o = f32[8,8]{1,0} add(f32[8,8]{1,0} %mm, f32[8,8]{1,0} %wm)
}
"""


def test_analyze_overlap_async_pairs():
    ov = analyze_overlap(_ASYNC_HLO)
    assert ov["async_pairs"] == 1
    assert ov["async_pairs_enclosing_compute"] == 1


# ring-carried gather: the result is dynamic-update-sliced into a (2,64)
# ring buffer in the carry, so it is consumed two iterations later —
# slack_iters must read the ring depth off the buffer's leading dim
_RING2_HLO = """
HloModule ring2

%cond (p: (s32[], f32[8], f32[2,64], f32[64])) -> pred[] {
  %p = (s32[], f32[8], f32[2,64], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[2,64], f32[64]) %p), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(s32[] %i, s32[] %n), direction=LT
}

%body (p: (s32[], f32[8], f32[2,64], f32[64])) -> (s32[], f32[8], f32[2,64], f32[64]) {
  %p = (s32[], f32[8], f32[2,64], f32[64]) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[8], f32[2,64], f32[64]) %p), index=0
  %w = f32[8]{0} get-tuple-element((s32[], f32[8], f32[2,64], f32[64]) %p), index=1
  %r = f32[2,64]{1,0} get-tuple-element((s32[], f32[8], f32[2,64], f32[64]) %p), index=2
  %h = f32[64]{0} get-tuple-element((s32[], f32[8], f32[2,64], f32[64]) %p), index=3
  %g = f32[64]{0} all-gather(f32[8]{0} %w), replica_groups={{0,1,2,3,4,5,6,7}}, dimensions={0}
  %gu = f32[1,64]{1,0} reshape(f32[64]{0} %g)
  %z = s32[] constant(0)
  %r2 = f32[2,64]{1,0} dynamic-update-slice(f32[2,64]{1,0} %r, f32[1,64]{1,0} %gu, s32[] %z, s32[] %z)
  %hm = f32[8,8]{1,0} reshape(f32[64]{0} %h)
  %mm = f32[8,8]{1,0} dot(f32[8,8]{1,0} %hm, f32[8,8]{1,0} %hm), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %h2 = f32[64]{0} reshape(f32[8,8]{1,0} %mm)
  %one = s32[] constant(1)
  %i2 = s32[] add(s32[] %i, s32[] %one)
  ROOT %out = (s32[], f32[8], f32[2,64], f32[64]) tuple(s32[] %i2, f32[8]{0} %w, f32[2,64]{1,0} %r2, f32[64]{0} %h2)
}

ENTRY %main (a: (s32[], f32[8], f32[2,64], f32[64])) -> (s32[], f32[8], f32[2,64], f32[64]) {
  %a = (s32[], f32[8], f32[2,64], f32[64]) parameter(0)
  ROOT %w0 = (s32[], f32[8], f32[2,64], f32[64]) while((s32[], f32[8], f32[2,64], f32[64]) %a), condition=%cond, body=%body
}
"""

# the same schedule with a one-slot ring (the classic double buffer)
_RING1_HLO = _RING2_HLO.replace("ring2", "ring1").replace("2,64", "1,64")


def test_ring_slack_detected():
    ov = analyze_overlap(_RING2_HLO)
    (loop,) = ov["per_loop"].values()
    assert loop["has_compute"]
    assert loop["max_slack_iters"] == 2
    (coll,) = loop["colls"]
    assert coll["overlappable"] and coll["slack_iters"] == 2
    ov1 = analyze_overlap(_RING1_HLO)
    (loop1,) = ov1["per_loop"].values()
    assert loop1["max_slack_iters"] == 1


def test_effective_overlap_depth_credit():
    """A gather issued d iterations early is credited against d iterations
    of compute: at a bandwidth where one iteration cannot cover it, the
    2-slot ring strictly beats the 1-slot ring; at a fast operating point
    both saturate to the structural fraction."""
    from repro.launch.hlo_analysis import effective_overlap

    ov1 = analyze_overlap(_RING1_HLO)
    ov2 = analyze_overlap(_RING2_HLO)
    assert ov1["overlap_fraction"] == ov2["overlap_fraction"] == 1.0
    slow = dict(peak_flops=1e9,
                tier_bw={"model": 1e6, "data": 1e6, "pod": 1e6},
                coll_latency_s=0.0)
    e1 = effective_overlap(ov1, **slow)["effective_overlap_fraction"]
    e2 = effective_overlap(ov2, **slow)["effective_overlap_fraction"]
    assert 0.0 < e1 < e2 <= 1.0, (e1, e2)
    fast = dict(peak_flops=1e9,
                tier_bw={"model": 1e12, "data": 1e12, "pod": 1e12},
                coll_latency_s=0.0)
    for ov in (ov1, ov2):
        eff = effective_overlap(ov, **fast)["effective_overlap_fraction"]
        assert eff == ov["overlap_fraction"], (eff, ov["overlap_fraction"])
