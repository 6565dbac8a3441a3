"""A configuration's layers, found by name (``bench/models``): the dense
model's numbers stay what they were, and the MoE layer's share of the
experts adds up to the whole layer."""
import dataclasses
import glob
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import flops, models, reference, spec, tokens, weights
from bench.models import dense, moe
from bench.tests import faults

GPT = spec.load_json(f"{spec.BENCH}/configs/gpt-350m.json")
SC2 = spec.load_json(f"{spec.BENCH}/configs/starcoder2-3b-l4.json")
TINY_MOE = spec.load_json(f"{faults.DATA}/tiny-moe.json")


def _model(cfg, world=1, **zero):
    from repro.configs import get_config
    from repro.core.zeropp import ZeroConfig
    from repro.models.model import Model
    arch = dataclasses.replace(get_config(cfg["arch"]),
                               **spec.arch_overrides(cfg))
    return Model(arch, ZeroConfig(**zero), world=world)


def test_a_configuration_names_its_module():
    assert models.load(GPT) is dense
    assert models.load(TINY_MOE) is moe
    with pytest.raises(ValueError):
        models.load({"model": "../run"})


# ---------------------------------------------------------------------------
# the dense model's numbers, as they were before the layers had modules
# ---------------------------------------------------------------------------

def test_flops_per_token_is_pinned():
    assert flops.flops_per_token(GPT, 1024) == 2875981824
    assert flops.flops_per_token(SC2, 4096) == 4416602112


@pytest.mark.parametrize("cfg,world,intra,calls,nbytes", [
    (GPT, 1, 1, 155, 8409068320.0),
    (GPT, 4, 2, 155, 4606618252.0),
    (SC2, 1, 1, 55, 15618837600.0),
    (SC2, 4, 2, 55, 8556241860.0),
], ids=["gpt-350m-1", "gpt-350m-4", "starcoder2-3b-l4-1",
        "starcoder2-3b-l4-4"])
def test_quant_kernel_calls_are_pinned(cfg, world, intra, calls, nbytes):
    shapes = _model(cfg, world).param_shapes()
    for c in (None, cfg):
        got = flops.quant_kernel_calls(shapes, world, intra, 1, cfg=c)
        assert got == {"calls_per_step": calls, "bytes_per_step": nbytes}


def test_dense_reference_readings_are_pinned():
    """The tiny dense cell's reference, bit for bit what it read when the
    dense block lived in bench/reference.py."""
    cell = faults.tiny()
    cfg, tr = cell.config, cell.traffic
    gen = tokens.Tokens(tr, cfg["vocab"], faults.SEED)
    r = reference.Reference(cfg, tr).run(
        faults.SEED, [gen.batch(k) for k in range(3)])
    assert r.losses == [6.700566291809082, 6.799050331115723,
                        6.677418231964111]
    assert len(r.grad_sumsq) == 19
    assert math.fsum(r.grad_sumsq.values()) == 0.9999999999997696
    assert math.fsum(r.change_sumsq.values()) == 0.4734328099511913


# ---------------------------------------------------------------------------
# the MoE layer
# ---------------------------------------------------------------------------

def test_moe_departures_are_configuration_keys():
    """Every place the reference can depart from the paper is a key the
    tiny file states and lists under ``departures``; the keys the program
    has no option for are kept from it."""
    from repro.configs.base import ArchConfig
    fields = {f.name for f in dataclasses.fields(ArchConfig)}
    assert set(moe.DEPARTURES) <= set(TINY_MOE["departures"])
    assert set(moe.DEPARTURES) <= set(TINY_MOE)
    for k in TINY_MOE["reference_only"]:
        assert k not in fields and k not in spec.arch_overrides(TINY_MOE)
    assert set(spec.arch_overrides(TINY_MOE)) <= fields


def test_moe_matmul_params_must_be_whole():
    """A held share whose routed parameters do not divide over the router's
    experts has no whole count a token meets: refused, not rounded."""
    with pytest.raises(ValueError):
        moe.matmul_params(dict(TINY_MOE, router_experts=7))
    assert isinstance(moe.matmul_params(dict(TINY_MOE, router_experts=16)),
                      int)


def test_moe_matmul_params_match_the_model_layout():
    cfg = TINY_MOE
    model = _model(cfg)
    mats = sum(math.prod(s) for _, s in model.period_spec.entries
               if len(s) == 2) * model.n_periods
    per_expert = model.expert_spec.size * cfg["expert_chunks"] \
        // cfg["n_experts"]
    mats += cfg["top_k"] * per_expert * model.n_periods
    mats += sum(math.prod(s) for _, s in model.unemb_spec.entries) \
        * model.unemb_chunks
    assert flops.matmul_params(cfg) == mats


def _by_group(site: str) -> str:
    if site.startswith("experts") or site.startswith("blocks.") and \
            "spec" in site:
        return "experts"
    return site.split(".")[0]


@pytest.mark.parametrize("prefetch", [0, 1, 2])
@pytest.mark.parametrize("world", [1, 4])
def test_moe_gathers_are_the_programs_plan(world, prefetch):
    """The qwZ gathers and qgZ reduces counted for each buffer group are
    the ones the program's own per-step plan lists (``Model.comm_events``;
    hpZ's backward gathers quantize nothing)."""
    model = _model(TINY_MOE, world, prefetch=prefetch)
    want = {}
    for ev in model.comm_events():
        g = _by_group(ev["site"])
        n = want.setdefault(g, [0, 0])
        if ev["kind"] == "fwd_gather":
            n[0] += int(ev["count"])
        elif ev["kind"] == "grad_reduce":
            n[1] += int(ev["count"])
    got = moe.quant_sites(model.param_shapes(), prefetch)
    assert {g: tuple(n) for g, n in want.items()} == got
    calls = flops.quant_kernel_calls(model.param_shapes(), world, 1,
                                     prefetch, cfg=TINY_MOE)
    assert calls["calls_per_step"] == sum(2 * a + 3 * b
                                          for a, b in got.values())


def test_packer_lays_every_expert_chunk_of_every_layer():
    """Each row of the program's expert stack holds its chunk's slice of
    its layer's expert leaves, as the reference draws them."""
    cfg = TINY_MOE
    model = _model(cfg)
    pk = weights.Packer(model, cfg)
    ref = reference.Reference(cfg, faults.tiny("1x1", "tiny-moe").traffic)
    kd = weights.key_data(faults.SEED)
    stack = np.asarray(jax.jit(lambda kd: pk.build(kd, "experts"))(kd))
    nc = cfg["expert_chunks"]
    per = cfg["n_experts"] // nc
    assert stack.shape == pk.shapes["experts"]
    for layer in range(cfg["n_layers"]):
        for c in range(nc):
            row = model.expert_spec.unpack(jnp.asarray(stack[layer, c]))
            for base in ("egu", "edn"):
                leaf = np.asarray(ref.draw(kd, f"layer{layer}.{base}"))
                np.testing.assert_array_equal(
                    np.asarray(row[base]), leaf[c * per:(c + 1) * per])
    sums = jax.vmap(lambda r: pk.row_sumsq(r, "experts"))(
        jnp.asarray(stack.reshape(-1, stack.shape[-1])))
    folded = pk.to_leaves("experts", np.asarray(sums))
    assert sorted(folded) == [f"layer{l}.{b}" for l in range(2)
                              for b in ("edn", "egu")]


@pytest.mark.parametrize("n_shares", [2, 4])
def test_expert_shares_add_up_to_the_layer(n_shares):
    """Split one MoE layer's experts into disjoint shares: each share's
    part, with the router, attention and shared experts counted once, adds
    up to the uncut layer's output and gradients, to float32 round-off."""
    cfg = TINY_MOE
    tr = faults.tiny("1x1", "tiny-moe").traffic
    ref = reference.Reference(cfg, tr)
    kd = weights.key_data(faults.SEED)
    W = {n: ref.draw(kd, f"layer0.{n}") for n in ref.layer_names[0]}
    B, S, d = 2, 32, cfg["d_model"]
    h = jax.random.normal(jax.random.key(1), (B, S, d), jnp.float32)
    ct = jax.random.normal(jax.random.key(2), (B, S, d), jnp.float32)
    cos, sin = reference.rope_tables(S, cfg["head_dim"], cfg["rope_theta"])
    E = cfg["n_experts"]
    shares = [list(range(s, E, n_shares)) for s in range(n_shares)]

    def layer(W, h, held):
        if held is not None:
            W = dict(W, egu=W["egu"][jnp.asarray(held, jnp.int32)],
                     edn=W["edn"][jnp.asarray(held, jnp.int32)])
        return moe.block(cfg, 0, reference._einsum, W, h, cos, sin,
                         held=held)

    def whole(W, h):
        return layer(W, h, None)

    def in_shares(W, h):
        common, aux = layer(W, h, [])
        out = common
        for ids in shares:
            part, aux_s = layer(W, h, ids)
            out = out + (part - common)
        return out, aux

    def loss(f):
        return lambda W, h: jnp.sum(f(W, h)[0] * ct) + f(W, h)[1]

    (y1, a1), (y2, a2) = whole(W, h), in_shares(W, h)
    scale = float(jnp.max(jnp.abs(y1)))
    np.testing.assert_allclose(y2, y1, rtol=0, atol=2e-6 * scale)
    assert float(a2) == pytest.approx(float(a1), rel=1e-6)
    g1 = jax.grad(loss(whole), argnums=(0, 1))(W, h)
    g2 = jax.grad(loss(in_shares), argnums=(0, 1))(W, h)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        s = float(jnp.max(jnp.abs(a)))
        np.testing.assert_allclose(b, a, rtol=0, atol=2e-5 * s)
    # tokens reach every expert, so every expert's gradient is compared
    assert float(jnp.min(jnp.sum(g1[0]["egu"] ** 2, axis=(1, 2)))) > 0


def test_moe_layers_differ_by_kind_and_hold_a_share():
    """A leading dense layer, and layers that hold 8 of the router's 16
    experts: the leaves, the count and the reference follow."""
    cfg = dict(TINY_MOE, first_k_dense_replace=1, router_experts=16,
               n_layers=3, d_ff=256)
    tr = faults.tiny("1x1", "tiny-moe").traffic
    assert [moe.layer_kind(cfg, l) for l in range(3)] == \
        ["dense", "moe", "moe"]
    shapes = dict((n, s) for n, s, _ in moe.leaves(cfg))
    assert "layer0.wgu" in shapes and "layer0.router" not in shapes
    assert shapes["layer1.router"] == (128, 16)
    assert shapes["layer2.egu"] == (8, 128, 128)
    d, f = 128, 64
    attn = dense.attention_params(cfg)
    assert moe.matmul_params(cfg) == (attn + 3 * d * 256) + 2 * (
        attn + d * 16 + 3 * d * f + 3 * d * f * 2 * 8 // 16) + 512 * d
    gen = tokens.Tokens(tr, cfg["vocab"], 5)
    r = reference.Reference(cfg, tr).run(5, [gen.batch(0)])
    assert np.isfinite(r.losses[0]) and set(r.grad_sumsq) == set(shapes)


def _balance_by_hand(x, router, k, E, groups):
    """The paper's expert-level balance term of each token group, in
    float64: E * sum_e mean_t(s_e) * count_e / (k T)."""
    out = []
    for g in groups:
        z = g @ router
        s = np.exp(z - z.max(-1, keepdims=True))
        s /= s.sum(-1, keepdims=True)
        top = np.argsort(-s, axis=-1)[:, :k]
        count = np.bincount(top.reshape(-1), minlength=E)
        out.append(E * np.sum(s.mean(0) * count / (k * len(g))))
    return np.array(out)


@pytest.mark.parametrize("scope", ["chip", "batch"])
def test_moe_balance_term_per_routing_scope(scope):
    """On a 2 x 2 split of the batch (2 groups of 2 rows, 2 pieces of the
    sequence), ``chip`` takes the balance term over each chip's tokens and
    averages it; ``batch`` takes it over all the tokens at once.  Both
    against the term worked out by hand."""
    cfg = dict(TINY_MOE, routing_scope=scope)
    tr = faults.tiny("1x1", "tiny-moe").traffic
    ref = reference.Reference(cfg, tr)
    kd = weights.key_data(faults.SEED)
    W = {n: ref.draw(kd, f"layer0.{n}") for n in ref.layer_names[0]}
    B, S, d, E, k = 4, 32, cfg["d_model"], cfg["n_experts"], cfg["top_k"]
    h = jax.random.normal(jax.random.key(3), (B, S, d), jnp.float32)
    cos, sin = reference.rope_tables(S, cfg["head_dim"], cfg["rope_theta"])
    x = np.asarray(dense.rms_norm(
        dense.attend(cfg, reference._einsum, W, h, cos, sin), W["ln2"]),
        np.float64)
    router = np.asarray(W["router"], np.float64)
    if scope == "chip":
        groups = [x[2 * r:2 * r + 2, p * S // 2:(p + 1) * S // 2]
                  .reshape(-1, d) for r in range(2) for p in range(2)]
    else:
        groups = [x.reshape(B * S, d)]
    want = cfg["aux_loss_weight"] * np.mean(
        _balance_by_hand(x, router, k, E, groups)) / cfg["n_layers"]
    _, aux = moe.block(cfg, 0, reference._einsum, W, h, cos, sin,
                       shards=(2, 2))
    assert float(aux) == pytest.approx(want, rel=1e-5)
    _, whole = moe.block(cfg, 0, reference._einsum, W, h, cos, sin,
                         shards=(1, 1))
    other = dict(cfg, routing_scope="batch" if scope == "chip" else "chip")
    _, aux_other = moe.block(other, 0, reference._einsum, W, h, cos, sin,
                             shards=(2, 2))
    # one group: the scopes agree; four: they differ
    assert float(whole) == pytest.approx(
        float(aux) if scope == "batch" else float(aux_other), rel=1e-6)
    assert abs(float(aux_other) - float(aux)) > 1e-3 * abs(float(aux))


def _traffics():
    files = sorted(glob.glob(f"{spec.BENCH}/traffic/*.json")) + \
        sorted(glob.glob(f"{faults.DATA}/tiny-[12]x*.json"))
    out = [pytest.param(spec.load_json(f), id=os.path.basename(f)[:-5])
           for f in files]
    for gb, mesh in [(1, (2, 2)), (2, (2, 2)), (3, (2, 2)), (6, (2, 2)),
                     (2, (1, 4)), (2, (4, 1))]:
        out.append(pytest.param({"global_batch": gb, "mesh": list(mesh)},
                                id=f"b{gb}-{mesh[0]}x{mesh[1]}"))
    return out


@pytest.mark.parametrize("traffic", _traffics())
def test_data_shards_are_the_programs_batch_split(traffic):
    """The reference routes together the tokens each chip holds
    (``routing_scope`` "chip"), so its split of the batch has to be the
    one the program's train step lays the batch out by: the batch's spec
    from ``trainer.choose_batch_seq_axes`` and ``trainer.batch_specs``, as
    ``build_train_step`` makes it for ``run.Program``."""
    from jax.sharding import AbstractMesh

    from bench import run
    from repro.train import trainer
    mesh = AbstractMesh(tuple(int(n) for n in traffic["mesh"]), run.AXES)
    b, s = trainer.choose_batch_seq_axes(int(traffic["global_batch"]), mesh)
    spec_ = trainer.batch_specs(_model(TINY_MOE), run.AXES, b, s)["tokens"]

    def size(axes):
        axes = (axes,) if isinstance(axes, str) else (axes or ())
        return math.prod(mesh.shape[a] for a in axes)

    assert reference.data_shards(traffic) == (size(spec_[0]), size(spec_[1]))


def test_the_program_lays_its_batch_out_by_that_split():
    """``run.Program`` takes the batch's spec that the test above
    reproduces."""
    from bench import run
    from repro.train import trainer
    cell = faults.tiny("1x1", "tiny-moe")
    prog = run.Program(cell, jax.devices()[:1])
    b, s = trainer.choose_batch_seq_axes(int(cell.traffic["global_batch"]),
                                         prog.mesh)
    assert prog.b_specs == trainer.batch_specs(prog.model, run.AXES, b, s)
