"""The FLOP count of ``bench/flops.py`` against the program's own
parameter layout."""
import dataclasses

import pytest

from bench import flops, spec


def _model(cfg):
    from repro.configs import get_config
    from repro.core.zeropp import ZeroConfig
    from repro.models.model import Model
    arch = dataclasses.replace(get_config(cfg["arch"]),
                               **spec.arch_overrides(cfg))
    return Model(arch, ZeroConfig(), world=1)


@pytest.mark.parametrize("name", ["gpt-350m", "starcoder2-3b-l4"])
def test_matmul_params_match_the_model_layout(name):
    cfg = spec.load_json(f"{spec.BENCH}/configs/{name}.json")
    model = _model(cfg)
    mats = sum(s[0] * s[1] for _, s in model.period_spec.entries
               if len(s) == 2) * model.n_periods
    mats += sum(s[0] * s[1] for _, s in model.unemb_spec.entries) \
        * model.unemb_chunks
    assert flops.matmul_params(cfg) == mats
    shapes = model.param_shapes()
    assert shapes["blocks"][0] == cfg["n_layers"]


def test_flops_per_token_by_hand():
    gpt = spec.load_json(f"{spec.BENCH}/configs/gpt-350m.json")
    want = 6 * (24 * (4 * 1024 ** 2 + 3 * 1024 * 4096) + 50304 * 1024) \
        + 24 * 3 * 2 * 1024 * 1024
    assert flops.flops_per_token(gpt, 1024) == want
    sc = spec.load_json(f"{spec.BENCH}/configs/starcoder2-3b-l4.json")
    per_layer = 3072 * 3072 + 2 * 3072 * 256 + 3072 * 3072 \
        + 3 * 3072 * 12288
    want = 6 * (4 * per_layer + 49152 * 3072) + 4 * 3 * 2 * 4096 * 3072
    assert flops.flops_per_token(sc, 4096) == want
