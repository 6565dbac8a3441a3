"""BENCHMARK.json and the files it names: every cell, configuration and
metric is found by name and is consistent with its neighbours."""
import dataclasses
import os

import pytest

from bench import spec

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_loads(name):
    cell = spec.cell(name)
    assert cell.mesh[0] * cell.mesh[1] == cell.chips
    assert set(cell.limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s",
                                                    "tokens_per_s"}
    assert cell.per_layer, name


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_is_the_registry_arch_with_its_cuts(entry):
    from repro.configs import get_config
    cfg = spec.load_json(os.path.join(spec.ROOT, entry["file"]))
    base = get_config(cfg["arch"])
    over = spec.arch_overrides(cfg)
    changed = sorted(k for k, v in over.items() if getattr(base, k) != v)
    assert changed == sorted(entry["reduced"]) == sorted(cfg["reduced"])
    arch = dataclasses.replace(base, **over)
    assert arch.head_dim * arch.n_heads == cfg["n_heads"] * cfg["head_dim"]


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(metric):
    path = os.path.join(spec.BENCH, "metrics", metric["name"] + ".py")
    assert os.path.isfile(path)
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    for w in metric.get("workloads", []):
        assert w in CELLS


def test_names_and_units_keep_the_contract():
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert name.match(w["name"]) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= \
        max(1, len(BENCH["workloads"]) // 2)


def test_traffic_keys_the_harness_does_not_read_are_refused(tmp_path):
    import json
    from bench.tests import faults
    good = spec.load_json(os.path.join(faults.DATA, "tiny-1x1.json"))
    bad = tmp_path / "t.json"
    bad.write_text(json.dumps(dict(good, loop="open")))
    with pytest.raises(ValueError, match="loop"):
        spec.make_cell("t", 1, os.path.join(faults.DATA, "tiny.json"),
                       str(bad), os.path.join(faults.DATA,
                                              "tiny-limits.json"))
