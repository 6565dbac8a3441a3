"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own, found by name:

  bench/configs/<config>.json     the model as it is run, with its source
  bench/models/<model>.py         its layers, named by the file's "model"
  bench/traffic/<traffic>.json    batch, sequence, mesh, optimizer, data
  bench/workloads/<cell>.json     the limits that decide ``correct``
  bench/metrics/<metric>.py       one reader per per-layer metric

A later change adds a cell, a configuration or a metric by adding files
and entries here, never by editing one.  Nothing in this module imports
JAX.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# keys of a configuration file that describe it rather than set a size;
# ``model`` names its layers' module (bench/models/<model>.py), and
# ``reference_only`` lists keys that state behaviour the program fixes in
# code: the reference reads them, the program has no option for them
CONFIG_META = ("arch", "source", "reduced", "published", "assumed",
               "departures", "deployment", "precision", "model",
               "reference_only")


# what the harness reads from a traffic file; any other key is refused, so
# that a file cannot name traffic the harness does not run
TRAFFIC_KEYS = {"mesh", "variant", "global_batch", "seq_len", "optimizer",
                "data"}
DATA_KEYS = {"branching", "alpha"}     # of bench/tokens.py's Markov chain


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def mesh(self) -> tuple:
        return tuple(int(x) for x in self.traffic["mesh"])

    @property
    def tokens_per_step(self) -> int:
        return int(self.traffic["global_batch"]) * int(self.traffic["seq_len"])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def arch_overrides(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes a configuration file sets, as ``ArchConfig`` fields."""
    skip = set(CONFIG_META) | set(config.get("reference_only", ()))
    out = {k: v for k, v in config.items() if k not in skip}
    if "pattern" in out:
        out["pattern"] = tuple(out["pattern"])
    return out


def make_cell(name: str, chips: int, config_file: str, traffic_file: str,
              cell_file: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    """A cell from explicit files (tests use this for their small cells)."""
    cfg = load_json(config_file)
    traffic = load_json(traffic_file)
    limits = load_json(cell_file)["limits"]
    bench = bench or {"end_to_end": [], "per_layer": []}
    extra = (set(traffic) - TRAFFIC_KEYS) | \
        (set(traffic.get("data", {})) - DATA_KEYS)
    if extra:
        raise ValueError(f"traffic {traffic_file}: keys {sorted(extra)} are "
                         f"not read by the harness")
    mesh = [int(x) for x in traffic["mesh"]]
    if mesh[0] * mesh[1] != chips:
        raise ValueError(f"cell {name}: mesh {mesh} does not use its "
                         f"{chips} chips")
    return Cell(name=name, chips=chips,
                config_name=os.path.splitext(os.path.basename(config_file))[0],
                config=cfg,
                traffic_name=os.path.splitext(
                    os.path.basename(traffic_file))[0],
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell(name: str, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its files loaded."""
    bench = benchmark(root)
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    base = os.path.join(root, "bench")
    return make_cell(
        name, int(w["chips"]),
        os.path.join(base, "configs", w["config"] + ".json"),
        os.path.join(base, "traffic", w["traffic"] + ".json"),
        os.path.join(base, "workloads", name + ".json"), bench)
