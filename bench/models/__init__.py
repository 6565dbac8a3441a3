"""A configuration's layers, found by name: ``bench/models/<model>.py``.

The configuration file's ``"model"`` key names the module; without it the
model is ``dense``.  A configuration with other layers comes with a module
of its own, and the harness (``bench/reference.py``, ``bench/weights.py``,
``bench/flops.py``) finds it here without an edit.  A module gives:

  leaves(cfg)                    every leaf (a tensor the benchmark draws
                                 from the seed): ``(name, shape, init std)``
  layer_leaves(cfg, l)           layer ``l``'s leaves in block order, named
                                 without the ``layer<l>.`` prefix
  layer_kind(cfg, l)             layers of one kind have the same leaves
                                 and one compiled reference block
  block(cfg, l, dot, W, h, cos, sin, shards) -> (h, aux)
                                 layer ``l``'s float32 forward pass; ``dot``
                                 is the reference's einsum, ``shards`` the
                                 (row groups, sequence pieces) each chip
                                 holds of the batch, ``aux`` the layer's
                                 term of the loss, weighted
  matmul_params(cfg)             parameters each token multiplies by
  attention_flops_per_token(cfg, seq)
  source(cfg, model, group, entry, q)
                                 where entry ``entry`` of flat row ``q`` of
                                 the program's buffer group ``group`` comes
                                 from: ``(leaf base name, layer or None,
                                 first index along the leaf's axis 0)``;
                                 ``q`` may be traced
  quant_sites(shapes, prefetch)  ``{group: (qwZ gathers, qgZ reduces)}`` a
                                 step issues, from the program's buffer
                                 shapes and ring depth

Nothing here imports the program.
"""
from __future__ import annotations

import importlib
import re
from typing import Dict

DEFAULT = "dense"


def load(cfg: Dict):
    """The module of a configuration's layers."""
    name = cfg.get("model", DEFAULT)
    if not re.fullmatch(r"[a-z][a-z0-9_]*", name):
        raise ValueError(f"model {name!r} is not a module name")
    return importlib.import_module(f"bench.models.{name}")
