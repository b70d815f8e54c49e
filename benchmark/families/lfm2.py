"""The LFM2-MoE block family (a gated short convolution in three layers
of four, grouped-query attention with a head norm and rotary positions
in the fourth, leading dense layers, then expert layers under a sigmoid
router with a selection bias; a head tied to the token table) through
the program's own entry points: the graph
``models/transformer.py::build_lm`` builds from the configuration's keys,
the serving executor.  The benchmark's side of the family — the weight
recipe, the reference, the costs — is named here and lives in
``references/`` and ``costs/``."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.references import lfm2 as reference  # noqa: F401  (the runners read it)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_lm

COSTS = "lfm2"


def build_serve(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    """``(graph, FFConfig)`` as ``apps/serve.py --model-config`` builds them."""
    ffcfg = FFConfig.parse_args(list(traffic["flags"]))
    return build_lm(cfg, traffic["slots"], traffic["max_seq"], ffcfg), ffcfg


def leaf_spec(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    return reference.leaf_spec(cfg)
