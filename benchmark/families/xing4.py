"""The Xing4.0 block family (the DeepSeek-V3 block round a residual of
four hyper-connected streams, a compressed query, YaRN positions)
through the program's own entry points: the graph
``models/transformer.py::build_lm`` builds from the configuration's keys,
the serving executor.  The benchmark's side of the family — the weight
recipe and the reference — is named here and lives in ``references/``;
its three kernels are the DeepSeek-V3 family's, and so are their costs."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.references import xing4 as reference  # noqa: F401  (the runners read it)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_lm

COSTS = "deepseek_v3"


def build_serve(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    """``(graph, FFConfig)`` as ``apps/serve.py --model-config`` builds them."""
    ffcfg = FFConfig.parse_args(list(traffic["flags"]))
    return build_lm(cfg, traffic["slots"], traffic["max_seq"], ffcfg), ffcfg


def leaf_spec(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    return reference.leaf_spec(cfg)
