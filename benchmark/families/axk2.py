"""The A.X-K2 family (the DeepSeek-V3 block with a learned token selector
over the latent cache, a gate a head, gated norms and group-limited
routing over a share of the experts) through the program's own entry
points: the graph ``models/transformer.py::build_lm`` builds from the
configuration's keys, the serving executor.  The benchmark's side of the
family — the weight recipe, the reference, the costs — is named here
and lives in ``references/`` and ``costs/``."""

from __future__ import annotations

from typing import Any, Dict

from benchmark.references import axk2 as reference  # noqa: F401  (the runners read it)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_lm

COSTS = "axk2"


def build_serve(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    """``(graph, FFConfig)`` as ``apps/serve.py --model-config`` builds them."""
    ffcfg = FFConfig.parse_args(list(traffic["flags"]))
    return build_lm(cfg, traffic["slots"], traffic["max_seq"], ffcfg), ffcfg


def leaf_spec(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    return reference.leaf_spec(cfg)
