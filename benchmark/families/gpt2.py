"""GPT-2 through the program's own entry points: the graph
``build_transformer_lm`` emits, the transformer app's strategy helper
and flags, the serving executor.  The benchmark's side of the family —
the weight recipe, the reference, the costs — is named here and lives
in ``references/`` and ``costs/``."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.references import gpt2 as reference  # noqa: F401  (the runners read it)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.transformer import build_transformer_lm, transformer_strategy

COSTS = "gpt2"


def _graph(cfg: Dict[str, Any], ffcfg, batch: int, seq: int):
    return build_transformer_lm(
        batch_size=batch, seq_len=seq, vocab_size=cfg["vocab_size"],
        d_model=cfg["n_embd"], num_heads=cfg["n_head"], num_layers=cfg["n_layer"],
        config=ffcfg,
    )


def build_train(cfg: Dict[str, Any], traffic: Dict[str, Any], n_devices: int):
    """``(graph, FFConfig, strategy)`` as ``apps/transformer.py`` builds them."""
    ffcfg = FFConfig.parse_args(list(traffic["flags"]))
    ff = _graph(cfg, ffcfg, ffcfg.batch_size, traffic["seq_len"])
    return ff, ffcfg, transformer_strategy(n_devices, num_layers=cfg["n_layer"])


def build_serve(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    """``(graph, FFConfig)`` as ``apps/serve.py`` builds them."""
    ffcfg = FFConfig.parse_args(list(traffic["flags"]))
    return _graph(cfg, ffcfg, traffic["slots"], traffic["max_seq"]), ffcfg


def leaf_spec(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    return reference.leaf_spec(cfg, traffic.get("seq_len") or traffic["max_seq"])


def host_batches(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int, n: int,
                 batch: int) -> List[Dict[str, np.ndarray]]:
    """``n`` batches of random token ids, every row different; the label
    of a position is the token after it."""
    rng = np.random.default_rng([int(seed), 1])
    out = []
    for _ in range(n):
        t = rng.integers(0, cfg["vocab_size"], size=(batch, traffic["seq_len"] + 1), dtype=np.int32)
        out.append({"tokens": np.ascontiguousarray(t[:, :-1]),
                    "label": np.ascontiguousarray(t[:, 1:])})
    return out


def items_per_sample(traffic: Dict[str, Any]) -> int:
    return int(traffic["seq_len"])
