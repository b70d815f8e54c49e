"""DLRM through the program's own entry points: ``build_dlrm``, the DLRM
app's flags and its table-parallel ``dlrm_strategy``."""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.references import dlrm as reference  # noqa: F401  (the runners read it)
from flexflow_tpu.config import FFConfig
from flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm, dlrm_strategy

COSTS = "dlrm"


def build_train(cfg: Dict[str, Any], traffic: Dict[str, Any], n_devices: int):
    """``(graph, FFConfig, strategy)`` as ``apps/dlrm.py`` builds them."""
    ffcfg = FFConfig.parse_args(list(traffic["flags"]))
    arch = DLRMConfig(
        sparse_feature_size=cfg["sparse_feature_size"],
        embedding_size=[cfg["rows_per_table"]] * cfg["num_tables"],
        mlp_bot=list(cfg["mlp_bot"]), mlp_top=list(cfg["mlp_top"]),
        arch_interaction_op=cfg["interaction"],
    )
    ff = build_dlrm(batch_size=ffcfg.batch_size, dlrm=arch, config=ffcfg)
    return ff, ffcfg, dlrm_strategy(n_devices, arch, shard_embeddings=ffcfg.shard_embeddings)


def leaf_spec(cfg: Dict[str, Any], traffic: Dict[str, Any]):
    return reference.leaf_spec(cfg)


def host_batches(cfg: Dict[str, Any], traffic: Dict[str, Any], seed: int, n: int,
                 batch: int) -> List[Dict[str, np.ndarray]]:
    """``n`` batches: uniform dense features, one uniform id a table a
    sample over the whole table, 0/1 labels."""
    rng = np.random.default_rng([int(seed), 1])
    out = []
    for _ in range(n):
        out.append({
            "dense_input": rng.random((batch, cfg["mlp_bot"][0]), dtype=np.float32),
            "sparse_input": rng.integers(0, cfg["rows_per_table"],
                                         size=(batch, cfg["num_tables"]), dtype=np.int32),
            "label": rng.integers(0, 2, size=(batch, 1)).astype(np.float32),
        })
    return out


def items_per_sample(traffic: Dict[str, Any]) -> int:
    return 1
