"""Operations and bytes DLRM's row kernels need, from shapes alone.

A step names ``batch * tables`` rows; with the tables split over the
chips each chip owns ``1 / chips`` of them.  ``gather_rows`` reads each
row once and writes it once; ``scatter_add_rows`` reads the row and its
update and writes the row back.  The adds are ``d`` flops a row.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple


def kernel_cost(kind: str, rctx: Dict[str, Any], calls: int) -> Tuple[float, float]:
    cfg, res = rctx["config"], rctx["result"]
    rows = res["batch"] * cfg["num_tables"] / rctx["cell"]["chips"]
    row_bytes = cfg["sparse_feature_size"] * 4
    if kind == "gather_rows":
        return 0.0, 2.0 * rows * row_bytes * calls
    if kind == "scatter_add_rows":
        return float(rows * cfg["sparse_feature_size"] * calls), 3.0 * rows * row_bytes * calls
    raise KeyError(kind)


def train_flops_per_item(cfg: Dict[str, Any], traffic: Dict[str, Any]) -> float:
    """FLOPs of forward and backward for one sample: ``6`` a weight of
    the two MLPs."""
    n = sum(a * b for ln in (cfg["mlp_bot"], cfg["mlp_top"]) for a, b in zip(ln, ln[1:]))
    return 6.0 * n
