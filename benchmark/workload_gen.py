"""The one general traffic generator.  A mix is a data file of
parameters under ``traffic/``; nothing here knows a mix by name.

The length law is ``flexflow_tpu/serving/workload.py``'s
``_bounded_zipf`` (a zipf(alpha) draw, clamped, shifted to the range's
floor), copied so the yardstick cannot move with the program.  Where
that generator draws each length at random, this one takes the law's
quantiles, pairs and orders them by the mix's own fixed seed, and lets
``--seed`` draw only the tokens: a closed loop over a finite backlog
drains differently for every order (which requests come last decides
how long slots stand empty; measured, 8% between orders in tokens/s
against under 1% between two runs of one order), so the order is part
of the mix and every seed does the same work.
"""

from __future__ import annotations

from typing import Any, Dict, List

import numpy as np


def bounded_zipf_pmf(alpha: float, lo: int, hi: int) -> np.ndarray:
    """P(length = lo + k) for k = 0 .. hi-lo under ``_bounded_zipf``:
    zipf(alpha) on 1, 2, ... with everything past the range's width
    folded onto its last value."""
    if alpha <= 1.0:
        raise ValueError(f"zipf alpha must be > 1.0, got {alpha}")
    if hi < lo or lo < 1:
        raise ValueError(f"need 1 <= lo <= hi, got ({lo}, {hi})")
    width = hi - lo + 1
    k = np.arange(1, width + 1, dtype=np.float64)
    w = k ** (-alpha)
    # Tail mass past `width`, by the integral bounds' midpoint (exact to
    # ~1e-4 of the tail; the pmf is normalised after).
    tail = ((width + 0.5) ** (1.0 - alpha)) / (alpha - 1.0)
    w[-1] += tail
    return w / w.sum()


def zipf_quantiles(n: int, alpha: float, lo: int, hi: int) -> np.ndarray:
    """``n`` lengths at the law's quantiles ``(i + 0.5) / n``."""
    cdf = np.cumsum(bounded_zipf_pmf(alpha, lo, hi))
    q = (np.arange(n, dtype=np.float64) + 0.5) / n
    return (lo + np.searchsorted(cdf, q, side="left")).astype(np.int64).clip(lo, hi)


def closed_backlog(mix: Dict[str, Any], n: int, seed: int, vocab: int) -> List[Dict[str, Any]]:
    """``n`` requests ``{"id", "prompt", "max_new_tokens"}``.  Lengths
    and budgets are the quantiles of the mix's two laws, paired and
    ordered by the mix's own fixed ``pairing_seed``; ``seed`` draws the
    tokens."""
    p = mix["prompt_len"]
    b = mix["budget"]
    prompts = zipf_quantiles(n, p["alpha"], p["lo"], p["hi"])
    budgets = zipf_quantiles(n, b["alpha"], b["lo"], b["hi"])
    pair = np.random.default_rng([int(mix["pairing_seed"]), n])
    prompts = pair.permutation(prompts)
    budgets = pair.permutation(budgets)
    limit = int(mix["max_seq"])
    budgets = np.minimum(budgets, limit - prompts)
    order = pair.permutation(n)
    rng = np.random.default_rng([int(seed), 2])
    out = []
    for rid, j in enumerate(order):
        out.append({
            "id": rid,
            "prompt": rng.integers(0, vocab, size=int(prompts[j]), dtype=np.int32),
            "max_new_tokens": int(budgets[j]),
        })
    return out
