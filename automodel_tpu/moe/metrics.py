"""MoE expert-load-balance metrics (reference components/moe/load_balance_metrics.py).

The reference hooks Gate modules to stash per-layer loads and all-reduces them over dp;
here :func:`moe_forward` already returns per-layer ``expert_load`` arrays (globally
summed under pjit), so metrics are pure post-processing of a stacked (L, E) array.
"""

from __future__ import annotations

import numpy as np

from automodel_tpu.moe.experts import HELD_BLOCK_ROWS

__all__ = ["compute_load_balance_metrics", "held_rows_share", "held_row_blocks_share"]


def held_rows_share(expert_loads: np.ndarray, first_held: int, n_held: int) -> float:
    """Of the (token, expert) pairs the routers chose (``expert_loads`` (L, E) over all the
    routed experts), the share whose expert is one of the ``n_held`` held here from
    ``first_held`` on: what the held experts' GEMMs work on. ``n_held / E`` if routing is
    even, 1.0 for a layer that holds all its experts."""
    loads = np.asarray(expert_loads, np.float64)
    total = loads.sum()
    return float(loads[..., first_held : first_held + n_held].sum() / total) if total > 0 else 0.0


def held_row_blocks_share(expert_loads: np.ndarray, first_held: int, n_held: int, top_k: int) -> float:
    """How far the held share's block loop ran (``moe.experts.grouped_experts_apply``):
    blocks of ``HELD_BLOCK_ROWS`` rows run, ``sum_l ceil(held_rows_l / B)``, over the most
    that any routing of the same tokens runs, ``L * ceil(T * min(top_k, n_held) / B)``.
    1.0 when routing crowds the held experts, 0.0 when no row came. ``expert_loads``
    (L, E) is one call's a layer (``T = pairs / top_k``); summed over micro-batches, each
    of which rounds up on its own, the loop ran up to one block a call and layer more."""
    loads = np.atleast_2d(np.asarray(expert_loads, np.float64))
    held = loads[:, first_held : first_held + n_held].sum(axis=1)
    most = np.ceil(loads.sum(axis=1) / top_k * min(top_k, n_held) / HELD_BLOCK_ROWS).sum()
    return float(np.ceil(held / HELD_BLOCK_ROWS).sum() / most) if most > 0 else 0.0


def compute_load_balance_metrics(
    expert_loads: np.ndarray,  # (L, E) tokens routed per expert per MoE layer
    *,
    mode: str = "brief",
    top_k_experts: int = 5,
    prefix: str = "moe_load",
) -> dict[str, float]:
    """Scalar metrics dict for the metric logger / wandb.

    Utilization ratio = load / ideal (ideal = mean over experts); 1.0 is perfect
    balance, > 1 overloaded (reference _compute_expert_utilization semantics).
    ``brief`` emits aggregates + global top/bottom-k; ``detailed`` adds per-layer stats.
    """
    loads = np.asarray(expert_loads, np.float64)
    if loads.ndim == 1:
        loads = loads[None]
    L, E = loads.shape
    ideal = loads.mean(axis=1, keepdims=True)  # (L, 1)
    util = np.divide(loads, ideal, out=np.ones_like(loads), where=ideal > 0)

    per_layer_max = util.max(axis=1)
    per_layer_min = util.min(axis=1)
    per_layer_std = util.std(axis=1)
    zero_frac = (loads == 0).mean(axis=1)

    metrics = {
        f"{prefix}/max_util_mean": float(per_layer_max.mean()),
        f"{prefix}/max_util_max": float(per_layer_max.max()),
        f"{prefix}/min_util_mean": float(per_layer_min.mean()),
        f"{prefix}/util_std_mean": float(per_layer_std.mean()),
        f"{prefix}/zero_expert_frac": float(zero_frac.mean()),
    }

    mean_util = util.mean(axis=0)  # (E,) average across layers
    order = np.argsort(mean_util)
    k = min(top_k_experts, E)
    for rank, e in enumerate(order[::-1][:k]):
        metrics[f"{prefix}/top{rank}_expert{e}_util"] = float(mean_util[e])
    for rank, e in enumerate(order[:k]):
        metrics[f"{prefix}/bottom{rank}_expert{e}_util"] = float(mean_util[e])

    if mode == "detailed":
        for layer in range(L):
            metrics[f"{prefix}/layer{layer}/max_util"] = float(per_layer_max[layer])
            metrics[f"{prefix}/layer{layer}/min_util"] = float(per_layer_min[layer])
            metrics[f"{prefix}/layer{layer}/util_std"] = float(per_layer_std[layer])
    return metrics
