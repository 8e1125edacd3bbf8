"""MoE block: gate + grouped experts + shared experts (reference MoE,
components/moe/layers.py:515).

The reference overlaps shared experts with the EP all-to-all on a separate CUDA stream
(layers.py:615-630); under XLA the scheduler overlaps independent ops inside one jit
program, so the block is just straight-line code.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from automodel_tpu.moe.config import MoEConfig
from automodel_tpu.moe.experts import (
    capacity_experts_apply,
    expert_logical_axes,
    grouped_experts_apply,
    init_expert_params,
)
from automodel_tpu.ops.fp8 import project
from automodel_tpu.moe.gate import (
    fake_balanced_route,
    gate_logical_axes,
    init_gate_params,
    route,
)

__all__ = ["init_moe_params", "moe_logical_axes", "moe_forward", "cast_moe_compute_params",
           "to_expert_width", "from_expert_width"]


def cast_moe_compute_params(moe_params: dict, dtype) -> dict:
    """Cast MoE block params to the compute dtype, keeping the routing correction bias
    fp32 (bf16 rounding flips expert selection, reference layers.py:262-266)."""
    return {
        sub: {
            k: (v if sub == "gate" and k == "score_correction_bias" else v.astype(dtype))
            for k, v in leaves.items()
        }
        if isinstance(leaves, dict)
        else leaves.astype(dtype)
        for sub, leaves in moe_params.items()
    }


def init_moe_params(cfg: MoEConfig, key: jax.Array, dtype=jnp.float32, init_std: float = 0.02) -> dict:
    kg, ke, ks, ksg = jax.random.split(key, 4)
    params = {
        "gate": init_gate_params(cfg, kg, dtype, init_std),
        "experts": init_expert_params(cfg, ke, dtype, init_std),
    }
    if cfg.latent_dim:
        kd, ku = jax.random.split(jax.random.fold_in(key, 1))
        shape = (cfg.dim, cfg.latent_dim)
        params["latent"] = {
            "w_down": (jax.random.normal(kd, shape, jnp.float32) * init_std).astype(dtype),
            "w_up": (jax.random.normal(ku, shape[::-1], jnp.float32) * init_std).astype(dtype),
        }
    if cfg.n_shared_experts > 0:
        D, I = cfg.dim, cfg.shared_inter_dim
        keys = jax.random.split(ks, 3)
        shared = {
            "w_up": (jax.random.normal(keys[0], (D, I), jnp.float32) * init_std).astype(dtype),
            "w_down": (jax.random.normal(keys[1], (I, D), jnp.float32) * init_std).astype(dtype),
        }
        if cfg.shared_expert_activation == "swiglu":
            shared["w_gate"] = (jax.random.normal(keys[2], (D, I), jnp.float32) * init_std).astype(dtype)
        params["shared_experts"] = shared
        if cfg.shared_expert_gate:
            params["shared_expert_gate"] = (
                jax.random.normal(ksg, (D, 1), jnp.float32) * init_std
            ).astype(dtype)
    return params


def moe_logical_axes(cfg: MoEConfig) -> dict:
    axes = {"gate": gate_logical_axes(cfg), "experts": expert_logical_axes(cfg)}
    if cfg.latent_dim:
        axes["latent"] = {"w_down": ("embed", None), "w_up": (None, "embed")}
    if cfg.n_shared_experts > 0:
        shared = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
        if cfg.shared_expert_activation == "swiglu":
            shared["w_gate"] = ("embed", "mlp")
        axes["shared_experts"] = shared
        if cfg.shared_expert_gate:
            axes["shared_expert_gate"] = ("embed", None)
    return axes


def to_expert_width(cfg: MoEConfig, params: dict, x: jnp.ndarray, linear: str = "default"):
    """LatentMoE: tokens go down to ``cfg.latent_dim`` before the routed experts (no bias,
    norm or activation). Without a latent, x as it is."""
    if not cfg.latent_dim:
        return x
    with jax.named_scope("moe_latent_proj"):
        return project(x, params["latent"]["w_down"], 1, linear)


def from_expert_width(cfg: MoEConfig, params: dict, y: jnp.ndarray, linear: str = "default"):
    """LatentMoE: the combined experts' output goes back up to ``cfg.dim``."""
    if not cfg.latent_dim:
        return y
    with jax.named_scope("moe_latent_proj"):
        return project(y, params["latent"]["w_up"], 1, linear)


def _shared_experts_forward(cfg: MoEConfig, params: dict, x: jnp.ndarray,
                            linear: str = "default") -> jnp.ndarray:
    sp = params["shared_experts"]
    up = project(x, sp["w_up"], 1, linear)
    if cfg.shared_expert_activation == "swiglu":
        act = jax.nn.silu(project(x, sp["w_gate"], 1, linear)) * up
    else:  # relu2
        act = jnp.square(jax.nn.relu(up))
    z = project(act, sp["w_down"], 1, linear)
    if "shared_expert_gate" in params:
        z = jax.nn.sigmoid(x @ params["shared_expert_gate"]) * z
    return z


def moe_forward(
    cfg: MoEConfig,
    params: dict,
    x: jnp.ndarray,  # (B, S, D) or (T, D)
    token_mask: jnp.ndarray | None = None,  # (B, S) or (T,) bool; True = valid
    *,
    training: bool = True,
    dispatcher: str = "ragged",  # "ragged" (dropless) | "capacity" (GShard one-hot)
    capacity_factor: float = 1.25,
    fake_balanced_gate: bool = False,
    fake_gate_noise: float = 0.0,
    experts_backend: str = "ragged_dot",  # "ragged_dot" | "pallas" (ragged only)
    linear: str = "default",  # backend.linear: the latent and shared projections' GEMMs
):
    """Returns ``(y, aux_loss|None, expert_load (E,))``; y has x's shape.

    Router and shared experts read the full width; with ``cfg.latent_dim`` the routed
    experts read ``x W_down`` and their combined output goes through ``W_up``. With
    ``cfg.n_held_experts`` the routed part is that of the experts held here (the router
    still scores and picks among all ``n_routed_experts``; ``expert_load`` counts all).

    aux_loss is *unscaled* — the recipe adds ``cfg.aux_loss_coeff * aux_loss``
    (x num-tokens correction) to the train loss, replacing the reference's autograd-hook
    scaler (megatron/moe_utils.py MoEAuxLossAutoScaler).
    """
    shape = x.shape
    x2 = x.reshape(-1, cfg.dim)
    mask = None if token_mask is None else token_mask.reshape(-1)

    # named scopes label the trace's routing vs expert-GEMM vs shared regions
    # (autonvtx parity for the MoE block internals)
    with jax.named_scope("moe_gate"):
        if fake_balanced_gate:
            weights, indices, aux_loss, expert_load = fake_balanced_route(
                cfg, x2, noise=fake_gate_noise
            )
        else:
            weights, indices, aux_loss, expert_load = route(
                cfg, params["gate"], x2, mask, training=training
            )

    xe = to_expert_width(cfg, params, x2, linear)
    with jax.named_scope("moe_experts"):
        if dispatcher == "capacity":
            if not cfg.holds_all_experts:
                raise ValueError("the one-hot capacity dispatch lays out every routed expert: "
                                 "a layer that holds a share of them takes the ragged path")
            y = capacity_experts_apply(
                cfg, params["experts"], xe, weights, indices, mask, capacity_factor=capacity_factor
            )
        else:
            y = grouped_experts_apply(cfg, params["experts"], xe, weights, indices, mask,
                                      experts_backend=experts_backend)
    y = from_expert_width(cfg, params, y, linear)

    if cfg.n_shared_experts > 0:
        with jax.named_scope("moe_shared_experts"):
            y = y + _shared_experts_forward(cfg, params, x2, linear)

    return y.reshape(shape), aux_loss, expert_load
