"""Backend switchboard (reference BackendConfig, models/common/utils.py:139).

The reference toggles between TE/flex/SDPA attention, Triton/gmm experts, fused losses.
On TPU the choices collapse to: XLA einsum vs Pallas kernels, and how to rematerialize.
One config object threads through every model family.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

__all__ = ["BackendConfig"]

# policy name -> jax.checkpoint policy ("full" = no remat; None = remat everything)
_REMAT_POLICIES = {
    "none": None,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    # save ONLY the two fat MLP projections (gate/up): their matmuls are ~half a
    # layer's forward FLOPs, so keeping just them cuts the backward replay almost
    # as much as "dots" at a fraction of its footprint. The middle ground between
    # "none" (replay everything, minimal memory) and "dots" (replay nothing,
    # ~2.8x the activation footprint).
    "mlp_dots": jax.checkpoint_policies.save_only_these_names("mlp_gate", "mlp_up"),
    # half of mlp_dots: fits alongside losses that still materialize logits
    "mlp_gate_dot": jax.checkpoint_policies.save_only_these_names("mlp_gate"),
    # save only the post-activation (tokens*K, I) expert tensor — HALF of
    # mlp_gate_dot's (tokens*K, 2I) footprint for gated experts. The down-proj
    # backward reads it saved; only the gate_up GEMM + activation replay. The
    # MoE-tuned rung: with the Pallas grouped GEMM (custom VJP, no saved
    # intermediates of its own) this is the cheapest save that still skips the
    # fattest recompute.
    "mlp_act_dot": jax.checkpoint_policies.save_only_these_names("mlp_act"),
    # additionally keep k/v + the attention output: replay shrinks to the q
    # projection + elementwise (q is recomputed for the flash backward; saving it
    # too was measured 20MB over the 15.75G HBM line at the 1B bench shape).
    # ``attn_out`` is named where the output is made (ops/attention.py: the flash
    # kernel's custom VJP keeps it as its own residual, the einsum names its result),
    # ``attn_lse`` is one lane of the kernel's log-sum-exp, (batch*heads, seq)
    # float32: with both kept the backward pass does not run the forward kernel again
    "mlp_attn_dots": jax.checkpoint_policies.save_only_these_names(
        "mlp_gate", "mlp_up", "attn_k", "attn_v", "attn_out", "attn_lse"
    ),
    "full": "full",
}


@dataclasses.dataclass
class BackendConfig:
    """Compute-backend knobs shared by all model families.

    attention:    "xla" (einsum softmax) | "flash" (Pallas, TPU only)
    remat_policy: "none" (recompute every layer, the flash forward kernel included) | "dots" |
                  "mlp_dots" | "mlp_gate_dot" | "mlp_act_dot" | "mlp_attn_dots" (keeps gate/up,
                  k/v, the attention's output and log-sum-exp: one flash forward call a layer) |
                  "full" (save everything, no remat)
    scan_layers:  stack layer params and lax.scan over them (fast compiles, PP-friendly)
    dtype:        activation/param compute dtype (bf16 default; optimizer keeps fp32 master)
    """

    attention: str = "xla"
    # pass segment ids into the attention mask. True is always correct; False is
    # a fast path for RIGHT-PADDED UNPACKED batches, where causal masking alone
    # already stops real tokens from attending to pads (pads sit after every
    # real token; pad rows' outputs are loss-masked). Packed sequences NEED it
    # on — the recipe guards that combination.
    attention_segments: bool = True
    # "allgather": rely on XLA SPMD to gather k/v across the cp axis (always
    # correct). "ring": ppermute ring attention over cp (overlaps comm with
    # compute; full/causal GQA attention without sinks/soft-cap/traced windows)
    context_parallel: str = "allgather"
    # "default" (einsum) | "fp8" (e4m3/e5m2 dynamic scaling). fp8 covers the dense
    # attention/MLP projections; MoE expert GEMMs keep their own experts_backend.
    linear: str = "default"
    remat_policy: str = "none"
    scan_layers: bool = True
    dtype: str = "bfloat16"
    # MoE knobs (used by MoE families only). "ragged_dot" is XLA's native ragged
    # matmul (the megablocks/gmm equivalent); "pallas" routes the same sorted
    # layout through the blocked Pallas grouped GEMM (ops/pallas/grouped_gemm.py:
    # hand-scheduled tiles, fused custom-VJP backward, per-shape ragged_dot
    # fallback); "dense" is the GShard one-hot einsum path.
    experts_backend: str = "ragged_dot"  # "ragged_dot" | "pallas" | "dense"
    dispatcher: str = "dense"  # "dense" (GSPMD ragged/one-hot) | "a2a" (EP all_to_all)
    # a2a only: per-destination-rank send capacity = ep_capacity_factor * T * K / ep.
    # Overflow copies are dropped AND reported (stats["dropped_token_frac"]).
    ep_capacity_factor: float = 1.5
    # a2a only: split dispatch/combine into this many capacity slices so chunk
    # i's expert GEMM overlaps chunk i+1's all_to_all (XLA's latency-hiding
    # scheduler overlaps them once the dependency graph allows it). 1 = one
    # monolithic a2a. Token selection and dropped_frac are EXACT under any
    # chunk count (routing/capacity math happens before slicing).
    a2a_chunks: int = 1
    fake_balanced_gate: bool = False  # benchmark mode: uniform routing, no gate math
    fake_gate_noise: float = 0.0

    def __post_init__(self):
        if self.linear not in ("default", "fp8"):
            raise ValueError(f"unknown linear backend {self.linear!r} (default | fp8)")
        if self.context_parallel not in ("allgather", "ring"):
            raise ValueError(
                f"unknown context_parallel {self.context_parallel!r} (allgather | ring)"
            )
        if self.experts_backend not in ("ragged_dot", "pallas", "dense"):
            raise ValueError(
                f"unknown experts_backend {self.experts_backend!r} "
                "(ragged_dot | pallas | dense)"
            )
        if self.dispatcher not in ("dense", "a2a"):
            raise ValueError(f"unknown dispatcher {self.dispatcher!r} (dense | a2a)")
        if self.remat_policy not in _REMAT_POLICIES:
            raise ValueError(
                f"unknown remat_policy {self.remat_policy!r} (choose from {list(_REMAT_POLICIES)})"
            )
        if int(self.a2a_chunks) < 1:
            raise ValueError(f"a2a_chunks must be >= 1, got {self.a2a_chunks}")

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)

    def layer_remat(self, fn):
        """Wrap a layer fn with jax.checkpoint per the policy."""
        policy = _REMAT_POLICIES[self.remat_policy]
        if policy == "full":
            return fn
        return jax.checkpoint(fn, policy=policy)
