"""The flash kernel's output and log-sum-exp are named residuals: the remat rung that
promises to keep the attention's output (``mlp_attn_dots``) keeps the kernel's own, and
the backward pass does not run the forward kernel again. Interpret mode on the CPU: the
jaxpr says how often each kernel is called, ``saved_residuals`` what crosses the remat
boundary, and the gradients are those of the layer without ``jax.checkpoint``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.ad_checkpoint import checkpoint_name

from automodel_tpu.models.common.backend import BackendConfig
from automodel_tpu.ops.attention import sharded_attention
from automodel_tpu.ops.pallas.flash_attention import LANES

B, S, D, HIDDEN = 2, 64, 16, 48

# name -> (q heads, kv heads, segment ids, a sink, the backward kernels)
CASES = {
    "fused": (4, 2, False, False, "fused"),
    "split": (4, 2, False, False, "split"),
    "segment_ids": (4, 2, True, False, "fused"),
    "gqa_groups_4": (8, 2, False, False, "fused"),
    "sink": (4, 2, False, True, "fused"),
    "sink_segment_ids_split": (4, 1, True, True, "split"),
}


def _layer_and_inputs(n, nk, segmented, sink):
    keys = iter(jax.random.split(jax.random.key(n * 7 + nk), 8))
    w = {
        "wq": jax.random.normal(next(keys), (HIDDEN, n * D)) * 0.2,
        "wk": jax.random.normal(next(keys), (HIDDEN, nk * D)) * 0.2,
        "wv": jax.random.normal(next(keys), (HIDDEN, nk * D)) * 0.2,
        "wo": jax.random.normal(next(keys), (n * D, HIDDEN)) * 0.2,
    }
    if sink:
        w["sinks"] = jax.random.normal(next(keys), (n,))
    x = jax.random.normal(next(keys), (B, S, HIDDEN))
    seg = None
    if segmented:
        seg = jnp.concatenate([jnp.full((B, 24), 1, jnp.int32), jnp.full((B, S - 24), 2, jnp.int32)], 1)

    def layer(w, x):
        q = (x @ w["wq"]).reshape(B, S, n, D)
        k = checkpoint_name((x @ w["wk"]).reshape(B, S, nk, D), "attn_k")
        v = checkpoint_name((x @ w["wv"]).reshape(B, S, nk, D), "attn_v")
        out = sharded_attention(q, k, v, rules=None, causal=True, segment_ids_q=seg,
                                sinks=w.get("sinks"), backend="flash_interpret")
        return jnp.sum(jnp.tanh(x + out.reshape(B, S, n * D) @ w["wo"]))

    return layer, w, x


def _kernel_calls(jaxpr) -> dict[str, int]:
    names = re.findall(r"name=(flash_attention_\w+)", str(jaxpr))
    return {k: names.count(k) for k in set(names)}


@pytest.mark.parametrize("policy,fwd_calls", [("mlp_attn_dots", 1), ("none", 2)])
@pytest.mark.parametrize("case", list(CASES))
def test_the_forward_kernel_runs_once_where_its_residuals_are_saved(
        case, policy, fwd_calls, monkeypatch):
    n, nk, segmented, sink, bwd = CASES[case]
    monkeypatch.setenv("AUTOMODEL_FLASH_FUSED_BWD", "1" if bwd == "fused" else "0")
    layer, w, x = _layer_and_inputs(n, nk, segmented, sink)
    remat = BackendConfig(remat_policy=policy).layer_remat(layer)

    calls = _kernel_calls(jax.make_jaxpr(jax.value_and_grad(remat))(w, x))
    backward = "flash_attention_bwd" if bwd == "fused" else "flash_attention_bwd_dq"
    assert calls.pop("flash_attention_fwd") == fwd_calls
    assert calls.pop(backward) == 1
    assert calls == ({} if bwd == "fused" else {"flash_attention_bwd_dkv": 1})

    # the same deterministic kernel's numbers, kept or made again: nothing may differ
    want = jax.grad(layer, argnums=(0, 1))(w, x)
    got = jax.grad(remat, argnums=(0, 1))(w, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    shapes = [tuple(aval.shape) for aval, _ in saved_residuals(remat, w, x)]
    # the log-sum-exp never crosses on its 128 lanes
    assert (B * n, S, LANES) not in shapes
    out_sized = [s for s in shapes if int(np.prod(s)) == B * S * n * D]
    if policy == "mlp_attn_dots":
        # ONE copy of the output, as the output projection reads it, and one lane of the lse
        assert out_sized == [(B, S, n, D)]
        assert shapes.count((B * n, S)) == 1
    else:
        assert out_sized == [] and (B * n, S) not in shapes


def test_without_remat_the_residual_lse_is_one_lane_too():
    """``remat_policy: full`` (no ``jax.checkpoint``): the custom VJP's own residuals."""
    layer, w, x = _layer_and_inputs(4, 2, False, False)
    _, vjp = jax.vjp(layer, w, x)
    shapes = [tuple(leaf.shape) for leaf in jax.tree.leaves(vjp) if hasattr(leaf, "shape")]
    assert (B * 4, S) in shapes and (B * 4, S, LANES) not in shapes


def test_the_xla_path_names_its_output_once():
    """Off the kernel the einsum's result carries ``attn_out``: the rung keeps it there
    as it did when the decoder named it."""
    def layer(q, k, v):
        return jnp.sum(jnp.tanh(sharded_attention(q, k, v, rules=None, backend="xla")))

    q = jax.random.normal(jax.random.key(0), (B, S, 4, D))
    kv = jax.random.normal(jax.random.key(1), (B, S, 2, D))
    remat = BackendConfig(remat_policy="mlp_attn_dots").layer_remat(layer)
    kept = [tuple(aval.shape) for aval, why in saved_residuals(remat, q, kv, kv)
            if "from the argument" not in str(why)]
    assert kept == [(B, S, 4, D)]
