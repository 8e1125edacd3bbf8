"""EP-dispatch microbench: dense GSPMD path vs explicit a2a (VERDICT r3 #6).

Default (no args): single TPU chip, ep=1 degenerate mesh — the all_to_all is a
self-copy, so the delta between the two dispatchers is exactly the a2a path's
bucketing overhead (one-hot-cumsum queue positions + (ep, cap, D) scatter
layout) with zero real ICI traffic in either. Measured on v5e: a2a 2.25x
slower (577ms vs 257ms/step).

``--ep 4 --devices 8`` (run under JAX_PLATFORMS=cpu with
--xla_force_host_platform_device_count=8): the multi-rank comparison on the
virtual mesh, where routing actually crosses ranks — measured a2a ~2.05x
FASTER than dense (1.77s vs 3.63s/step at the scaled-down shape the flag
selects). Prints one JSON line per dispatcher.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


def measure(dispatcher: str, *, ep=1, devices=1, seq_len=2048, micro_batch=4,
            n_steps=10):
    import jax
    import jax.numpy as jnp
    import optax

    from automodel_tpu.models.auto import AutoModelForCausalLM
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.ops.losses import masked_cross_entropy
    from automodel_tpu.parallel.mesh import MeshContext, default_sharding_rules
    from automodel_tpu.training.train_step import make_train_step

    ctx = MeshContext(ep=ep, dp_shard=devices // ep, world_size=devices)
    mesh = ctx.build_mesh(jax.devices()[:devices])
    rules = default_sharding_rules().with_mesh(mesh)
    if devices == 1:
        # qwen3-moe-A3B-ish proxy scaled to one 16GB chip
        hf_cfg = {
            "architectures": ["Qwen3MoeForCausalLM"],
            "vocab_size": 32000, "hidden_size": 1024, "intermediate_size": 3072,
            "moe_intermediate_size": 384, "num_hidden_layers": 12,
            "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 64,
            "num_experts": 32, "num_experts_per_tok": 4, "norm_topk_prob": True,
            "max_position_embeddings": seq_len,
        }
        backend = BackendConfig(dtype="bfloat16", attention="flash",
                                remat_policy="mlp_attn_dots", dispatcher=dispatcher)
    else:
        # virtual-CPU-mesh shape (fp32, xla attention — CPU has no pallas/bf16 win)
        seq_len, micro_batch = 256, 8
        hf_cfg = {
            "architectures": ["Qwen3MoeForCausalLM"],
            "vocab_size": 512, "hidden_size": 128, "intermediate_size": 256,
            "moe_intermediate_size": 64, "num_hidden_layers": 4,
            "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
            "num_experts": 16, "num_experts_per_tok": 4, "norm_topk_prob": True,
            "max_position_embeddings": seq_len,
        }
        backend = BackendConfig(dtype="float32", dispatcher=dispatcher)
    model = AutoModelForCausalLM.from_config(hf_cfg, backend)
    with mesh:
        params = model.init(jax.random.key(0), jnp.bfloat16)
        optimizer = optax.chain(optax.scale_by_factored_rms(), optax.scale(-1e-5))
        opt_state = jax.jit(optimizer.init)(params)

        def forward_loss(p, batch, n):
            # rules passed in BOTH modes (a2a needs the mesh; keeping the dense
            # path identical makes the comparison constraint-for-constraint fair)
            out, stats = model(
                p, batch["input_ids"], positions=batch["positions"],
                segment_ids=batch["segment_ids"],
                token_mask=batch["segment_ids"] != 0,
                rules=rules, training=True,
            )
            return masked_cross_entropy(out, batch["labels"], n), {
                "expert_load": stats["expert_load"]}

        step = jax.jit(make_train_step(forward_loss, optimizer), donate_argnums=(0, 1))
        rng = np.random.RandomState(0)
        ids = rng.randint(0, 32000, (1, micro_batch, seq_len)).astype(np.int32)
        batch = {
            "input_ids": jnp.asarray(ids), "labels": jnp.asarray(ids),
            "positions": jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32), ids.shape),
            "segment_ids": jnp.ones_like(jnp.asarray(ids)),
        }
        for _ in range(3):  # warmup + compile
            params, opt_state, m = step(params, opt_state, batch)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            params, opt_state, m = step(params, opt_state, batch)
        float(m["loss"])
        dt = (time.perf_counter() - t0) / n_steps
    tokens = micro_batch * seq_len
    return {"dispatcher": dispatcher, "ep": ep, "devices": devices,
            "seq_len": seq_len, "step_time_ms": round(dt * 1e3, 2),
            "tokens_per_sec": round(tokens / dt, 1)}


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--ep", type=int, default=1)
    ap.add_argument("--devices", type=int, default=1)
    args = ap.parse_args()
    for disp in ("dense", "a2a"):
        print(json.dumps(measure(disp, ep=args.ep, devices=args.devices)))
