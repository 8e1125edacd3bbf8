"""On-chip MoE throughput bench (VERDICT r4 missing #5): a Qwen3-MoE-A3B-class
proxy scaled to one 16GB chip, measured under the reference's own benchmark
conditions (mock data, fake balanced gating, no grad clip —
/root/reference/docs/performance-summary.md:66-72), plus the a2a-vs-dense
dispatcher delta at ep=1.

``vs_baseline`` is MFU-normalized against the reference's Qwen3-MoE-30B row:
277 TFLOPs/s/GPU on H100 = 28.0% MFU vs 989 bf16 peak
(docs/performance-summary.md:16). Prints ONE JSON line. No result of it is
committed: the last record predated the current code and was removed.

Run on the chip: PYTHONPATH=. python tools/bench_moe_onchip.py
"""

from __future__ import annotations

import json
import time

import numpy as np

PROXY_CFG = {
    # qwen3-moe-A3B geometry scaled to a 16GB chip: same head/expert ratios
    # (top-4 of 32 experts, gqa 4:1), ~1B total / ~300M active params
    "architectures": ["Qwen3MoeForCausalLM"],
    "vocab_size": 32000, "hidden_size": 1024, "intermediate_size": 3072,
    "moe_intermediate_size": 384, "num_hidden_layers": 12,
    "num_attention_heads": 16, "num_key_value_heads": 4, "head_dim": 64,
    "num_experts": 32, "num_experts_per_tok": 4, "norm_topk_prob": True,
}


def measure(dispatcher: str, seq_len=2048, micro_batch=4, n_steps=10):
    import jax
    import jax.numpy as jnp
    import optax

    from automodel_tpu.models.auto import AutoModelForCausalLM
    from automodel_tpu.models.common.backend import BackendConfig
    from automodel_tpu.ops.losses import masked_cross_entropy
    from automodel_tpu.training.train_step import make_train_step

    from automodel_tpu.parallel.mesh import MeshContext, default_sharding_rules

    hf_cfg = dict(PROXY_CFG, max_position_embeddings=seq_len)
    backend = BackendConfig(
        dtype="bfloat16", attention="flash", remat_policy="mlp_attn_dots",
        attention_segments=False, dispatcher=dispatcher,
        fake_balanced_gate=True,  # the reference's measurement condition
    )
    # 1-device ep=1 mesh: the a2a dispatcher needs an ep axis; rules are
    # passed in BOTH modes so the comparison is constraint-for-constraint fair
    mesh = MeshContext(ep=1, dp_shard=1, world_size=1).build_mesh(jax.devices()[:1])
    rules = default_sharding_rules().with_mesh(mesh)
    model = AutoModelForCausalLM.from_config(hf_cfg, backend)
    params = model.init(jax.random.key(0), jnp.bfloat16)
    optimizer = optax.chain(optax.scale_by_factored_rms(), optax.scale(-1e-5))
    opt_state = jax.jit(optimizer.init)(params)

    def forward_loss(p, batch, num_label_tokens):
        logits, stats = model(p, batch["input_ids"], positions=batch["positions"],
                              segment_ids=batch["segment_ids"], rules=rules,
                              training=True)
        return (masked_cross_entropy(logits, batch["labels"], num_label_tokens),
                {"expert_load": stats["expert_load"]})

    step = jax.jit(make_train_step(forward_loss, optimizer), donate_argnums=(0, 1))
    rng = np.random.RandomState(0)
    ids = rng.randint(0, hf_cfg["vocab_size"], (1, micro_batch, seq_len)).astype(np.int32)
    batch = {
        "input_ids": jnp.asarray(ids),
        "labels": jnp.asarray(ids),
        "positions": jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32), ids.shape),
        "segment_ids": jnp.ones_like(jnp.asarray(ids)),
    }
    # TWO chained warmup steps, not one: some MoE param layouts (expert-weight
    # operands of ragged_dot) come back from the first donated step in a
    # different XLA layout than model.init produced, so the SECOND call
    # recompiles once (measured: 12.9s) before layouts reach a fixed point.
    # Timing after a single warmup would bill that compile to the steady state.
    for _ in range(3):
        params, opt_state, m = step(params, opt_state, batch)
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, m = step(params, opt_state, batch)
    jax.block_until_ready(m["loss"])
    dt = time.perf_counter() - t0
    return n_steps * micro_batch * seq_len / dt


def main():
    import jax

    from automodel_tpu.models.qwen3_moe.model import Qwen3MoeConfig
    from automodel_tpu.utils.flops import flops_per_token

    import gc

    seq_len = 2048
    tps_dense = measure("dense", seq_len=seq_len)
    gc.collect()  # free the dense leg's HBM before the a2a model compiles
    tps_a2a = measure("a2a", seq_len=seq_len)

    cfg = Qwen3MoeConfig.from_hf(dict(PROXY_CFG, max_position_embeddings=seq_len))
    f_tok = flops_per_token(cfg, seq_len)
    from bench import device_peak_tflops

    device = str(jax.devices()[0])
    peak = device_peak_tflops(jax.devices()[0].device_kind)
    mfu = tps_dense * f_tok / 1e12 / peak
    ref_mfu = 277.0 / 989.0  # reference Qwen3-MoE-30B on 8xH100

    print(json.dumps({
        "metric": "qwen3-moe-a3b-proxy SFT tokens/sec/chip (bf16, seq 2048, fake balanced gate)",
        "value": round(tps_dense, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / ref_mfu, 4),
        "extra": {
            "model_tflops_per_sec": round(tps_dense * f_tok / 1e12, 1),
            "mfu": round(mfu, 4),
            "flops_per_token_g": round(f_tok / 1e9, 2),
            "a2a_tokens_per_sec": round(tps_a2a, 1),
            "a2a_vs_dense": round(tps_a2a / tps_dense, 4),
            "dispatcher": "dense (a2a delta in a2a_vs_dense; ep=1 so a2a pays "
                          "bucketing overhead with no real ICI traffic)",
            "assumed_peak_tflops": peak,
            "device": device,
        },
    }))


if __name__ == "__main__":
    main()
