"""8B-geometry layer-slice microbench (VERDICT r4 weak #3): full Llama-3-8B
can't train in bf16 on one 16GB chip, so the README's north-star #1 number is
a FLOPs-ratio extrapolation from the 1B proxy that ASSUMES MFU holds at 8B
geometry. This pins that assumption: a 4-layer slice with the exact 8B layer
dims (hidden 4096, inter 14336, 32 q / 8 kv heads, head_dim 128, vocab
128256) trains at seq 4096 on-chip, and its measured MFU is compared to the
1B bench's. Layer math dominates (the embed/head share is scaled out in the
FLOPs count), so slice MFU ~ full-model MFU at this geometry.

Run on the chip: PYTHONPATH=. python tools/bench_8b_slice.py
"""

from __future__ import annotations

import json


def main():
    import jax

    from bench import _measure, device_peak_tflops, llama_flops_per_token
    from automodel_tpu.models.llama.model import LlamaConfig

    # exact 8B layer geometry, 4-layer slice, tied head to fit 16GB
    cfg = LlamaConfig(
        vocab_size=128256,
        hidden_size=4096,
        intermediate_size=14336,
        num_hidden_layers=4,
        num_attention_heads=32,
        num_key_value_heads=8,
        head_dim=128,
        rope_theta=500000.0,
        tie_word_embeddings=True,
        max_position_embeddings=131072,
    )
    tps = _measure(cfg, seq_len=4096, micro_batch=1, n_steps=10)

    device = str(jax.devices()[0])
    peak = device_peak_tflops(jax.devices()[0].device_kind)
    f_tok = llama_flops_per_token(cfg, 4096)
    mfu = tps * f_tok / 1e12 / peak
    # the extrapolation target: full 8B at the slice's MFU
    cfg8b = LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8,
        head_dim=128,
    )
    f_8b = llama_flops_per_token(cfg8b, 4096)
    print(json.dumps({
        "metric": "llama-8B-geometry 4-layer slice (bf16, seq 4096)",
        "value": round(tps, 1),
        "unit": "tokens/s/chip",
        "extra": {
            "slice_mfu": round(mfu, 4),
            "model_tflops_per_sec": round(tps * f_tok / 1e12, 1),
            "implied_8b_tokens_per_sec": round(mfu * peak * 1e12 / f_8b, 1),
            "assumed_peak_tflops": peak,
            "device": device,
        },
    }))


if __name__ == "__main__":
    main()
