"""HF architecture-name -> model family registry (reference _transformers/registry.py:33).

The reference scans components/models/*/model.py for classes; here registration is
explicit and lazy (import strings) so importing the registry stays cheap.
"""

from __future__ import annotations

import importlib

__all__ = ["MODEL_REGISTRY", "resolve_model_class", "register_model"]

# architecture name (HF config.json "architectures"[0]) -> "module:Class"
MODEL_REGISTRY: dict[str, str] = {
    "LlamaForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    "Qwen2ForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    "Qwen3ForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    "MistralForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # Granite = llama + four mup-style static scalars, read straight from config
    # (embedding/residual/attention multipliers + logits_scaling)
    "GraniteForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # SmolLM3 = llama + per-layer NoPE (no_rope_layers via layer_flags bit 1)
    "SmolLM3ForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # Olmo2/3 = llama + post-sublayer norms + whole-projection qk-RMSNorm
    # (norm_placement="post", qk_norm_whole; Olmo3 adds per-layer sliding via
    # layer_types, which the lineage already carries)
    "Olmo2ForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    "Olmo3ForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # Cohere (Command R) = llama + mean-centered LN + parallel attn||mlp block
    # + interleaved rope + multiplicative logit_scale (+ per-head qk-LN on R+)
    "CohereForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # Cohere2 (Command R7B) adds the 3:1 sliding pattern with rope ONLY on
    # sliding layers (NoPE full-attention layers via no_rope_layers)
    "Cohere2ForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # Arcee (AFM) = llama + ungated relu^2 MLP
    "ArceeForCausalLM": "automodel_tpu.models.llama.model:LlamaForCausalLM",
    # GLM-4 dense = llama + sandwich norms + interleaved partial rope + fused
    # gate_up checkpoints (split by its adapter); old GLM (glm-4-9b-chat-hf) is
    # the same minus the sandwich norms and rides the same adapter
    "Glm4ForCausalLM": "automodel_tpu.models.glm4.model:Glm4ForCausalLM",
    "GlmForCausalLM": "automodel_tpu.models.glm4.model:Glm4ForCausalLM",
    "MixtralForCausalLM": "automodel_tpu.models.mixtral.model:MixtralForCausalLM",
    # Phi-3 lineage is llama-shaped with fused checkpoint tensors + longrope
    "Phi3ForCausalLM": "automodel_tpu.models.phi3.model:Phi3ForCausalLM",
    "Gemma2ForCausalLM": "automodel_tpu.models.gemma.model:GemmaForCausalLM",
    "Gemma3ForCausalLM": "automodel_tpu.models.gemma.model:GemmaForCausalLM",
    "Gemma3ForConditionalGeneration": "automodel_tpu.models.gemma.model:GemmaForCausalLM",
    "Ministral3ForCausalLM": "automodel_tpu.models.mistral3.model:Ministral3ForCausalLM",
    "Qwen3MoeForCausalLM": "automodel_tpu.models.qwen3_moe.model:Qwen3MoeForCausalLM",
    "GptOssForCausalLM": "automodel_tpu.models.gpt_oss.model:GptOssForCausalLM",
    "DeepseekV3ForCausalLM": "automodel_tpu.models.deepseek_v3.model:DeepseekV3ForCausalLM",
    "DeepseekV2ForCausalLM": "automodel_tpu.models.deepseek_v3.model:DeepseekV3ForCausalLM",
    "DeepseekV32ForCausalLM": "automodel_tpu.models.deepseek_v32.model:DeepseekV32ForCausalLM",
    # Kimi-K2 ships DeepseekV3 architecture in its config.json (reference kimi support)
    "KimiK2ForCausalLM": "automodel_tpu.models.deepseek_v3.model:DeepseekV3ForCausalLM",
    # GLM4-MoE-Lite is MLA attention + GLM gating — same param/weight surface as DSv3
    "Glm4MoeLiteForCausalLM": "automodel_tpu.models.deepseek_v3.model:DeepseekV3ForCausalLM",
    "Glm4MoeForCausalLM": "automodel_tpu.models.glm4_moe.model:Glm4MoeForCausalLM",
    "MiniMaxM2ForCausalLM": "automodel_tpu.models.minimax_m2.model:MiniMaxM2ForCausalLM",
    "Qwen3NextForCausalLM": "automodel_tpu.models.qwen3_next.model:Qwen3NextForCausalLM",
    "Qwen3_5MoeForConditionalGeneration": "automodel_tpu.models.qwen3_5_moe.model:Qwen3_5MoeForCausalLM",
    "Qwen3_5MoeForCausalLM": "automodel_tpu.models.qwen3_5_moe.model:Qwen3_5MoeForCausalLM",
    "GPT2LMHeadModel": "automodel_tpu.models.gpt2.model:GPT2LMHeadModel",
    "NemotronHForCausalLM": "automodel_tpu.models.nemotron_v3.model:NemotronHForCausalLM",
    # Falcon-H1: a Mamba-2 mixer and a GQA mixer side by side in every block, muP scalars
    "FalconH1ForCausalLM": "automodel_tpu.models.falcon_h1.model:FalconH1ForCausalLM",
    "Step3p5ForCausalLM": "automodel_tpu.models.step3p5.model:Step3p5ForCausalLM",
    "NemotronV3ForCausalLM": "automodel_tpu.models.nemotron_v3.model:NemotronHForCausalLM",
    "LlavaForConditionalGeneration": "automodel_tpu.models.llava.model:LlavaForConditionalGeneration",
    "Qwen3VLMoeForConditionalGeneration": "automodel_tpu.models.qwen3_vl_moe.model:Qwen3VLMoeForConditionalGeneration",
    "KimiVLForConditionalGeneration": "automodel_tpu.models.kimivl.model:KimiVLForConditionalGeneration",
    "KimiK25VLForConditionalGeneration": "automodel_tpu.models.kimi_k25_vl.model:KimiK25VLForConditionalGeneration",
    "NemotronParseForConditionalGeneration": "automodel_tpu.models.nemotron_parse.model:NemotronParseForConditionalGeneration",
    "Qwen3OmniMoeThinkerForConditionalGeneration": "automodel_tpu.models.qwen3_omni_moe.model:Qwen3OmniMoeThinkerForConditionalGeneration",
    "Qwen3OmniMoeForConditionalGeneration": "automodel_tpu.models.qwen3_omni_moe.model:Qwen3OmniMoeThinkerForConditionalGeneration",
    "LlamaBidirectionalModel": "automodel_tpu.models.llama_bidirectional.model:LlamaBidirectionalModel",
}


def register_model(architecture: str, target: str) -> None:
    MODEL_REGISTRY[architecture] = target


def resolve_model_class(architecture: str):
    target = MODEL_REGISTRY.get(architecture)
    if target is None:
        import difflib

        near = difflib.get_close_matches(architecture, MODEL_REGISTRY, n=3, cutoff=0.5)
        hint = (
            f" Closest supported: {near} — if the architecture is a config-level "
            "variant of one of these, register an alias with "
            "automodel_tpu.models.registry.register_model(arch, target)."
            if near
            else ""
        )
        raise KeyError(
            f"architecture {architecture!r} is not supported; known: "
            f"{sorted(MODEL_REGISTRY)}.{hint}"
        )
    mod_name, cls_name = target.split(":")
    return getattr(importlib.import_module(mod_name), cls_name)
