"""Analytic training-FLOPs formulas + MFU (reference utils/flops_utils.py:18-830).

``flops_per_token`` dispatches per architecture family the way the reference's
per-model formula table does:

- dense GQA decoders (llama lineage),
- MoE (active-expert counting, shared experts, dense prefix),
- MLA (DeepSeek lineage: low-rank q/kv projections, asymmetric qk/v head dims),
- DSv3.2 sparse attention (lightning indexer + top-k-limited score term),
- gated-DeltaNet hybrids (qwen3-next lineage: linear-attention layers cost
  state-size, not seq^2),
- Mamba2/SSD hybrids (nemotron-H lineage).

Train FLOPs = 3x forward (fwd + 2x bwd). MFU = achieved / peak, with the peak
read from the one table in observability/hlo_costs.py.
"""

from __future__ import annotations

from typing import Any

__all__ = ["flops_per_token", "vision_tower_flops", "mfu"]

def _getter(cfg: Any):
    if isinstance(cfg, dict):
        return lambda k, d=None: cfg.get(k, d)
    return lambda k, d=None: getattr(cfg, k, d)


def _dense_attn(get, seq_len: int) -> float:
    d = get("hidden_size")
    n = get("num_attention_heads")
    k = get("num_key_value_heads", n) or n
    h = get("head_dim") or d // n
    qkv = 2 * d * (n + 2 * k) * h
    o = 2 * n * h * d
    scores = 2 * 2 * seq_len * n * h  # QK^T + PV; full count like the reference
    return qkv + o + scores


def _mla_attn(get, seq_len: int) -> float:
    """MLA (reference flops_utils deepseek formulas): low-rank q/kv factors,
    qk_head_dim for scores, v_head_dim for values."""
    d = get("hidden_size")
    n = get("num_attention_heads")
    nope = get("qk_nope_head_dim")
    rope = get("qk_rope_head_dim")
    vh = get("v_head_dim")
    qk_hd = nope + rope
    q_rank = get("q_lora_rank")
    kv_rank = get("kv_lora_rank")
    if q_rank:
        q = 2 * d * q_rank + 2 * q_rank * n * qk_hd
    else:
        q = 2 * d * n * qk_hd
    kv = 2 * d * (kv_rank + rope) + 2 * kv_rank * n * (nope + vh)
    o = 2 * n * vh * d
    kv_len = seq_len
    topk = get("index_topk")
    if topk:
        # DSv3.2 sparse attention: scores limited to the top-k indexed keys, plus
        # the lightning indexer's own projections + full-length index scores
        kv_len = min(topk, seq_len)
        hi = get("index_n_heads") or 1
        di = get("index_head_dim") or qk_hd
        idx_proj = 2 * d * di + 2 * (q_rank or d) * hi * di + 2 * d * hi
        idx_scores = 2 * seq_len * hi * di  # the full-length scan lives HERE
        o += idx_proj + idx_scores
    # both score terms run over the (possibly top-k-limited) kv set
    scores = 2 * kv_len * n * qk_hd + 2 * kv_len * n * vh
    return q + kv + o + scores


def _linear_attn(get) -> float:
    """Gated DeltaNet layer (qwen3-next lineage): cost scales with state size
    (dk x dv per value head), not seq — the whole point of the hybrid."""
    d = get("hidden_size")
    hk = get("linear_num_key_heads")
    dk = get("linear_key_head_dim")
    hv = get("linear_num_value_heads")
    dv = get("linear_value_head_dim")
    conv = get("linear_conv_kernel_dim", 4) or 4
    proj = 2 * d * (2 * hk * dk + 2 * hv * dv)  # q,k + v,z
    ba = 2 * d * 2 * hv
    conv_f = 2 * (2 * hk * dk + hv * dv) * conv
    # delta rule per token: state decay + rank-1 update + readout over (dk, dv)
    state = 6 * hv * dk * dv
    out = 2 * hv * dv * d
    return proj + ba + conv_f + state + out


def _mamba2(get) -> float:
    """Mamba2/SSD layer (nemotron-H lineage)."""
    d = get("hidden_size")
    heads = get("mamba_num_heads") or get("n_mamba_heads") or 0
    hd = get("mamba_head_dim") or 64
    d_inner = heads * hd if heads else int((get("expand") or 2) * d)
    d_state = get("ssm_state_size") or get("state_size") or 128
    groups = get("n_groups") or get("mamba_n_groups") or 1
    d_conv = get("conv_kernel") or get("d_conv") or 4
    in_proj = 2 * d * (2 * d_inner + 2 * groups * d_state + (heads or d_inner // hd))
    conv = 2 * (d_inner + 2 * groups * d_state) * d_conv
    # SSD per token: state decay + input outer-product + readout over (hd, d_state)
    ssd = 6 * d_inner * d_state
    out_proj = 2 * d_inner * d
    return in_proj + conv + ssd + out_proj


def _layer_kinds(get, L: int) -> list[str]:
    """Per-layer kind: "attn" | "linear" | "mamba" | "mlp_only" | "moe_only"."""
    lt = get("layer_types")
    if lt:
        kinds = []
        for t in lt:
            t = str(t)
            if "linear" in t:
                kinds.append("linear")
            elif "mamba" in t or t == "M":
                kinds.append("mamba")
            else:
                kinds.append("attn")
        return kinds
    pattern = get("hybrid_override_pattern")
    if pattern:
        # nemotron-H style: one mixer a layer. M = mamba, * = attention, - = dense MLP,
        # E = MoE (Nemotron-3)
        by_char = {"M": "mamba", "*": "attn", "-": "mlp_only", "E": "moe_only"}
        kinds = [by_char[ch] for ch in pattern if ch in by_char]
        return kinds or ["attn"] * L
    if get("linear_num_key_heads") and get("full_attention_interval"):
        fi = int(get("full_attention_interval"))
        return ["attn" if (i + 1) % fi == 0 else "linear" for i in range(L)]
    return ["attn"] * L


def vision_tower_flops(cfg: Any) -> float:
    """Forward FLOPs for ONE image through a CLIP-style ViT tower.

    ``cfg`` is a CLIPVisionConfig-like object or HF ``vision_config`` dict.
    Patch embedding is the conv-as-matmul count (``num_patches`` projections of
    a ``3*patch^2`` pixel column); each of the ``num_hidden_layers`` encoder
    layers runs full MHA plus an UN-gated 2-matmul MLP (fc1/fc2 — not the
    3-matmul gated count dense decoders use) over ``num_patches + 1`` tokens
    (the CLS token attends too).
    """
    get = _getter(cfg)
    d = get("hidden_size")
    inter = get("intermediate_size")
    L = get("num_hidden_layers")
    patch = get("patch_size", 14) or 14
    image = get("image_size", 336) or 336
    num_patches = (image // patch) ** 2
    n_pos = num_patches + 1  # + CLS
    patch_embed = num_patches * 2 * (3 * patch * patch) * d
    per_token_attn = (2 * d * 3 * d) + (2 * d * d) + (2 * 2 * n_pos * d)
    per_token_mlp = 2 * 2 * d * inter
    return float(patch_embed + n_pos * L * (per_token_attn + per_token_mlp))


def flops_per_token(cfg: Any, seq_len: int, training: bool = True,
                    num_images: int = 1) -> float:
    """FLOPs per token for a decoder config (ours or an HF-config-like dict).

    VLM configs (llava lineage: a ``vision_config``/``text_config`` pair, or
    our LlavaConfig's ``vision``/``text``) count the decoder from the text
    config and amortize ``num_images`` vision-tower forwards over ``seq_len``
    tokens — so MFU on llava-style runs credits the vision compute instead of
    pretending the image tokens were free.
    """
    get = _getter(cfg)
    vision = get("vision_config") or get("vision")
    text = get("text_config") or get("text")
    if text is not None:
        get = _getter(text)
    d = get("hidden_size")
    L = get("num_hidden_layers")
    v = get("vocab_size")
    inter = get("intermediate_size")

    is_mla = bool(get("kv_lora_rank"))
    kinds = _layer_kinds(get, L)
    if len(kinds) != L:
        # pattern tables may describe only the repeating block; tile to L
        kinds = (kinds * (L // max(len(kinds), 1) + 1))[:L]

    def attn_flops():
        return _mla_attn(get, seq_len) if is_mla else _dense_attn(get, seq_len)

    per_kind = {
        "attn": attn_flops(),
        "linear": _linear_attn(get) if get("linear_num_key_heads") else attn_flops(),
        "mamba": _mamba2(get),
        "mlp_only": 0.0,
        "moe_only": 0.0,
    }
    attn_total = sum(per_kind[k] for k in kinds)

    # MLP: dense or MoE (active experts + shared). Which layers carry an MLP is
    # family-dependent: nemotron-H-style patterns give mamba/attention layers NO
    # FFN (only the '-' slots have one), while layer_types hybrids (qwen-next,
    # gpt-oss) put an MLP in every layer.
    pattern = get("hybrid_override_pattern")
    if pattern:
        n_mlp_layers = kinds.count("mlp_only")
    else:
        n_mlp_layers = L
    n_routed = get("num_experts") or get("n_routed_experts") or 0
    if pattern and "E" in pattern:
        # Nemotron-3: the E layers are the MoE, the - layers a dense MLP; relu2 FFNs have two
        # matrices, not three. A LatentMoE's routed experts are 2 x latent x width each and
        # its two latent projections are met once a token; router and shared expert read
        # the full width. A layer that holds a share of the experts (``router_n_experts``)
        # meets ``top_k x held / all`` of them a token if routing is even.
        mats = 2 if get("mlp_hidden_act") == "relu2" else 3
        top_k = get("num_experts_per_tok") or 1
        router = get("router_n_experts") or n_routed
        latent = get("moe_latent_size") or 0
        moe_inter = get("moe_intermediate_size") or inter
        shared_inter = (get("n_shared_experts") or 0) * (
            get("moe_shared_expert_intermediate_size") or moe_inter)
        moe_mlp = (2 * d * router + (2 * 2 * d * latent if latent else 0)
                   + top_k * n_routed / router * mats * 2 * (latent or d) * moe_inter
                   + mats * 2 * d * shared_inter)
        mlp_total = n_mlp_layers * mats * 2 * d * inter + kinds.count("moe_only") * moe_mlp
    elif n_routed:
        top_k = get("num_experts_per_tok") or get("top_k") or 1
        moe_inter = get("moe_intermediate_size") or inter
        shared = get("n_shared_experts") or 0
        dense_layers = get("first_k_dense_replace") or 0
        moe_mlp = 3 * 2 * d * moe_inter * (top_k + shared)
        dense_mlp = 3 * 2 * d * inter
        mlp_total = dense_layers * dense_mlp + (n_mlp_layers - dense_layers) * moe_mlp
    else:
        mlp_total = n_mlp_layers * 3 * 2 * d * inter

    fwd = attn_total + mlp_total + 2 * d * v
    if vision is not None:
        fwd += vision_tower_flops(vision) * max(int(num_images), 0) / float(seq_len)
    return 3.0 * fwd if training else fwd


def mfu(tokens_per_sec: float, flops_per_tok: float, device_kind: str,
        n_devices: int = 1) -> float | None:
    """Model FLOPs utilization in [0,1] against the one peak table
    (observability/hlo_costs). ``None`` on a CPU, which has no peak; an unknown
    device kind raises."""
    from automodel_tpu.observability.hlo_costs import device_specs

    spec = device_specs(device_kind)
    if spec is None:
        return None
    achieved = tokens_per_sec * flops_per_tok / 1e12
    return achieved / (spec.peak_bf16_tflops * n_devices)
