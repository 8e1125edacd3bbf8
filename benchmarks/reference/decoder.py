"""Plain float32 decoder-only transformer, block by block.

Written from the published descriptions (Mistral 7B, Qwen3 technical report; the
Hugging Face ``modeling_mistral`` / ``modeling_qwen3_moe`` equations): token
embedding, pre-norm blocks of RMSNorm -> grouped-query causal attention with rotary
position embedding (rotate-half convention; Qwen3: RMSNorm over each head's q and k
before the rotation) -> residual -> RMSNorm -> SwiGLU MLP, or a softmax router that
keeps its top-k probabilities (renormalised where ``norm_topk_prob``) over plain
per-expert SwiGLU MLPs -> residual; final RMSNorm, untied output head, mean
cross-entropy over the labelled positions. No kernel, no cache, nothing imported
from the program. Every matmul runs at ``Precision.HIGHEST``: on a TPU a float32
matmul is otherwise done in bfloat16 passes.

A model is a list of blocks ``embed, layer_0 .. layer_{L-1}, head``. Each block is a
pure function of its own parameters, so a caller can take gradients one block at a
time and never hold more than one block's gradient (what fits beside 7.5 GB of
float32 parameters on a 16 GB chip).

Layout of the parameters (``x @ W`` everywhere, heads kept as their own axis):
``embed (V, D)``; per layer ``attn_norm (D,)``, ``wq (D, n, h)``, ``wk``/``wv (D, k, h)``,
``wo (n, h, D)``, optional ``q_norm``/``k_norm (h,)``, ``mlp_norm (D,)``, then either
``w_gate``/``w_up (D, F)``, ``w_down (F, D)`` or ``router (E, D)``,
``experts_gate_up (E, D, 2I)`` (gate columns first), ``experts_down (E, I, D)``;
``final_norm (D,)``, ``lm_head (D, V)``. Departure from the papers: none in the
mathematics.

This module is also the worked example of what a configuration's reference answers for
(``benchmarks/README.md``, "The reference's protocol"): the harness asks the module named
by the configuration's ``reference`` key (this one when the key is absent) for the
shapes, the layer groups, the sweep, the FLOP count's parts and the kernels' least work,
and looks nowhere else for anything that depends on the architecture.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.harness import gemm_costs
from benchmarks.harness import kernel_costs as costs

IGNORE = -100
_HEAD_ROWS = 1024  # rows of logits held at once


def dims(m: dict) -> dict:
    moe = "num_experts" in m
    return dict(
        L=m["num_hidden_layers"], D=m["hidden_size"], n=m["num_attention_heads"],
        k=m["num_key_value_heads"], h=m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"],
        V=m["vocab_size"], F=m["intermediate_size"], moe=moe,
        E=m.get("num_experts", 0), K=m.get("num_experts_per_tok", 0),
        I=m.get("moe_intermediate_size", 0),
        qk_norm=m["architectures"][0].startswith("Qwen3"),
        norm_topk=bool(m.get("norm_topk_prob", False)),
        eps=m["rms_norm_eps"], theta=m["rope_theta"],
    )


def block_shapes(m: dict) -> dict[str, dict[str, tuple[tuple[int, ...], str]]]:
    """``{block: {leaf: (shape, init)}}`` with ``init`` ``normal``, ``ones`` or ``zeros``;
    blocks ``embed``, ``layer_0 ..``, ``head``. Layers may differ in their leaves."""
    d = dims(m)
    D, n, k, h = d["D"], d["n"], d["k"], d["h"]
    layer = {"attn_norm": ((D,), "ones"), "wq": ((D, n, h), "normal"),
             "wk": ((D, k, h), "normal"), "wv": ((D, k, h), "normal"),
             "wo": ((n, h, D), "normal"), "mlp_norm": ((D,), "ones")}
    if d["qk_norm"]:
        layer |= {"q_norm": ((h,), "ones"), "k_norm": ((h,), "ones")}
    if d["moe"]:
        E, I = d["E"], d["I"]
        layer |= {"router": ((E, D), "normal"), "experts_gate_up": ((E, D, 2 * I), "normal"),
                  "experts_down": ((E, I, D), "normal")}
    else:
        F = d["F"]
        layer |= {"w_gate": ((D, F), "normal"), "w_up": ((D, F), "normal"),
                  "w_down": ((F, D), "normal")}
    blocks = {"embed": {"embed": ((d["V"], D), "normal")}}
    for i in range(d["L"]):
        blocks[f"layer_{i}"] = dict(layer)
    blocks["head"] = {"final_norm": ((D,), "ones"), "lm_head": ((D, d["V"]), "normal")}
    return blocks


def layer_groups(m: dict) -> dict[str, list[int]]:
    """``{group: [layer indices]}`` in the order of the program's stacks: the harness
    stacks each group's layers under ``<group>.<leaf>``. One stack here."""
    return {"layers": list(range(m["num_hidden_layers"]))}


def matrix_params_per_token(m: dict) -> dict[str, float]:
    """Matrix parameters one token is multiplied by, by part: attention projections, the
    dense MLP or the router plus the top-k experts (not all experts), the output head;
    nothing for the embedding lookup."""
    d = dims(m)
    attn = d["L"] * (2 * d["D"] * d["n"] * d["h"] + 2 * d["D"] * d["k"] * d["h"])
    if d["moe"]:
        mlp = d["L"] * d["K"] * 3 * d["D"] * d["I"]
        router = d["L"] * d["E"] * d["D"]
    else:
        mlp, router = d["L"] * 3 * d["D"] * d["F"], 0
    return {"attention_projections": attn, "mlp": mlp, "router": router,
            "head": d["D"] * d["V"]}


def score_flops_per_token(m: dict, seq_len: int) -> float:
    """QK^T and PV, forward and backward, causal (a token at position t meets t + 1
    keys): 3 x 4 x n x h x (S + 1) / 2 a layer."""
    d = dims(m)
    return d["L"] * 12.0 * d["n"] * d["h"] * (seq_len + 1) / 2


def parameter_count(m: dict) -> int:
    """Every parameter held (all experts, embedding, norms)."""
    d = dims(m)
    layer = 2 * d["D"] * d["n"] * d["h"] + 2 * d["D"] * d["k"] * d["h"] + 2 * d["D"]
    if d["qk_norm"]:
        layer += 2 * d["h"]
    if d["moe"]:
        layer += d["E"] * d["D"] + d["E"] * 3 * d["D"] * d["I"]
    else:
        layer += 3 * d["D"] * d["F"]
    return d["L"] * layer + 2 * d["V"] * d["D"] + d["D"]


def kernel_costs(m: dict, rows: int, seq_len: int) -> dict[str, dict[str, float]]:
    """Operations and bytes the model's kernels need for one optimizer step over ``rows``
    sequences of ``seq_len`` tokens, by kernel, from ``harness/kernel_costs.py`` and
    ``harness/gemm_costs.py``: every layer's attention full causal, every layer's
    experts where the model has them, the head's three GEMMs."""
    d = dims(m)
    tokens = rows * seq_len
    out = {"flash_attention": costs.flash_attention_step(rows, seq_len, d["n"], d["k"],
                                                         d["h"], d["L"]),
           "linear_ce": gemm_costs.linear_ce_step(tokens, d["D"], d["V"])}
    if d["moe"]:
        out["expert_gemms"] = gemm_costs.expert_gemms_step(tokens * d["K"], d["D"], d["I"],
                                                           d["E"], d["L"])
    return out


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x (B, S, heads, h): rotate-half convention, positions 0..S-1."""
    h = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # (S, h/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., h // 2:], x[..., : h // 2]], -1)
    return x * cos + rot * sin


def _attention(q, k, v):
    """q (B, S, n, h), k/v (B, S, kv, h) -> (B, S, n, h); one (batch row, kv head) at
    a time so that only one group's S x S scores exist."""
    B, S, n, h = q.shape
    kv = k.shape[2]
    g = n // kv
    qg = q.reshape(B, S, kv, g, h).transpose(0, 2, 3, 1, 4).reshape(B * kv, g, S, h)
    kg = k.transpose(0, 2, 1, 3).reshape(B * kv, S, h)
    vg = v.transpose(0, 2, 1, 3).reshape(B * kv, S, h)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args
        s = _mm("gqh,sh->gqs", qi, ki) * (h ** -0.5)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return _mm("gqs,sh->gqh", p, vi)

    out = jax.lax.map(one, (qg, kg, vg))  # (B*kv, g, S, h)
    return out.reshape(B, kv, g, S, h).transpose(0, 3, 1, 2, 4).reshape(B, S, n, h)


def _dense_mlp(p, x):
    gate = _mm("bsd,df->bsf", x, p["w_gate"])
    up = _mm("bsd,df->bsf", x, p["w_up"])
    return _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, p["w_down"])


def _moe_mlp(p, x, d):
    B, S, D = x.shape
    t = x.reshape(B * S, D)
    probs = jax.nn.softmax(_mm("td,ed->te", t, p["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, d["K"])
    if d["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[jnp.arange(t.shape[0])[:, None], idx].set(top)  # (T, E)
    I = d["I"]

    @jax.checkpoint
    def one(y, args):
        w_gu, w_dn, w_e = args
        hcat = _mm("td,df->tf", t, w_gu)
        act = jax.nn.silu(hcat[:, :I]) * hcat[:, I:]
        return y + _mm("tf,fd->td", act, w_dn) * w_e[:, None], None

    y, _ = jax.lax.scan(one, jnp.zeros_like(t),
                        (p["experts_gate_up"], p["experts_down"], weight.T))
    return y.reshape(B, S, D)


def embed_block(p, ids):
    return p["embed"][ids]


def layer_block(p, x, *, m: dict):
    d = dims(m)
    a = _rms(x, p["attn_norm"], d["eps"])
    q = _mm("bsd,dnh->bsnh", a, p["wq"])
    k = _mm("bsd,dkh->bskh", a, p["wk"])
    v = _mm("bsd,dkh->bskh", a, p["wv"])
    if d["qk_norm"]:
        q, k = _rms(q, p["q_norm"], d["eps"]), _rms(k, p["k_norm"], d["eps"])
    q, k = _rope(q, d["theta"]), _rope(k, d["theta"])
    x = x + _mm("bsnh,nhd->bsd", _attention(q, k, v), p["wo"])
    f = _rms(x, p["mlp_norm"], d["eps"])
    return x + (_moe_mlp(p, f, d) if "router" in p else _dense_mlp(p, f))


def head_block(p, x, labels, *, m: dict):
    """Mean cross-entropy over labels != IGNORE, in row blocks of the logits."""
    d = dims(m)
    t = _rms(x, p["final_norm"], d["eps"]).reshape(-1, d["D"])
    y = labels.reshape(-1)
    rows = min(_HEAD_ROWS, t.shape[0])
    if t.shape[0] % rows:
        raise ValueError(f"{t.shape[0]} rows do not divide into blocks of {rows}")

    @jax.checkpoint
    def one(args):
        tb, yb = args
        logits = _mm("td,dv->tv", tb, p["lm_head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(yb, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(yb != IGNORE, lse - picked, 0.0).sum()

    total = jax.lax.map(one, (t.reshape(-1, rows, d["D"]), y.reshape(-1, rows))).sum()
    return total / jnp.maximum((y != IGNORE).sum(), 1)


@functools.lru_cache(maxsize=None)
def _jitted(m_key: str):
    import json

    m = json.loads(m_key)
    layer = functools.partial(layer_block, m=m)
    head = functools.partial(head_block, m=m)

    def layer_vjp(p, x, gy):
        _, pull = jax.vjp(layer, p, x)
        return pull(gy)

    def head_grad(p, x, labels):
        loss, (gp, gx) = jax.value_and_grad(head, argnums=(0, 1))(p, x, labels)
        return loss, gp, gx

    def embed_grad(p, ids, gx):
        return {"embed": jnp.zeros_like(p["embed"]).at[ids].add(gx)}

    return dict(embed=jax.jit(embed_block), layer=jax.jit(layer),
                layer_vjp=jax.jit(layer_vjp), head_grad=jax.jit(head_grad),
                embed_grad=jax.jit(embed_grad))


def loss_and_grads(blocks: dict, ids, labels, *, m: dict, on_grad):
    """One forward and backward sweep. ``on_grad(block_name, grads)`` is called once per
    block, last block first, with that block's gradient; the block's parameters may be
    replaced inside the call. Returns the loss."""
    import json

    fns = _jitted(json.dumps(m, sort_keys=True))
    L = dims(m)["L"]
    xs = [fns["embed"](blocks["embed"], ids)]
    for i in range(L):
        xs.append(fns["layer"](blocks[f"layer_{i}"], xs[-1]))
    loss, gp, gx = fns["head_grad"](blocks["head"], xs.pop(), labels)
    on_grad("head", gp)
    for i in reversed(range(L)):
        gp, gx = fns["layer_vjp"](blocks[f"layer_{i}"], xs.pop(), gx)
        on_grad(f"layer_{i}", gp)
    on_grad("embed", fns["embed_grad"](blocks["embed"], ids, gx))
    return loss
