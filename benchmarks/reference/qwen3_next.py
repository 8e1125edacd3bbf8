"""Plain float32 Qwen3-Next hybrid decoder, block by block.

Written from the published ``config.json`` keys and the ``modeling_qwen3_next`` equations
(Qwen3-Next-80B-A3B; gated delta rule: Yang et al., arXiv 2412.06464). Every layer is
``h <- h + mixer(N(h))``, ``h <- h + moe(N(h))`` with ``N`` the ZERO-CENTRED RMSNorm,
``x / rms(x) * (1 + w)``; every ``full_attention_interval``-th layer's mixer is gated full
attention, every other one a gated DeltaNet:

- gated DeltaNet. ``q, k`` (``Hk`` key heads of ``dk``), ``v, z`` (``Hv`` value heads of
  ``dv``), ``b, a`` (a value head each) are linear in the input; ``[q | k | v]`` pass a
  causal depthwise conv of ``taps`` taps, no bias, then SiLU; value head j reads key head
  ``j // (Hv / Hk)``; ``q, k <- x / sqrt(sum x^2 + 1e-6)``; ``beta = sigmoid(b)``;
  ``g = -exp(A_log) softplus(a + dt_bias)``. The rule, a head, state ``S`` (dk x dv):
  ``S_t = exp(g_t) S_{t-1}``; ``S_t += beta_t k_t (v_t - S_t^T k_t)^T``;
  ``o_t = S_t^T q_t / sqrt(dk)``. It runs TOKEN BY TOKEN, as written: not in the chunked
  form with its triangular solve that the program uses. It is checkpointed every
  ``_RULE_CHUNK`` tokens, so that its backward holds one chunk's states and not a
  sequence's (4096 states of 2 MB a layer otherwise). Then the GATED RMSNorm over each
  head, ``w * o / rms(o) * silu(z)`` (weight NOT zero-centred), and the out projection.
- gated full attention. ``q`` and a gate of the same shape, ``k``, ``v``; zero-centred
  RMSNorm over each head's q and k; rotary embedding (rotate-half) on the first
  ``head_dim x partial_rotary_factor`` dims of each head; causal softmax attention at
  ``1 / sqrt(head_dim)``; the output times ``sigmoid(gate)``, elementwise; out projection.
- MoE. ``p = softmax(u W_r^T)`` over all routed experts, the ``num_experts_per_tok``
  largest, renormalised over the picks where ``norm_topk_prob``; SwiGLU experts; one
  shared SwiGLU expert times ``sigmoid(u w_g)``, a scalar a token. A loop over the experts
  HELD HERE: ``num_experts`` of the router's ``router_n_experts``, from
  ``first_held_expert`` on (all of them when the configuration states no share). What the
  other experts would add is left out, as in the program: that partial result is the
  layer's output. The normalisation is over all the picks, held or not.

Final zero-centred RMSNorm, untied head, mean cross-entropy in row blocks. Left out: the
multi-token-prediction head (the published modeling code loads none of it). The conv is
``taps`` shifted adds. No kernel, no cache, nothing imported from the program or from
``transformers``; every matmul at ``Precision.HIGHEST``; the recurrence is elementwise
products and sums, exact in float32.

Parameters (``x @ W`` everywhere, heads as their own axis). DeltaNet layer: ``attn_norm``,
``wq``/``wk (D, Hk, dk)``, ``wv``/``wz (D, Hv, dv)``, ``wb``/``wa (D, Hv)``,
``conv_w (2 Hk dk + Hv dv, taps)`` over ``[q | k | v]``, ``dt_bias``/``a_log (Hv,)``,
``gated_norm (dv,)``, ``wo (Hv, dv, D)``. Full layer: ``attn_norm``, ``wq``/``wg (D, n, h)``,
``wk``/``wv (D, k, h)``, ``q_norm``/``k_norm (h,)``, ``wo (n, h, D)``. Both: ``mlp_norm``,
``router (E_all, D)``, ``experts_gate_up (E_held, D, 2F)`` (gate columns first),
``experts_down (E_held, F, D)``, ``shared_gate``/``shared_up (D, Fs)``, ``shared_down (Fs, D)``,
``shared_expert_gate (D, 1)``. Inits are the harness's three: zero-centred norms ``zeros``,
``gated_norm`` and ``dt_bias`` ``ones`` (published), ``a_log`` ``normal`` (A about 1 where
the published code draws it from (0, 16): the configuration's ``assumed`` says what that
does to the state's reach).

The protocol this module answers is in ``benchmarks/README.md``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.harness import gemm_costs
from benchmarks.harness import kernel_costs as costs

IGNORE = -100
_HEAD_ROWS = 1024  # rows of logits held at once
_RULE_CHUNK = 64   # tokens between the states the recurrence keeps for its backward
_GROUP_OF = {"linear": "linear_layers", "full": "full_layers"}


def dims(m: dict) -> dict:
    L = m["num_hidden_layers"]
    if m.get("layer_types"):
        kinds = ["full" if t == "full_attention" else "linear" for t in m["layer_types"]]
    else:
        every = m.get("full_attention_interval", 4)
        kinds = ["full" if (i + 1) % every == 0 else "linear" for i in range(L)]
    if len(kinds) != L:
        raise ValueError(f"{len(kinds)} layer kinds for num_hidden_layers {L}")
    h = m["head_dim"]
    return dict(
        kinds=kinds, D=m["hidden_size"], V=m["vocab_size"], eps=m["rms_norm_eps"],
        Hk=m["linear_num_key_heads"], dk=m["linear_key_head_dim"],
        Hv=m["linear_num_value_heads"], dv=m["linear_value_head_dim"],
        taps=m["linear_conv_kernel_dim"],
        conv=2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
        + m["linear_num_value_heads"] * m["linear_value_head_dim"],
        n=m["num_attention_heads"], k=m["num_key_value_heads"], h=h,
        rot=int(h * m.get("partial_rotary_factor", 1.0)), theta=float(m["rope_theta"]),
        E_all=m.get("router_n_experts", m["num_experts"]), E=m["num_experts"],
        first=m.get("first_held_expert", 0), K=m["num_experts_per_tok"],
        F=m["moe_intermediate_size"], Fs=m["shared_expert_intermediate_size"],
        norm_topk=bool(m.get("norm_topk_prob", True)),
    )


def _layer_shapes(d: dict, kind: str) -> dict:
    D = d["D"]
    if kind == "linear":
        mixer = {"wq": ((D, d["Hk"], d["dk"]), "normal"), "wk": ((D, d["Hk"], d["dk"]), "normal"),
                 "wv": ((D, d["Hv"], d["dv"]), "normal"), "wz": ((D, d["Hv"], d["dv"]), "normal"),
                 "wb": ((D, d["Hv"]), "normal"), "wa": ((D, d["Hv"]), "normal"),
                 "conv_w": ((d["conv"], d["taps"]), "normal"),
                 "dt_bias": ((d["Hv"],), "ones"), "a_log": ((d["Hv"],), "normal"),
                 "gated_norm": ((d["dv"],), "ones"), "wo": ((d["Hv"], d["dv"], D), "normal")}
    else:
        n, k, h = d["n"], d["k"], d["h"]
        mixer = {"wq": ((D, n, h), "normal"), "wg": ((D, n, h), "normal"),
                 "wk": ((D, k, h), "normal"), "wv": ((D, k, h), "normal"),
                 "q_norm": ((h,), "zeros"), "k_norm": ((h,), "zeros"),
                 "wo": ((n, h, D), "normal")}
    return {"attn_norm": ((D,), "zeros"), **mixer, "mlp_norm": ((D,), "zeros"),
            "router": ((d["E_all"], D), "normal"),
            "experts_gate_up": ((d["E"], D, 2 * d["F"]), "normal"),
            "experts_down": ((d["E"], d["F"], D), "normal"),
            "shared_gate": ((D, d["Fs"]), "normal"), "shared_up": ((D, d["Fs"]), "normal"),
            "shared_down": ((d["Fs"], D), "normal"), "shared_expert_gate": ((D, 1), "normal")}


def block_shapes(m: dict) -> dict[str, dict[str, tuple[tuple[int, ...], str]]]:
    d = dims(m)
    blocks = {"embed": {"embed": ((d["V"], d["D"]), "normal")}}
    for i, kind in enumerate(d["kinds"]):
        blocks[f"layer_{i}"] = _layer_shapes(d, kind)
    blocks["head"] = {"final_norm": ((d["D"],), "zeros"), "lm_head": ((d["D"], d["V"]), "normal")}
    return blocks


def layer_groups(m: dict) -> dict[str, list[int]]:
    """Two stacks, in the order of the program's: a layer's place in its group is its
    place among the layers of its kind."""
    kinds = dims(m)["kinds"]
    return {_GROUP_OF[kind]: [i for i, k in enumerate(kinds) if k == kind]
            for kind in ("linear", "full") if kind in kinds}


def _counts(d: dict) -> dict[str, int]:
    return {kind: d["kinds"].count(kind) for kind in ("linear", "full")}


def matrix_params_per_token(m: dict) -> dict[str, float]:
    """Matrix parameters one token is multiplied by. Of the routed experts a token meets
    those of its ``K`` that are held here: ``K x held / all`` of them if routing is even."""
    d = dims(m)
    c = _counts(d)
    D, L = d["D"], len(d["kinds"])
    delta_in = D * (2 * d["Hk"] * d["dk"] + 2 * d["Hv"] * d["dv"] + 2 * d["Hv"])
    return {
        "delta_net_projections": c["linear"] * (delta_in + d["Hv"] * d["dv"] * D),
        "delta_net_conv": c["linear"] * d["conv"] * d["taps"],
        "attention_projections": c["full"] * (3 * D * d["n"] * d["h"] + 2 * D * d["k"] * d["h"]),
        "router": L * d["E_all"] * D,
        "shared_expert": L * (3 * D * d["Fs"] + D),
        "routed_experts": L * d["K"] * d["E"] / d["E_all"] * 3 * D * d["F"],
        "head": D * d["V"],
    }


def _rule_flops_per_token(d: dict) -> float:
    """Forward, one DeltaNet layer, an element of the ``Hv x dk x dv`` state: the decay
    (1), ``S^T k`` (2), the rank-one update (2) and the read-out ``S^T q`` (2)."""
    return 7.0 * d["Hv"] * d["dk"] * d["dv"]


def score_flops_per_token(m: dict, seq_len: int) -> float:
    """Causal QK^T and PV of the full-attention layers (a token at t meets t + 1 keys) and
    the recurrence's own arithmetic of the DeltaNet layers, forward and backward (3 x)."""
    d = dims(m)
    c = _counts(d)
    attention = c["full"] * 12.0 * d["n"] * d["h"] * (seq_len + 1) / 2
    return attention + c["linear"] * 3.0 * _rule_flops_per_token(d)


def parameter_count(m: dict) -> int:
    """Every parameter held here (the held experts, the sliced vocabulary, norms)."""
    total = 0
    for leaves in block_shapes(m).values():
        for shape, _ in leaves.values():
            n = 1
            for s in shape:
                n *= s
            total += n
    return total


def kernel_costs(m: dict, rows: int, seq_len: int) -> dict[str, dict[str, float]]:
    """Operations and bytes of one optimizer step over ``rows`` sequences, by kernel.

    ``flash_attention``: the full-attention layers, full causal, at their ``head_dim``.
    ``expert_gemms``: the held experts' gate/up and down GEMMs over the rows routed here if
    routing is even (``harness/gemm_costs.py``). ``gated_delta``: the recurrence alone
    (what the program runs under ``delta_rule``), token by token: its arithmetic forward
    and twice that backward; forward it reads q, k (a key head each: a kernel need not
    repeat them), v, g, beta and writes o once, backward it reads those and do and writes
    their five gradients, two bytes an element."""
    d = dims(m)
    c = _counts(d)
    tokens = rows * seq_len
    L = len(d["kinds"])
    out = {"expert_gemms": gemm_costs.expert_gemms_step(
        tokens * d["K"] * d["E"] / d["E_all"], d["D"], d["F"], d["E"], L)}
    if c["full"]:
        out["flash_attention"] = costs.flash_attention_step(rows, seq_len, d["n"], d["k"],
                                                            d["h"], c["full"])
    if c["linear"]:
        inputs = 2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"] + 2 * d["Hv"]  # q k v g beta
        o = d["Hv"] * d["dv"]
        elements = (inputs + o) + (inputs + o + inputs)  # forward; backward
        out["gated_delta"] = {"flops": c["linear"] * 3.0 * _rule_flops_per_token(d) * tokens,
                              "bytes": float(c["linear"] * tokens * elements * 2)}
    return out


# ---- the model


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    """Zero-centred: the stored weight is the scale's distance from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def delta_rule(q, k, v, g, beta, chunk: int = _RULE_CHUNK, state=None):
    """q, k (B, S, H, dk) already normalised, v (B, S, H, dv), g, beta (B, S, H) ->
    o (B, S, H, dv) and the last state (B, H, dk, dv). One token at a time; the state is
    kept at every ``chunk`` tokens and the steps between are run again in the backward."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"{S} tokens do not divide into chunks of {chunk}")
    scale = dk ** -0.5

    def token(state, args):
        q_t, k_t, v_t, g_t, b_t = args  # (B,H,dk) (B,H,dk) (B,H,dv) (B,H) (B,H)
        state = state * jnp.exp(g_t)[..., None, None]
        seen = jnp.sum(state * k_t[..., None], axis=-2)          # S^T k: (B,H,dv)
        delta = (v_t - seen) * b_t[..., None]
        state = state + k_t[..., None] * delta[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2) * scale

    @jax.checkpoint
    def one_chunk(state, args):
        return jax.lax.scan(token, state, args)

    def chunked(a):  # (B, S, ...) -> (S / chunk, chunk, B, ...)
        return jnp.moveaxis(a, 1, 0).reshape(S // chunk, chunk, B, *a.shape[2:])

    if state is None:
        state = jnp.zeros((B, H, dk, dv), jnp.float32)
    state, o = jax.lax.scan(one_chunk, state, tuple(chunked(a) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o.reshape(S, B, H, dv), 0, 1), state


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _delta_net(p, u, d):
    B, S, _ = u.shape
    Hk, dk, Hv, dv, taps = d["Hk"], d["dk"], d["Hv"], d["dv"], d["taps"]
    q = _mm("bsd,dhk->bshk", u, p["wq"]).reshape(B, S, Hk * dk)
    k = _mm("bsd,dhk->bshk", u, p["wk"]).reshape(B, S, Hk * dk)
    v = _mm("bsd,dhk->bshk", u, p["wv"]).reshape(B, S, Hv * dv)
    z = _mm("bsd,dhk->bshk", u, p["wz"])
    beta = jax.nn.sigmoid(_mm("bsd,dh->bsh", u, p["wb"]))
    g = -jnp.exp(p["a_log"]) * jax.nn.softplus(_mm("bsd,dh->bsh", u, p["wa"]) + p["dt_bias"])
    padded = jnp.pad(jnp.concatenate([q, k, v], -1), ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j of the published conv1d weight multiplies the input taps - 1 - j tokens back
    mixed = jax.nn.silu(sum(padded[:, j:j + S] * p["conv_w"][:, j] for j in range(taps)))
    q, k, v = jnp.split(mixed, [Hk * dk, 2 * Hk * dk], axis=-1)
    q = jnp.repeat(_l2(q.reshape(B, S, Hk, dk)), Hv // Hk, axis=2)
    k = jnp.repeat(_l2(k.reshape(B, S, Hk, dk)), Hv // Hk, axis=2)
    o, _ = delta_rule(q, k, v.reshape(B, S, Hv, dv), g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + d["eps"]) * p["gated_norm"]
    return _mm("bshk,hkd->bsd", o * jax.nn.silu(z), p["wo"])


def _rope(x, theta, rot):
    """x (B, S, heads, h): rotate-half over the first ``rot`` dims, positions 0..S-1."""
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv  # (S, rot/2)
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    head, rest = x[..., :rot], x[..., rot:]
    turned = jnp.concatenate([-head[..., rot // 2:], head[..., : rot // 2]], -1)
    return jnp.concatenate([head * cos + turned * sin, rest], -1)


def _full_attention(p, u, d):
    """One (batch row, key/value head) at a time, so that one group's S x S scores exist."""
    B, S, _ = u.shape
    n, kv, h = d["n"], d["k"], d["h"]
    q = _rope(_rms(_mm("bsd,dnh->bsnh", u, p["wq"]), p["q_norm"], d["eps"]), d["theta"], d["rot"])
    k = _rope(_rms(_mm("bsd,dkh->bskh", u, p["wk"]), p["k_norm"], d["eps"]), d["theta"], d["rot"])
    v = _mm("bsd,dkh->bskh", u, p["wv"])
    gate = jax.nn.sigmoid(_mm("bsd,dnh->bsnh", u, p["wg"]))
    r = n // kv
    qg = q.reshape(B, S, kv, r, h).transpose(0, 2, 3, 1, 4).reshape(B * kv, r, S, h)
    kg = k.transpose(0, 2, 1, 3).reshape(B * kv, S, h)
    vg = v.transpose(0, 2, 1, 3).reshape(B * kv, S, h)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args
        s = _mm("gqh,sh->gqs", qi, ki) * (h ** -0.5)
        return _mm("gqs,sh->gqh", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vi)

    out = jax.lax.map(one, (qg, kg, vg)).reshape(B, kv, r, S, h)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, S, n, h)
    return _mm("bsnh,nhd->bsd", out * gate, p["wo"])


def routed_experts(p, t, d):
    """t (T, D) -> the part of the routed experts' result that the experts held here give."""
    probs = jax.nn.softmax(_mm("td,ed->te", t, p["router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, d["K"])
    if d["norm_topk"]:
        top = top / top.sum(-1, keepdims=True)  # over all the picks, held here or not
    weight = jnp.zeros_like(probs).at[jnp.arange(t.shape[0])[:, None], idx].set(top)
    held = weight[:, d["first"]:d["first"] + d["E"]].T  # (E_held, T): the experts here
    F = d["F"]

    @jax.checkpoint
    def one(y, args):
        w_gu, w_dn, w_e = args
        both = _mm("td,df->tf", t, w_gu)
        act = jax.nn.silu(both[:, :F]) * both[:, F:]
        return y + _mm("tf,fd->td", act, w_dn) * w_e[:, None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(t),
                             (p["experts_gate_up"], p["experts_down"], held))
    return routed


def shared_expert(p, t):
    """What every chip of the layer computes alike: the gated shared expert."""
    act = jax.nn.silu(_mm("td,df->tf", t, p["shared_gate"])) * _mm("td,df->tf", t, p["shared_up"])
    gate = jax.nn.sigmoid(_mm("td,do->to", t, p["shared_expert_gate"]))  # a scalar a token
    return _mm("tf,fd->td", act, p["shared_down"]) * gate


def _moe(p, u, d):
    t = u.reshape(-1, u.shape[-1])
    return (routed_experts(p, t, d) + shared_expert(p, t)).reshape(u.shape)


_MIXER = {"linear": _delta_net, "full": _full_attention}


def embed_block(p, ids):
    return p["embed"][ids]


def layer_block(p, x, *, m: dict, kind: str):
    d = dims(m)
    x = x + _MIXER[kind](p, _rms(x, p["attn_norm"], d["eps"]), d)
    return x + _moe(p, _rms(x, p["mlp_norm"], d["eps"]), d)


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
    m = json.loads(m_key)
    head = functools.partial(head_block, m=m)

    def head_grad(p, x, labels):
        loss, (gp, gx) = jax.value_and_grad(head, argnums=(0, 1))(p, x, labels)
        return loss, gp, gx

    def embed_grad(p, ids, gx):
        return {"embed": jnp.zeros_like(p["embed"]).at[ids].add(gx)}

    fns = dict(embed=jax.jit(embed_block), head_grad=jax.jit(head_grad),
               embed_grad=jax.jit(embed_grad))
    for kind in _MIXER:
        layer = functools.partial(layer_block, m=m, kind=kind)

        def layer_vjp(p, x, gy, layer=layer):
            _, pull = jax.vjp(layer, p, x)
            return pull(gy)

        fns[kind] = jax.jit(layer)
        fns[kind + "_vjp"] = jax.jit(layer_vjp)
    return fns


def loss_and_grads(blocks: dict, ids, labels, *, m: dict, on_grad):
    """One forward and backward sweep. ``on_grad(block_name, grads)`` is called once per
    block, last block first, with that block's gradient; the block's parameters may be
    replaced inside the call. Returns the loss."""
    fns = _jitted(json.dumps(m, sort_keys=True))
    kinds = dims(m)["kinds"]
    xs = [fns["embed"](blocks["embed"], ids)]
    for i, kind in enumerate(kinds):
        xs.append(fns[kind](blocks[f"layer_{i}"], xs[-1]))
    loss, gp, gx = fns["head_grad"](blocks["head"], xs.pop(), labels)
    on_grad("head", gp)
    for i in reversed(range(len(kinds))):
        gp, gx = fns[kinds[i] + "_vjp"](blocks[f"layer_{i}"], xs.pop(), gx)
        on_grad(f"layer_{i}", gp)
    on_grad("embed", fns["embed_grad"](blocks["embed"], ids, gx))
    return loss
