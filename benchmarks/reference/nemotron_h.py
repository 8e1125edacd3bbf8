"""Plain float32 Nemotron-H / Nemotron-3 hybrid decoder, block by block.

Written from the published ``config.json`` keys and the ``modeling_nemotron_h`` equations
(Nemotron-H, arXiv 2504.03624; Mamba-2, arXiv 2405.21060; the LatentMoE of Nemotron-3).
Every layer is pre-norm with ONE mixer, ``h <- h + mixer(RMSNorm(h))``, the kind given
per layer by ``hybrid_override_pattern``:

- ``M`` Mamba-2 mixer. ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)`` (causal,
  depthwise, ``conv_kernel`` taps); ``[x | B | C] = xBC``; ``dt = softplus(dt + dt_bias)``;
  ``A = -exp(A_log)`` a head; for head j in group ``j // (heads / n_groups)``:
  ``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``;
  ``y = w * GroupRMSNorm(y * silu(z))`` over ``n_groups`` groups; out ``y W_out``.
  The recurrence runs TOKEN BY TOKEN, as written: not in the chunked dual form the
  program uses. It is checkpointed every ``chunk_size`` tokens, so that its backward
  holds one chunk's states and not a sequence's.
- ``*`` causal grouped-query attention with NO rotary embedding (the published model
  applies none; ``rope_theta`` is unread).
- ``E`` LatentMoE. ``s = sigmoid(u W_r^T)`` over all routed experts; the
  ``num_experts_per_tok`` largest of ``s + b`` (``b``: the score-correction buffer);
  ``w = routed_scaling_factor * s_sel / sum(s_sel)``; ``l = u W_dn``;
  ``r = sum_k w_k W2_e relu(W1_e l)^2``; out ``r W_up + W_d relu(W_u u)^2`` (one shared
  expert). A loop over the experts HELD HERE: ``n_routed_experts`` of the router's
  ``router_n_experts``, from ``first_held_expert`` on (all of them when the
  configuration states no share). What the other experts would add is left out, as in
  the program: that partial result is the layer's output.

Final RMSNorm, untied head, mean cross-entropy. Left out: the multi-token-prediction
module (no layer of the stack; the configuration sets ``num_nextn_predict_layers`` 0).
No kernel, no cache, nothing imported from the program; every matmul at
``Precision.HIGHEST``.

Parameters (``x @ W`` everywhere). Mamba: ``norm (D,)``, ``in_proj (D, 2 I + 2 G N + H)``,
``conv_w (I + 2 G N, taps)``, ``conv_b``, ``dt_bias (H,)``, ``a_log (H,)``, ``d_skip (H,)``,
``gated_norm (I,)``, ``out_proj (I, D)`` with ``I = H x head``. Attention: ``norm``,
``wq (D, n, h)``, ``wk``/``wv (D, k, h)``, ``wo (n, h, D)``. MoE: ``norm``,
``router (E_all, D)``, ``router_bias (E_all,)``, ``latent_down (D, Z)``, ``latent_up (Z, D)``,
``experts_up (E_held, Z, F)``, ``experts_down (E_held, F, Z)``, ``shared_up (D, Fs)``,
``shared_down (Fs, D)``. Inits are the harness's three: ``a_log`` normal (A about -1),
``dt_bias`` and ``conv_b`` zeros, ``d_skip`` ones (PERF.md: what that does to the state's
reach, and why the published initialisation waits for an init kind of its own).

The protocol this module answers is in ``benchmarks/README.md``.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from benchmarks.harness import kernel_costs as costs

IGNORE = -100
_HEAD_ROWS = 1024  # rows of logits held at once
_KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
_GROUP_OF = {"mamba": "mamba_layers", "attn": "attn_layers", "moe": "moe_layers"}


def dims(m: dict) -> dict:
    pattern = m["hybrid_override_pattern"]
    unknown = set(pattern) - set(_KINDS)
    if unknown or len(pattern) != m["num_hidden_layers"]:
        raise ValueError(f"pattern {pattern!r}: kinds {sorted(unknown)} unknown or length "
                         f"not num_hidden_layers {m['num_hidden_layers']}")
    H, P = m["mamba_num_heads"], m["mamba_head_dim"]
    G, N = m["n_groups"], m["ssm_state_size"]
    return dict(
        kinds=[_KINDS[c] for c in pattern], D=m["hidden_size"], V=m["vocab_size"],
        eps=m["layer_norm_epsilon"],
        H=H, P=P, G=G, N=N, I=H * P, conv=H * P + 2 * G * N, taps=m["conv_kernel"],
        chunk=m["chunk_size"],
        n=m["num_attention_heads"], k=m["num_key_value_heads"], h=m["head_dim"],
        E_all=m.get("router_n_experts", m["n_routed_experts"]), E=m["n_routed_experts"],
        first=m.get("first_held_expert", 0), K=m["num_experts_per_tok"],
        Z=m["moe_latent_size"], F=m["moe_intermediate_size"],
        Fs=m["n_shared_experts"] * m["moe_shared_expert_intermediate_size"],
        scale=float(m["routed_scaling_factor"]), norm_topk=bool(m["norm_topk_prob"]),
    )


def _layer_shapes(d: dict, kind: str) -> dict:
    D = d["D"]
    if kind == "mamba":
        return {"norm": ((D,), "ones"),
                "in_proj": ((D, d["I"] + d["conv"] + d["H"]), "normal"),
                "conv_w": ((d["conv"], d["taps"]), "normal"), "conv_b": ((d["conv"],), "zeros"),
                "dt_bias": ((d["H"],), "zeros"), "a_log": ((d["H"],), "normal"),
                "d_skip": ((d["H"],), "ones"), "gated_norm": ((d["I"],), "ones"),
                "out_proj": ((d["I"], D), "normal")}
    if kind == "attn":
        n, k, h = d["n"], d["k"], d["h"]
        return {"norm": ((D,), "ones"), "wq": ((D, n, h), "normal"), "wk": ((D, k, h), "normal"),
                "wv": ((D, k, h), "normal"), "wo": ((n, h, D), "normal")}
    return {"norm": ((D,), "ones"), "router": ((d["E_all"], D), "normal"),
            "router_bias": ((d["E_all"],), "zeros"),
            "latent_down": ((D, d["Z"]), "normal"), "latent_up": ((d["Z"], D), "normal"),
            "experts_up": ((d["E"], d["Z"], d["F"]), "normal"),
            "experts_down": ((d["E"], d["F"], d["Z"]), "normal"),
            "shared_up": ((D, d["Fs"]), "normal"), "shared_down": ((d["Fs"], D), "normal")}


def block_shapes(m: dict) -> dict[str, dict[str, tuple[tuple[int, ...], str]]]:
    d = dims(m)
    blocks = {"embed": {"embed": ((d["V"], d["D"]), "normal")}}
    for i, kind in enumerate(d["kinds"]):
        blocks[f"layer_{i}"] = _layer_shapes(d, kind)
    blocks["head"] = {"final_norm": ((d["D"],), "ones"), "lm_head": ((d["D"], d["V"]), "normal")}
    return blocks


def layer_groups(m: dict) -> dict[str, list[int]]:
    """Three stacks, in the order of the program's: a layer's place in its group is its
    place among the layers of its kind."""
    kinds = dims(m)["kinds"]
    return {_GROUP_OF[kind]: [i for i, k in enumerate(kinds) if k == kind]
            for kind in ("mamba", "attn", "moe") if kind in kinds}


def _counts(d: dict) -> dict[str, int]:
    return {kind: d["kinds"].count(kind) for kind in ("mamba", "attn", "moe")}


def matrix_params_per_token(m: dict) -> dict[str, float]:
    """Matrix parameters one token is multiplied by. Of the routed experts a token meets
    those of its ``K`` that are held here: ``K x held / all`` of them if routing is even."""
    d = dims(m)
    c = _counts(d)
    D = d["D"]
    routed = d["K"] * d["E"] / d["E_all"] * 2 * d["Z"] * d["F"]
    return {
        "mamba_projections": c["mamba"] * (D * (d["I"] + d["conv"] + d["H"]) + d["I"] * D),
        "mamba_conv": c["mamba"] * d["conv"] * d["taps"],
        "attention_projections": c["attn"] * (2 * D * d["n"] * d["h"] + 2 * D * d["k"] * d["h"]),
        "router": c["moe"] * d["E_all"] * D,
        "latent_projections": c["moe"] * 2 * D * d["Z"],
        "shared_expert": c["moe"] * 2 * D * d["Fs"],
        "routed_experts": c["moe"] * routed,
        "head": D * d["V"],
    }


def _recurrence_flops_per_token(d: dict) -> float:
    """Forward, one Mamba layer: the state's update (one multiply-add an element of the
    ``H x P x N`` state) and its read-out (another)."""
    return 4.0 * d["H"] * d["P"] * d["N"]


def score_flops_per_token(m: dict, seq_len: int) -> float:
    """Causal QK^T and PV of the attention layers (a token at t meets t + 1 keys) and the
    recurrence's arithmetic of the Mamba layers, forward and backward (3 x forward)."""
    d = dims(m)
    c = _counts(d)
    attention = c["attn"] * 12.0 * d["n"] * d["h"] * (seq_len + 1) / 2
    return attention + c["mamba"] * 3.0 * _recurrence_flops_per_token(d)


def parameter_count(m: dict) -> int:
    """Every parameter held here (the held experts, the sliced vocabulary, norms, buffers)."""
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

    ``flash_attention``: the attention layers, full causal. ``expert_gemms``: the held
    experts' two ungated GEMMs (``Z -> F -> Z``) over the rows routed here if routing is
    even, forward and the two products of each gradient pass; every held expert's weights
    read once a pass and their gradient written once, the rows' inputs and outputs once a
    pass. ``ssd_scan``: the recurrence alone (what the program runs under ``mamba_ssd``):
    its multiply-adds forward and twice that backward; forward it reads x, dt, B, C and
    writes y once, backward it reads those and dy and writes their four gradients, two
    bytes an element."""
    d = dims(m)
    c = _counts(d)
    tokens = rows * seq_len
    out = {}
    if c["attn"]:
        out["flash_attention"] = costs.flash_attention_step(rows, seq_len, d["n"], d["k"],
                                                            d["h"], c["attn"])
    if c["moe"]:
        routed_rows = tokens * d["K"] * d["E"] / d["E_all"]
        forward = 2 * 2.0 * routed_rows * d["Z"] * d["F"]
        weights = d["E"] * 2 * d["Z"] * d["F"] * 2
        row_bytes = routed_rows * (d["Z"] + d["F"] + d["F"] + d["Z"]) * 2
        out["expert_gemms"] = {"flops": c["moe"] * 3 * forward,
                               "bytes": float(c["moe"] * 3 * (weights + row_bytes))}
    if c["mamba"]:
        inputs = d["I"] + d["H"] + 2 * d["G"] * d["N"]  # x, dt, B, C: elements a token
        elements = (inputs + d["I"]) + (inputs + d["I"] + inputs)  # forward; backward
        out["ssd_scan"] = {"flops": c["mamba"] * 3.0 * _recurrence_flops_per_token(d) * tokens,
                           "bytes": float(c["mamba"] * tokens * elements * 2)}
    return out


# ---- the model


def _mm(spec: str, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _recurrence(x, dt, A, Bm, Cm, chunk: int):
    """x (B, S, H, P), dt (B, S, H), A (H,), Bm/Cm (B, S, G, N) -> y (B, S, H, P) without
    the skip term. One token at a time; the state (B, H, P, N) is kept at every ``chunk``
    tokens and the steps between are run again in the backward pass."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2:]
    r = H // G
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"{S} tokens do not divide into chunks of {chunk}")

    def token(state, args):
        x_t, dt_t, b_t, c_t = args  # (B,H,P) (B,H) (B,G,N) (B,G,N)
        b_h, c_h = jnp.repeat(b_t, r, axis=1), jnp.repeat(c_t, r, axis=1)  # (B,H,N)
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_h[:, :, None, :])
        return state, jnp.sum(state * c_h[:, :, None, :], axis=-1)  # elementwise: exact f32

    @jax.checkpoint
    def one_chunk(state, args):
        return jax.lax.scan(token, state, args)

    def chunked(a):  # (B, S, ...) -> (S / chunk, chunk, B, ...)
        return jnp.moveaxis(a, 1, 0).reshape(S // chunk, chunk, B, *a.shape[2:])

    state0 = jnp.zeros((B, H, P, N), jnp.float32)
    _, y = jax.lax.scan(one_chunk, state0, tuple(chunked(a) for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y.reshape(S, B, H, P), 0, 1)


def _mamba(p, u, d):
    B, S, _ = u.shape
    I, H, P, G, N = d["I"], d["H"], d["P"], d["G"], d["N"]
    proj = _mm("bsd,dp->bsp", u, p["in_proj"])
    z, xbc, dt = jnp.split(proj, [I, I + d["conv"]], axis=-1)
    taps = d["taps"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j of the published conv1d weight multiplies the input taps - 1 - j tokens back
    conv = sum(padded[:, j:j + S] * p["conv_w"][:, j] for j in range(taps)) + p["conv_b"]
    x, Bm, Cm = jnp.split(jax.nn.silu(conv), [I, I + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    x = x.reshape(B, S, H, P)
    y = _recurrence(x, dt, -jnp.exp(p["a_log"]), Bm.reshape(B, S, G, N),
                    Cm.reshape(B, S, G, N), d["chunk"])
    y = (y + p["d_skip"][:, None] * x).reshape(B, S, I) * jax.nn.silu(z)
    grouped = y.reshape(B, S, G, I // G)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, -1, keepdims=True) + d["eps"])
    return _mm("bsi,id->bsd", grouped.reshape(B, S, I) * p["gated_norm"], p["out_proj"])


def _attention(p, u, d):
    """Causal, no position embedding; one query head at a time, so that one head's
    S x S scores exist."""
    B, S, _ = u.shape
    n, k, h = d["n"], d["k"], d["h"]
    q = _mm("bsd,dnh->bnsh", u, p["wq"]).reshape(B * n, S, h)
    kk = jnp.repeat(_mm("bsd,dkh->bksh", u, p["wk"]), n // k, axis=1).reshape(B * n, S, h)
    vv = jnp.repeat(_mm("bsd,dkh->bksh", u, p["wv"]), n // k, axis=1).reshape(B * n, S, h)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args
        s = _mm("qh,sh->qs", qi, ki) * (h ** -0.5)
        return _mm("qs,sh->qh", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vi)

    out = jax.lax.map(one, (q, kk, vv)).reshape(B, n, S, h)
    return _mm("bnsh,nhd->bsd", out, p["wo"])


def _moe(p, u, d):
    B, S, D = u.shape
    t = u.reshape(B * S, D)
    scores = jax.nn.sigmoid(_mm("td,ed->te", t, p["router"]))
    _, idx = jax.lax.top_k(scores + p["router_bias"], d["K"])
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    if d["norm_topk"]:
        picked = picked / picked.sum(-1, keepdims=True)
    picked = picked * d["scale"]
    weight = jnp.zeros_like(scores).at[jnp.arange(t.shape[0])[:, None], idx].set(picked)
    held = weight[:, d["first"]:d["first"] + d["E"]].T  # (E_held, T): the experts here
    latent = _mm("td,dz->tz", t, p["latent_down"])

    @jax.checkpoint
    def one(r, args):
        w_up, w_down, w_e = args
        act = jnp.square(jax.nn.relu(_mm("tz,zf->tf", latent, w_up)))
        return r + _mm("tf,fz->tz", act, w_down) * w_e[:, None], None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                             (p["experts_up"], p["experts_down"], held))
    shared = _mm("tf,fd->td", jnp.square(jax.nn.relu(_mm("td,df->tf", t, p["shared_up"]))),
                 p["shared_down"])
    return (_mm("tz,zd->td", routed, p["latent_up"]) + shared).reshape(B, S, D)


_MIXER = {"mamba": _mamba, "attn": _attention, "moe": _moe}


def embed_block(p, ids):
    return p["embed"][ids]


def layer_block(p, x, *, m: dict, kind: str):
    d = dims(m)
    return x + _MIXER[kind](p, _rms(x, p["norm"], d["eps"]), d)


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
