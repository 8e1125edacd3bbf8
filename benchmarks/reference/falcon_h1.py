"""Plain float32 Falcon-H1 decoder, block by block.

Written from the published ``config.json`` keys and the equations of the public
``transformers`` ``modeling_falcon_h1.py`` (``model_type: falcon_h1``; Mamba-2, arXiv
2405.21060). Every block is the same: ONE RMSNorm feeds two mixers side by side, whose
outputs, each times a published scalar, join the stream together; a second norm and a gated
SiLU MLP follow. ``x`` is the stream, every multiplier a scalar of the config::

    h0 = embed[ids] * embedding_multiplier
    u  = RMSNorm(x; input_norm)
    # Mamba-2 mixer
    p  = ((u * ssm_in_multiplier) @ W_in) * mup_vector     # ssm_multipliers over z | x | B | C | dt
    z, xBC, dt = split(p);  xBC = silu(conv(xBC) + b)      # causal, depthwise, mamba_d_conv taps
    xs, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T;  y_t = H_t C_t + D x_t    # head j in group j // (heads / groups)
    m  = (w * GroupRMSNorm(y * silu(z)) @ W_out) * ssm_out_multiplier     # mamba_n_groups groups, gate before norm
    # GQA mixer
    v_in = u * attention_in_multiplier
    q = v_in @ W_q;  k = (v_in @ W_k) * key_multiplier;  v = v_in @ W_v
    q, k = rope(q, k; rope_theta, the whole head, half-split)
    a  = (softmax(q k^T / sqrt(head_dim), causal) v @ W_o) * attention_out_multiplier
    x  = x + m + a
    w  = RMSNorm(x; mlp_norm)
    x  = x + (((w @ W_up) * silu((w @ W_gate) * mlp_multipliers[0])) @ W_down) * mlp_multipliers[1]
    logits = (RMSNorm(x; final_norm) @ W_head) * lm_head_multiplier;  loss = mean cross-entropy

No bias but the conv's. Every scalar stands where the published code has it: none is folded
into another scale or into a weight here (the program folds two, ``key_multiplier`` into
the softmax scale and ``lm_head_multiplier`` onto the normed hidden state; this module is
what shows that nothing changed by it).

Departures from the published code, each in how a thing is computed and none in what:
float32 throughout where the published model runs in bfloat16 with float32 norms; the
recurrence runs TOKEN BY TOKEN as written above, not in the chunked dual form of the
published CUDA path (it is checkpointed every ``mamba_chunk_size`` tokens so that its
backward holds one chunk's states); the conv is a sum of shifted products; attention is
one query head at a time against explicit scores; the logits are made in row blocks.
``time_step_limit`` is the published default (0, inf): no clamp. Keys the published code
never reads (``mamba_use_mlp``, ``mlp_expansion_factor``, ``mamba_expand`` beside
``mamba_d_ssm``, ``attn_layer_indices``, ``num_logits_to_keep``) are not read here.
No kernel, no cache, nothing imported from the program; every matmul at
``Precision.HIGHEST``.

Parameters (``x @ W`` everywhere), a layer: ``input_norm (D,)``, ``in_proj (D, 2 I + 2 G N +
H)``, ``conv_w (I + 2 G N, taps)``, ``b_conv``, ``dt_bias (H,)``, ``a_log (H,)``, ``d_skip
(H,)``, ``gated_norm (I,)``, ``out_proj (I, D)`` with ``I = mamba_d_ssm = H x mamba_d_head``;
``wq (D, n, h)``, ``wk`` / ``wv (D, k, h)``, ``wo (n, h, D)``; ``mlp_norm``, ``w_gate`` /
``w_up (D, F)``, ``w_down (F, D)``. Inits are the harness's three kinds: ``a_log`` normal (A
about -1), ``dt_bias`` and ``d_skip`` ones, ``b_conv`` zeros (the configuration's
``assumed.inits`` says what that does to the state's reach).

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


def dims(m: dict) -> dict:
    H, P = m["mamba_n_heads"], m["mamba_d_head"]
    G, N = m["mamba_n_groups"], m["mamba_d_state"]
    I = m.get("mamba_d_ssm") or m["mamba_expand"] * m["hidden_size"]
    if I != H * P or H % G:
        raise ValueError(f"mamba_d_ssm {I} is not {H} heads x {P}, or {H} heads do not "
                         f"divide into {G} groups")
    for key in ("attention_bias", "mlp_bias", "mamba_proj_bias", "projectors_bias",
                "mamba_norm_before_gate"):
        if m.get(key, False):
            raise ValueError(f"{key} true: not what this reference computes")
    if not m.get("mamba_rms_norm", True) or not m.get("mamba_conv_bias", True):
        raise ValueError("this reference has the gated group norm and the conv's bias")
    return dict(
        L=m["num_hidden_layers"], D=m["hidden_size"], V=m["vocab_size"], eps=m["rms_norm_eps"],
        H=H, P=P, G=G, N=N, I=I, conv=I + 2 * G * N, taps=m["mamba_d_conv"],
        chunk=m["mamba_chunk_size"],
        n=m["num_attention_heads"], k=m["num_key_value_heads"], h=m["head_dim"],
        theta=float(m["rope_theta"]), F=m["intermediate_size"],
        embed_mult=float(m["embedding_multiplier"]), head_mult=float(m["lm_head_multiplier"]),
        key_mult=float(m["key_multiplier"]),
        attn_in=float(m["attention_in_multiplier"]), attn_out=float(m["attention_out_multiplier"]),
        ssm_in=float(m["ssm_in_multiplier"]), ssm_out=float(m["ssm_out_multiplier"]),
        ssm=tuple(float(v) for v in m["ssm_multipliers"]),
        mlp=tuple(float(v) for v in m["mlp_multipliers"]),
    )


def block_shapes(m: dict) -> dict[str, dict[str, tuple[tuple[int, ...], str]]]:
    d = dims(m)
    D, n, k, h = d["D"], d["n"], d["k"], d["h"]
    layer = {
        "input_norm": ((D,), "ones"),
        "in_proj": ((D, d["I"] + d["conv"] + d["H"]), "normal"),
        "conv_w": ((d["conv"], d["taps"]), "normal"), "b_conv": ((d["conv"],), "zeros"),
        "dt_bias": ((d["H"],), "ones"), "a_log": ((d["H"],), "normal"),
        "d_skip": ((d["H"],), "ones"), "gated_norm": ((d["I"],), "ones"),
        "out_proj": ((d["I"], D), "normal"),
        "wq": ((D, n, h), "normal"), "wk": ((D, k, h), "normal"), "wv": ((D, k, h), "normal"),
        "wo": ((n, h, D), "normal"),
        "mlp_norm": ((D,), "ones"),
        "w_gate": ((D, d["F"]), "normal"), "w_up": ((D, d["F"]), "normal"),
        "w_down": ((d["F"], D), "normal"),
    }
    blocks = {"embed": {"embed": ((d["V"], D), "normal")}}
    for i in range(d["L"]):
        blocks[f"layer_{i}"] = dict(layer)
    blocks["head"] = {"final_norm": ((D,), "ones"), "lm_head": ((D, d["V"]), "normal")}
    return blocks


def layer_groups(m: dict) -> dict[str, list[int]]:
    """One stack: every block is the same."""
    return {"layers": list(range(m["num_hidden_layers"]))}


def matrix_params_per_token(m: dict) -> dict[str, float]:
    d = dims(m)
    L, D = d["L"], d["D"]
    return {
        "mamba_projections": L * (D * (d["I"] + d["conv"] + d["H"]) + d["I"] * D),
        "mamba_conv": L * d["conv"] * d["taps"],
        "attention_projections": L * (2 * D * d["n"] * d["h"] + 2 * D * d["k"] * d["h"]),
        "mlp": L * 3 * D * d["F"],
        "head": D * d["V"],
    }


def _recurrence_flops_per_token(d: dict) -> float:
    """Forward, one layer: the state's update (one multiply-add an element of the
    ``H x P x N`` state) and its read-out (another)."""
    return 4.0 * d["H"] * d["P"] * d["N"]


def score_flops_per_token(m: dict, seq_len: int) -> float:
    """Causal QK^T and PV (a token at t meets t + 1 keys) and the recurrence's arithmetic,
    forward and backward (3 x forward), of every block."""
    d = dims(m)
    attention = 12.0 * d["n"] * d["h"] * (seq_len + 1) / 2
    return d["L"] * (attention + 3.0 * _recurrence_flops_per_token(d))


def parameter_count(m: dict) -> int:
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
    ``flash_attention``: every block's GQA mixer, full causal. ``ssd_scan``: the recurrence
    alone (what the program runs under ``mamba_ssd``): its multiply-adds forward and twice
    that backward; forward it reads x, dt, B, C and writes y once, backward it reads those
    and dy and writes their four gradients, two bytes an element."""
    d = dims(m)
    tokens = rows * seq_len
    inputs = d["I"] + d["H"] + 2 * d["G"] * d["N"]  # x, dt, B, C: elements a token
    elements = (inputs + d["I"]) + (inputs + d["I"] + inputs)  # forward; backward
    return {
        "flash_attention": costs.flash_attention_step(rows, seq_len, d["n"], d["k"], d["h"], d["L"]),
        "ssd_scan": {"flops": d["L"] * 3.0 * _recurrence_flops_per_token(d) * tokens,
                     "bytes": float(d["L"] * tokens * elements * 2)},
    }


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


def _mup_vector(d: dict):
    gn = d["G"] * d["N"]
    widths = (d["I"], d["I"], gn, gn, d["H"])  # z | x | B | C | dt
    return jnp.concatenate([jnp.full((w,), s, jnp.float32) for w, s in zip(widths, d["ssm"])])


def _mamba(p, u, d):
    B, S, _ = u.shape
    I, H, P, G, N = d["I"], d["H"], d["P"], d["G"], d["N"]
    proj = _mm("bsd,dp->bsp", u * d["ssm_in"], p["in_proj"]) * _mup_vector(d)
    z, xbc, dt = jnp.split(proj, [I, I + d["conv"]], axis=-1)
    taps = d["taps"]
    padded = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
    # tap j of the published conv1d weight multiplies the input taps - 1 - j tokens back
    conv = sum(padded[:, j:j + S] * p["conv_w"][:, j] for j in range(taps)) + p["b_conv"]
    x, Bm, Cm = jnp.split(jax.nn.silu(conv), [I, I + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    x = x.reshape(B, S, H, P)
    y = _recurrence(x, dt, -jnp.exp(p["a_log"]), Bm.reshape(B, S, G, N),
                    Cm.reshape(B, S, G, N), d["chunk"])
    y = (y + p["d_skip"][:, None] * x).reshape(B, S, I) * jax.nn.silu(z)
    grouped = y.reshape(B, S, G, I // G)
    grouped = grouped * jax.lax.rsqrt(jnp.mean(grouped * grouped, -1, keepdims=True) + d["eps"])
    out = _mm("bsi,id->bsd", grouped.reshape(B, S, I) * p["gated_norm"], p["out_proj"])
    return out * d["ssm_out"]


def _rope(x, theta: float):
    """x (B, heads, S, h): the whole head rotated, half-split (``rotate_half``)."""
    S, h = x.shape[-2:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, h, 2, dtype=jnp.float32) / h))
    angle = jnp.arange(S, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., : h // 2], x[..., h // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(p, u, d):
    """Causal; one query head at a time, so that one head's S x S scores exist."""
    B, S, _ = u.shape
    n, k, h = d["n"], d["k"], d["h"]
    u = u * d["attn_in"]
    q = _rope(_mm("bsd,dnh->bnsh", u, p["wq"]), d["theta"]).reshape(B * n, S, h)
    kk = _rope(_mm("bsd,dkh->bksh", u, p["wk"]) * d["key_mult"], d["theta"])
    kk = jnp.repeat(kk, n // k, axis=1).reshape(B * n, S, h)
    vv = jnp.repeat(_mm("bsd,dkh->bksh", u, p["wv"]), n // k, axis=1).reshape(B * n, S, h)
    causal = jnp.tril(jnp.ones((S, S), bool))

    @jax.checkpoint
    def one(args):
        qi, ki, vi = args
        s = _mm("qh,sh->qs", qi, ki) * (h ** -0.5)
        return _mm("qs,sh->qh", jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1), vi)

    out = jax.lax.map(one, (q, kk, vv)).reshape(B, n, S, h)
    return _mm("bnsh,nhd->bsd", out, p["wo"]) * d["attn_out"]


def _mlp(p, w, d):
    gate = _mm("bsd,df->bsf", w, p["w_gate"]) * d["mlp"][0]
    act = _mm("bsd,df->bsf", w, p["w_up"]) * jax.nn.silu(gate)
    return _mm("bsf,fd->bsd", act, p["w_down"]) * d["mlp"][1]


def embed_block(p, ids, *, m: dict):
    return p["embed"][ids] * dims(m)["embed_mult"]


def layer_block(p, x, *, m: dict):
    d = dims(m)
    u = _rms(x, p["input_norm"], d["eps"])
    x = x + _mamba(p, u, d) + _attention(p, u, d)
    return x + _mlp(p, _rms(x, p["mlp_norm"], d["eps"]), d)


def logits_block(p, x, *, m: dict):
    """All logits at once (tests at small sizes; ``head_block`` makes them in row blocks)."""
    d = dims(m)
    return _mm("bsd,dv->bsv", _rms(x, p["final_norm"], d["eps"]), p["lm_head"]) * d["head_mult"]


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
        logits = _mm("td,dv->tv", tb, p["lm_head"]) * d["head_mult"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(yb, 0)[:, None], axis=-1)[:, 0]
        return jnp.where(yb != IGNORE, lse - picked, 0.0).sum()

    total = jax.lax.map(one, (t.reshape(-1, rows, d["D"]), y.reshape(-1, rows))).sum()
    return total / jnp.maximum((y != IGNORE).sum(), 1)


@functools.lru_cache(maxsize=None)
def _jitted(m_key: str):
    m = json.loads(m_key)
    head = functools.partial(head_block, m=m)
    layer = functools.partial(layer_block, m=m)
    embed = functools.partial(embed_block, m=m)

    def head_grad(p, x, labels):
        loss, (gp, gx) = jax.value_and_grad(head, argnums=(0, 1))(p, x, labels)
        return loss, gp, gx

    def layer_vjp(p, x, gy):
        _, pull = jax.vjp(layer, p, x)
        return pull(gy)

    def embed_grad(p, ids, gx):
        return jax.vjp(lambda q: embed(q, ids), p)[1](gx)[0]

    return dict(embed=jax.jit(embed), layer=jax.jit(layer), layer_vjp=jax.jit(layer_vjp),
                head_grad=jax.jit(head_grad), embed_grad=jax.jit(embed_grad))


def loss_and_grads(blocks: dict, ids, labels, *, m: dict, on_grad):
    """One forward and backward sweep. ``on_grad(block_name, grads)`` is called once per
    block, last block first, with that block's gradient; the block's parameters may be
    replaced inside the call. Returns the loss."""
    fns = _jitted(json.dumps(m, sort_keys=True))
    L = m["num_hidden_layers"]
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
