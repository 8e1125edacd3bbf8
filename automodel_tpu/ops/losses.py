"""Loss functions (reference components/loss/).

All losses return an *unreduced sum* over valid tokens plus the valid-token count, and
the recipe divides by the *global* ``num_label_tokens`` after a psum over the data axes —
the same normalization contract as the reference (every loss normalizes by global label
tokens, loss/masked_ce.py:22).

- ``masked_cross_entropy``: fp32 log-softmax CE with ignore_index masking
  (reference MaskedCrossEntropy, loss/masked_ce.py:22).
- ``chunked_cross_entropy``: vocab-chunked CE that never materializes the full
  (tokens, vocab) fp32 tensor at once (reference ChunkedCrossEntropy, chunked_ce.py:43).
- ``linear_cross_entropy``: fused hidden->logits->CE that takes the hidden states and
  the unembedding matrix and computes CE blockwise over the sequence, so the full logits
  tensor never exists (reference FusedLinearCrossEntropy via cut-cross-entropy,
  loss/linear_ce.py:119). XLA fuses each block's matmul+softmax; a Pallas variant can
  slot in underneath without changing the signature.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from automodel_tpu.ops import kernels
from automodel_tpu.ops.kernels import check_manual_region, kernel_usable, note

__all__ = [
    "masked_cross_entropy", "chunked_cross_entropy", "linear_cross_entropy",
    "fused_linear_ce_tokens", "pallas_linear_ce_supported", "kd_loss",
]

IGNORE_INDEX = -100


def _ce_sum(logits: jnp.ndarray, labels: jnp.ndarray, ignore_index: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Sum of token CE over valid labels + count of valid labels. fp32 math."""
    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0)
    logits32 = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits32, axis=-1)
    gold = jnp.take_along_axis(logits32, safe_labels[..., None], axis=-1)[..., 0]
    tok_loss = jnp.where(valid, logz - gold, 0.0)
    return tok_loss.sum(), valid.sum()


def masked_cross_entropy(
    logits: jnp.ndarray,  # (..., vocab)
    labels: jnp.ndarray,  # (...,) int, ignore_index = masked
    num_label_tokens: jnp.ndarray | int | None = None,
    ignore_index: int = IGNORE_INDEX,
) -> jnp.ndarray:
    """Mean CE over valid tokens; denominator overridable with the global token count."""
    total, count = _ce_sum(logits, labels, ignore_index)
    denom = count if num_label_tokens is None else num_label_tokens
    return total / jnp.maximum(denom, 1).astype(jnp.float32)


def chunked_cross_entropy(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    num_label_tokens: jnp.ndarray | int | None = None,
    ignore_index: int = IGNORE_INDEX,
    num_chunks: int = 8,
) -> jnp.ndarray:
    """CE computed over sequence chunks to bound the fp32 logits working set."""
    v = logits.shape[-1]
    flat_logits = logits.reshape(-1, v)
    flat_labels = labels.reshape(-1)
    n = flat_labels.shape[0]
    pad = (-n) % num_chunks
    if pad:
        flat_logits = jnp.pad(flat_logits, ((0, pad), (0, 0)))
        flat_labels = jnp.pad(flat_labels, (0, pad), constant_values=ignore_index)
    flat_logits = flat_logits.reshape(num_chunks, -1, v)
    flat_labels = flat_labels.reshape(num_chunks, -1)

    def body(carry, chunk):
        logits_c, labels_c = chunk
        # per-chunk sums ride as stacked outputs, not carries: a zero-init carry
        # would clash with shard_map's varying-axis tracking inside manual regions
        return carry, _ce_sum(logits_c, labels_c, ignore_index)

    _, (sums, counts) = jax.lax.scan(body, (), (flat_logits, flat_labels))
    total, count = sums.sum(), counts.sum()
    denom = count if num_label_tokens is None else num_label_tokens
    return total / jnp.maximum(denom, 1).astype(jnp.float32)


def fused_linear_ce_tokens(
    hidden2d: jnp.ndarray,  # (N, embed)
    unembed: jnp.ndarray,  # (embed, vocab_local)
    labels: jnp.ndarray,  # (N,) GLOBAL label ids
    ignore_index: int = IGNORE_INDEX,
    vocab_offset: jnp.ndarray | int = 0,
    interpret: bool | None = None,
    filter_eps: float | None = 1e-7,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pallas fused projection+CE partials: per-token (z, gold), logits never in HBM.

    Vocab-shard aware: with ``unembed`` a vocab shard and ``vocab_offset`` its
    global start, combine across shards with ``logsumexp(z)`` / ``sum(gold)``
    before forming ``loss = z - gold`` (reference te_cross_entropy.py:113).
    Returns None-equivalent is not provided — callers must check
    :func:`pallas_linear_ce_supported` first.
    """
    from automodel_tpu.ops.pallas.linear_ce import (
        fused_logsumexp, gold_logits, pick_blocks, pick_bwd_blocks,
    )

    n, e = hidden2d.shape
    v = unembed.shape[1]
    blocks, bwd_blocks = pick_blocks(e, v, n), pick_bwd_blocks(e, v, n)
    if interpret is None:
        interpret = kernels.interpret_mode()
    if not interpret:
        check_manual_region("loss: pallas linear_ce")
    # the run header names the tiles that ran and the columns of each kernel's
    # last vocabulary block that lie beyond V (computed by neither)
    tiles = lambda b: f"{b[0]}x{b[1]} masked {-v % b[1]}" if b else "xla"  # noqa: E731
    note("loss_tiles", f"fwd {tiles(blocks)} bwd {tiles(bwd_blocks)}", interpret=interpret)
    local_labels = labels.astype(jnp.int32) - vocab_offset
    gold = gold_logits(hidden2d, unembed, local_labels)
    pad = (-n) % max(blocks[0], (bwd_blocks or blocks)[0])  # powers of two: the larger serves both
    h_pad = jnp.pad(hidden2d, ((0, pad), (0, 0))) if pad else hidden2d
    z = fused_logsumexp(h_pad, unembed, blocks, bwd_blocks, interpret, filter_eps)
    return z[:n], gold


def pallas_linear_ce_supported(embed: int, vocab_local: int) -> bool:
    """True only when BOTH the forward and backward kernels can tile the shape.

    The backward adds its f32 accumulators to the VMEM model, so some shapes
    (e.g. embed>=12288 with 128k vocab) tile forward but not backward; checking
    only the forward would run training straight into the backward's fallback
    (or, before it existed, a trace-time crash). Asked without a token count:
    a shorter batch only admits shorter tiles, so the tiles
    ``fused_linear_ce_tokens`` then picks for the real count always exist."""
    from automodel_tpu.ops.pallas.linear_ce import pick_blocks, pick_bwd_blocks

    return (pick_blocks(embed, vocab_local) is not None
            and pick_bwd_blocks(embed, vocab_local) is not None)


def linear_cross_entropy(
    hidden: jnp.ndarray,  # (..., embed)
    unembed: jnp.ndarray,  # (embed, vocab)
    labels: jnp.ndarray,  # (...,)
    num_label_tokens: jnp.ndarray | int | None = None,
    ignore_index: int = IGNORE_INDEX,
    block_size: int = 1024,
    impl: str = "auto",  # auto | pallas | xla
    filter_eps: float | None = 1e-7,
) -> jnp.ndarray:
    """Fused projection+CE: logits exist only one (block, vocab) tile at a time.

    ``impl="pallas"`` (or auto on TPU) routes to the Pallas kernel pair with a
    manual VJP — logits live only as a VMEM tile even in the backward. The XLA
    path is the blockwise-remat scan; it also takes shapes the kernel can't
    tile, and that choice is recorded (automodel_tpu.ops.kernels). NOTE: the
    pallas path assumes an unsharded (replicated) ``unembed``; under
    tensor-parallel vocab sharding use :func:`fused_linear_ce_tokens` inside
    shard_map instead.
    """
    e = hidden.shape[-1]
    # "auto" asks for the kernel only where it runs compiled; "pallas" also
    # takes it interpreted (CPU tests of the kernel logic)
    if impl != "xla" and kernel_usable(
        "loss", requested="pallas", fallback="xla",
        needs=((pallas_linear_ce_supported(e, unembed.shape[-1]),
                f"no VMEM-fitting fwd+bwd tiles for ({e}, {unembed.shape[-1]})"),),
        interpret=None if impl == "auto" else kernels.interpret_mode(),
    ):
        flat_h = hidden.reshape(-1, e)
        flat_labels = labels.reshape(-1)
        z, gold = fused_linear_ce_tokens(
            flat_h, unembed, flat_labels, ignore_index, filter_eps=filter_eps,
        )
        valid = flat_labels != ignore_index
        total = jnp.where(valid, z - gold, 0.0).sum()
        count = valid.sum()
        denom = count if num_label_tokens is None else num_label_tokens
        return total / jnp.maximum(denom, 1).astype(jnp.float32)
    if impl == "xla":
        note("loss", "xla")
    flat_h = hidden.reshape(-1, e)
    flat_labels = labels.reshape(-1)
    n = flat_h.shape[0]
    pad = (-n) % block_size
    if pad:
        flat_h = jnp.pad(flat_h, ((0, pad), (0, 0)))
        flat_labels = jnp.pad(flat_labels, (0, pad), constant_values=ignore_index)
    blocks_h = flat_h.reshape(-1, block_size, e)
    blocks_l = flat_labels.reshape(-1, block_size)

    @jax.checkpoint
    def body(carry, blk):
        # remat: the (block, vocab) logits tile is recomputed in backward instead of
        # saved per scan step — without this the scan residuals re-materialize the
        # full logits tensor and the fusion saves nothing (cut-cross-entropy trick).
        # Sums ride as stacked outputs, not carries (shard_map varying-axis safety).
        h_b, l_b = blk
        logits_b = h_b.astype(jnp.float32) @ unembed.astype(jnp.float32)
        return carry, _ce_sum(logits_b, l_b, ignore_index)

    _, (sums, counts) = jax.lax.scan(body, (), (blocks_h, blocks_l))
    total, count = sums.sum(), counts.sum()
    denom = count if num_label_tokens is None else num_label_tokens
    return total / jnp.maximum(denom, 1).astype(jnp.float32)


def kd_loss(
    student_logits: jnp.ndarray,
    teacher_logits: jnp.ndarray,
    labels: jnp.ndarray,
    temperature: float = 1.0,
    ignore_index: int = IGNORE_INDEX,
    num_label_tokens: jnp.ndarray | int | None = None,
    divergence: str = "forward_kl",
) -> jnp.ndarray:
    """Distillation divergence on valid tokens (reference loss/kd_loss.py:21 is
    forward-KL; reverse-KL and symmetric JS ship as config options on top).

    - ``forward_kl``: KL(teacher || student) — mode-covering, the reference's loss.
    - ``reverse_kl``: KL(student || teacher) — mode-seeking, the MiniLLM-style
      objective for generative students.
    - ``js``: Jensen-Shannon, symmetric middle ground.
    """
    valid = labels != ignore_index
    t = jax.nn.log_softmax(teacher_logits.astype(jnp.float32) / temperature, axis=-1)
    s = jax.nn.log_softmax(student_logits.astype(jnp.float32) / temperature, axis=-1)
    if divergence == "forward_kl":
        per_tok = (jnp.exp(t) * (t - s)).sum(-1)
    elif divergence == "reverse_kl":
        per_tok = (jnp.exp(s) * (s - t)).sum(-1)
    elif divergence == "js":
        m = jnp.logaddexp(t, s) - jnp.log(2.0)
        per_tok = 0.5 * ((jnp.exp(t) * (t - m)).sum(-1) + (jnp.exp(s) * (s - m)).sum(-1))
    else:
        raise ValueError(
            f"unknown kd divergence {divergence!r} (forward_kl | reverse_kl | js)"
        )
    kl = per_tok * (temperature**2)
    total = jnp.where(valid, kl, 0.0).sum()
    denom = valid.sum() if num_label_tokens is None else num_label_tokens
    return total / jnp.maximum(denom, 1).astype(jnp.float32)
