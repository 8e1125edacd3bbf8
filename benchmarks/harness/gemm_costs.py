"""Operations and bytes of the GEMMs inside the fused linear cross entropy and the routed
experts, from their shapes. ``kernel_costs.roofline_seconds`` turns a cost into the least
time the chip could take; peaks come from ``peaks.py``."""

from __future__ import annotations


def linear_ce_step(tokens: int, hidden: int, vocab: int, bytes_per_element: int = 2) -> dict[str, float]:
    """The head and its loss over ``tokens`` rows, forward and backward, of one optimizer
    step: three ``tokens x hidden x vocab`` GEMMs (the logits, dh, dw). The backward
    kernels' own recomputation of the logits (once each) is not counted. Bytes: each
    kernel reads the hidden states and the head once; dh and dw are written once."""
    gemm = 2.0 * tokens * hidden * vocab
    h, w = tokens * hidden * bytes_per_element, hidden * vocab * bytes_per_element
    return {"flops": 3 * gemm, "bytes": float(3 * (h + w) + h + w)}


def expert_gemms_step(routed_rows: int, hidden: int, expert_width: int, experts: int,
                      layers: int, bytes_per_element: int = 2) -> dict[str, float]:
    """The routed experts' gate/up and down GEMMs of one optimizer step over ``layers``
    sparse layers, ``routed_rows`` = tokens x experts per token: forward, and the two
    products each gradient pass needs (dx, dw). Recomputation under a remat policy is
    not counted. Bytes: every expert's weights read once a pass and their gradient
    written once; the rows' inputs and outputs of each GEMM once a pass."""
    forward = 2.0 * routed_rows * hidden * (2 * expert_width) + 2.0 * routed_rows * expert_width * hidden
    weights = experts * hidden * 3 * expert_width * bytes_per_element
    rows = routed_rows * (hidden + 2 * expert_width + expert_width + hidden) * bytes_per_element
    return {"flops": layers * 3 * forward, "bytes": float(layers * 3 * (weights + rows))}
