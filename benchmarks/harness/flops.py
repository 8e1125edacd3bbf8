"""FLOPs the forward and backward passes REQUIRE per token.

6 x every matrix parameter a token meets (2 forward, 4 backward) plus the attention
scores, nothing for recomputation. Which parameters a token meets and how many keys a
query scores is the architecture's: the cell's reference (``cell.reference``,
``benchmarks/reference/<name>.py``) answers ``matrix_params_per_token`` and
``score_flops_per_token``.
"""

from __future__ import annotations


def flops_per_token(reference, m: dict, seq_len: int) -> float:
    return (6.0 * sum(reference.matrix_params_per_token(m).values())
            + reference.score_flops_per_token(m, seq_len))
