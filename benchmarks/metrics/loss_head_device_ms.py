"""Model: device time a traced step of the operations under the ``lm_head_loss`` scope
(final norm, head, cross entropy of either implementation, and their gradients)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, "lm_head_loss")
