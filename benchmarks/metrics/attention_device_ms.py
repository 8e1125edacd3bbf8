"""Model: device time a traced step of the operations under the ``attention`` scope
(projections, rotary, the flash kernels forward and backward, recomputation included)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, "attention")
