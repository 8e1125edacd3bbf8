"""Step: device time a traced step of the operations under the ``optimizer`` scope
(gradient norm, clip, the rule's update, applying it)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, "optimizer")
