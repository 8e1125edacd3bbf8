"""Model: device time a traced step of the operations under the ``mlp`` scope (the dense
MLP's GEMMs and activation, forward, backward and recomputed)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, "mlp")
