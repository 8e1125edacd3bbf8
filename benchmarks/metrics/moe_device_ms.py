"""Model: device time a traced step of the operations under the ``moe*`` scopes (gate,
dispatch, the experts' GEMMs, combine, shared experts), the ``ragged-dot`` calls among them."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, "moe")
