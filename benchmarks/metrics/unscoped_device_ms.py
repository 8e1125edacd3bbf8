"""Model: device time a traced step of the operations under none of the scopes the other
``*_device_ms`` metrics read (the embedding among them): the coverage check."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, None, "embed")
