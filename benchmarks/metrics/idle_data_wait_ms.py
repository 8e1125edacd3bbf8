"""Entry and input: device idle a traced step while the host was in ``data_wait``
(``pipeline.get()``)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.idle_ms(run, "data_wait", required=("data_wait",))
