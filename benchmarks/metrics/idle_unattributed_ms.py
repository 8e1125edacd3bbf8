"""Entry and input: device idle a traced step under no program span."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.idle_ms(run, "unattributed", required=())
