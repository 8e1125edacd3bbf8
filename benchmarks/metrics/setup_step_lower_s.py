"""Step: span ``step_lower``: tracing the step to a jaxpr and lowering it to MLIR, paid
at every start, warm or cold (row ``setup_summary``)."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.span_s(run, "step_lower")
