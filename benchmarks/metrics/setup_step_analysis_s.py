"""Step: span ``step_analysis``: what observing the compiled step costs at every start:
the HLO text, the parse for the cost row, ``step_scopes.json``, the memory attribution
(row ``setup_summary``)."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.span_s(run, "step_analysis")
