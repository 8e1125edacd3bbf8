"""Step: span ``step_compile``: the persistent cache's lookup and the executable's load when
warm, XLA and Mosaic when cold (row ``setup_summary``)."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.span_s(run, "step_compile")
