"""Step: span ``first_step``: the first execution of the compiled step, to
``block_until_ready`` (row ``setup_summary``)."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.span_s(run, "first_step")
