"""Entry and input: seconds of set-up spent building: the ``setup_*`` spans that tile the
recipe's ``setup()`` (mesh, model, data, optimizer, checkpoint, loggers, step function) and
``setup_pipeline`` at the loop's start, summed (row ``setup_summary``, key ``spans``)."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.span_s(run, *setup_rows.BUILD_SPANS)
