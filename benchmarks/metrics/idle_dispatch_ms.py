"""Step: device idle a traced step while the host was in ``train_step`` (the call that
enqueues the compiled step, until the device starts it)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.idle_ms(run, "train_step", required=("train_step",))
