"""Entry and input: device idle a traced step while the host was in ``loss_pull`` or
``log_row`` (``lr_schedule`` inside it): pulling the step's scalars and writing its row."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.idle_ms(run, "loss_pull", "log_row", "lr_schedule", required=("loss_pull", "log_row"))
