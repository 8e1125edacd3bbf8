"""Entry and input: the recipe's ``data_wait`` goodput bucket (the span around
``pipeline.get()``) over the window, per step."""


def read(run: dict):
    return run["data_wait_ms"]
