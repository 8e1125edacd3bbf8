"""Step: backend compiles that finished inside the window. A run with one is not correct."""


def read(run: dict):
    return run["compiles_in_window"]
