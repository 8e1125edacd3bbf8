"""Step: every jit compiled from process start to the loop's end: the program's count of
compile requests that reached the backend (row ``compile_summary``, key
``compile_requests``). The window holds none of them, or the run is not correct."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.value(run, "compile_summary", "compile_requests")
