"""Step: compile requests the persistent cache did not have (row ``compile_summary``, key
``compile_cache_misses``): 0 in a warm run."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.value(run, "compile_summary", "compile_cache_misses")
