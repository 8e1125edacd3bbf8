"""Entry and input: of the walls of ``setup()`` and of the loop's start up to the first
step's end, the seconds under no set-up span (row ``setup_summary``, key ``unspanned_s``):
the coverage check of the set-up spans, as ``unscoped_device_ms`` is of the scopes."""

from benchmarks.harness import setup_rows


def read(run: dict):
    return setup_rows.value(run, "setup_summary", "unspanned_s")
