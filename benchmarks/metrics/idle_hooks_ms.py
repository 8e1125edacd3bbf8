"""Entry and input: device idle a traced step while the host was in ``step_hooks``,
``step_end``, ``eval``, ``checkpoint`` (or ``rollback``, ``compile``: none in a window)."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.idle_ms(run, "step_hooks", "step_end", "eval", "checkpoint", "rollback", "compile", required=("step_hooks", "step_end"))
