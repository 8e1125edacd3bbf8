"""Step: what the compiled step holds at once by the compiler's own count
(arguments + outputs + temporaries - aliased), in GiB."""


def read(run: dict):
    return run["compiled_step_bytes"] / 2**30 if run["compiled_step_bytes"] else None
