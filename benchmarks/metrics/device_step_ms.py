"""Model: time an operation runs on the device, per traced step."""


def read(run: dict):
    trace = run.get("trace")
    return 1e3 * trace["busy_s"] / trace["steps"] if trace else None
