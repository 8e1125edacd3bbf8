"""Device: 1 - union of device-operation intervals over the traced steps, in percent."""


def read(run: dict):
    trace = run.get("trace")
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"]) if trace else None
