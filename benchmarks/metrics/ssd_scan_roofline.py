"""Kernels: least time the chip could take for the Mamba-2 recurrence of one optimizer
step (``kernel_costs(...)["ssd_scan"]`` of the cell's reference: the state's update and
read-out, forward and backward, x, dt, B, C and y moved once each way) over the device
time of the operations under the ``mamba_ssd`` scope, in percent. The program runs the
scan as XLA operations in the chunked dual form, float32: a few percent is expected, and
is what a kernel for the scan is judged by."""

from benchmarks.harness import kernel_costs, spans
from benchmarks.harness.peaks import peaks


def read(run: dict):
    red = spans.of(run)
    if red is None or "mamba_ssd" not in red["label_s"]:
        return None  # no device trace, or a program whose scan carries no such label
    measured_ms = spans.scope_ms(run, "mamba_ssd")
    cost = run["cell"].kernel_cost("ssd_scan")
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"ssd_scan_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {measured_ms:.3f} ms a step", flush=True)
    return 100.0 * 1e3 * least / measured_ms
