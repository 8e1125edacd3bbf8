"""Kernels: least time the chip could take for the step's head and loss GEMMs as the
cell's reference reckons them (``kernel_costs`` of ``benchmarks/reference/<name>.py``,
built from ``harness/gemm_costs.py``) over the device time of the ``linear_ce_*``
kernels, in percent."""

from benchmarks.harness import kernel_costs, spans
from benchmarks.harness.peaks import peaks


def read(run: dict):
    measured_ms = spans.kernels_ms(run, ("linear_ce_",))
    if measured_ms is None:
        return None  # no device trace, or a program whose kernels had no names
    cost = run["cell"].kernel_cost("linear_ce")
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"linear_ce_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {measured_ms:.3f} ms a step", flush=True)
    return 100.0 * 1e3 * least / measured_ms
