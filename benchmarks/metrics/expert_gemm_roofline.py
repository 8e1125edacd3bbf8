"""Kernels: least time the chip could take for the routed experts' gate/up/down GEMMs,
forward and backward, as the cell's reference reckons them (``kernel_costs`` of
``benchmarks/reference/<name>.py``, built from ``harness/gemm_costs.py``), over the
device time of the ``ragged-dot`` / ``grouped_gemm_*`` calls, in percent."""

from benchmarks.harness import kernel_costs, spans
from benchmarks.harness.peaks import peaks


def read(run: dict):
    measured_ms = spans.kernels_ms(run, ("ragged-dot", "grouped_gemm_"))
    if measured_ms is None:
        return None  # no device trace, or a program from before it wrote its spans
    cost = run["cell"].kernel_cost("expert_gemms")
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"expert_gemm_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {measured_ms:.3f} ms a step", flush=True)
    return 100.0 * 1e3 * least / measured_ms
