"""Kernels: least time the chip could take for the routed experts' gate/up/down GEMMs,
forward and backward (``harness/gemm_costs.py``), over the device time of the
``ragged-dot`` / ``grouped_gemm_*`` calls, in percent."""

from benchmarks.harness import gemm_costs, kernel_costs, spans
from benchmarks.harness.peaks import peaks
from benchmarks.reference.decoder import dims


def read(run: dict):
    measured_ms = spans.kernels_ms(run, ("ragged-dot", "grouped_gemm_"))
    if measured_ms is None:
        return None  # no device trace, or a program from before it wrote its spans
    cell = run["cell"]
    d = dims(cell.model)
    rows = cell.micro_batch * cell.grad_acc * cell.seq_len * d["K"]
    cost = gemm_costs.expert_gemms_step(rows, d["D"], d["I"], d["E"], d["L"])
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"expert_gemm_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {measured_ms:.3f} ms a step", flush=True)
    return 100.0 * 1e3 * least / measured_ms
