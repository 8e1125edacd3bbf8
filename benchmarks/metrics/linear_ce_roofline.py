"""Kernels: least time the chip could take for the step's head and loss GEMMs
(``harness/gemm_costs.py``) over the device time of the ``linear_ce_*`` kernels, in percent."""

from benchmarks.harness import gemm_costs, kernel_costs, spans
from benchmarks.harness.peaks import peaks
from benchmarks.reference.decoder import dims


def read(run: dict):
    measured_ms = spans.kernels_ms(run, ("linear_ce_",))
    if measured_ms is None:
        return None  # no device trace, or a program whose kernels had no names
    cell = run["cell"]
    d = dims(cell.model)
    cost = gemm_costs.linear_ce_step(cell.micro_batch * cell.grad_acc * cell.seq_len,
                                     d["D"], d["V"])
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"linear_ce_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {measured_ms:.3f} ms a step", flush=True)
    return 100.0 * 1e3 * least / measured_ms
