"""Kernels: device time of the flash attention forward and backward kernels per step
against the least time the chip could take for that step's attention as the cell's
reference reckons it (``kernel_costs`` of ``benchmarks/reference/<name>.py``, built from
``harness/kernel_costs.py``), in percent."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.peaks import peaks


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None  # no device trace: a rehearsal
    if not trace["flash_s"]:
        raise RuntimeError("the device trace holds no flash attention kernel event "
                           "(harness/trace.py is_flash): the kernel's name moved")
    cost = run["cell"].kernel_cost("flash_attention")
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"flash_attention_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {1e3 * trace['flash_s'] / trace['steps']:.3f} ms a step", flush=True)
    return 100.0 * least / (trace["flash_s"] / trace["steps"])
