"""Kernels: device time of the flash attention forward and backward kernels per step
against the least time the chip could take for that step's attention
(``harness/kernel_costs.py``), in percent."""

from benchmarks.harness import kernel_costs
from benchmarks.harness.peaks import peaks
from benchmarks.reference.decoder import dims


def read(run: dict):
    trace = run.get("trace")
    if not trace:
        return None  # no device trace: a rehearsal
    if not trace["flash_s"]:
        raise RuntimeError("the device trace holds no flash attention kernel event "
                           "(harness/trace.py is_flash): the kernel's name moved")
    cell = run["cell"]
    d = dims(cell.model)
    cost = kernel_costs.flash_attention_step(cell.micro_batch * cell.grad_acc, cell.seq_len,
                                             d["n"], d["k"], d["h"], d["L"])
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"flash_attention_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {1e3 * trace['flash_s'] / trace['steps']:.3f} ms a step", flush=True)
    return 100.0 * least / (trace["flash_s"] / trace["steps"])
