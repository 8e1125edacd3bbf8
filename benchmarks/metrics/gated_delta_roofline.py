"""Kernels: least time the chip could take for the gated delta rule of one optimizer step
(``kernel_costs(...)["gated_delta"]`` of the cell's reference: the state's decay, update
and read-out token by token, forward and backward, q, k, v, g, beta and o moved once each
way) over the device time of the operations under the ``delta_rule`` scope, in percent.
The program runs the rule as XLA operations in the chunked form, float32 at
``Precision.HIGHEST`` with a batched triangular solve, and recomputes it in the backward
pass: a few percent is expected, and is what a kernel for the rule is judged by."""

from benchmarks.harness import kernel_costs, spans
from benchmarks.harness.peaks import peaks


def read(run: dict):
    red = spans.of(run)
    if red is None or "delta_rule" not in red["label_s"]:
        return None  # no device trace, or a program whose rule carries no such label
    measured_ms = spans.scope_ms(run, "delta_rule")
    cost = run["cell"].kernel_cost("gated_delta")
    least, bound = kernel_costs.roofline_seconds(cost, peaks(run["device_kind"]))
    print(f"gated_delta_roofline: bound by {bound}; least {1e3 * least:.3f} ms, "
          f"measured {measured_ms:.3f} ms a step", flush=True)
    return 100.0 * 1e3 * least / measured_ms
