"""Model: device time a traced step of the operations under the ``delta_net`` scope (the
gated DeltaNet mixers alone: norm, in projections, conv, the delta rule, gated norm, out
projection; their MoE blocks stand outside it), forward, recomputation and backward.
``harness/spans.layer_of`` does not know the label, so the same time also lies in
``layer_stack_device_ms`` (every mixer runs inside the model's period scan; a mixer outside
a scan would land in ``unscoped_device_ms``): this reader takes it by the label itself."""

from benchmarks.harness import spans


def read(run: dict):
    red = spans.of(run)
    if red is None or "delta_net" not in red["label_s"]:
        return None  # no device trace, or a program whose mixers carry no such label
    return spans.scope_ms(run, "delta_net")
