"""Model: device time a traced step of the layer scans' own operations (scope
``layer_stack`` and no block's inside it): slicing each layer's weights out of the stack,
stacking residuals and gradients."""

from benchmarks.harness import spans


def read(run: dict):
    return spans.layer_ms(run, "layer_stack")
