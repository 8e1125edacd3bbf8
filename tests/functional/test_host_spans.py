"""The program's host spans, read back from a profiler trace of a real recipe run.

Every part of a loop iteration runs inside ``Observability.track`` (docs/observability.md
"Spans"), which enters a ``jax.profiler.TraceAnnotation``: whenever a trace is open the
spans lie on the trace's host plane, on the device trace's clock, with their step. Here a
tiny recipe runs four steps (the first compiles) under ``jax.profiler.trace`` and the
xplane is read with ``jax.profiler.ProfileData``, as the benchmark's reader does.
"""

import glob
import textwrap

import jax
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import (
    TrainFinetuneRecipeForNextTokenPrediction,
)

# siblings of one step, in the order they tile it (`data_wait` of step N lies in
# iteration N-1, after that step's dispatch: docs/observability.md "Spans")
_SIBLINGS = ("data_wait", "train_step", "step_hooks", "loss_pull", "log_row", "step_end")
_STEPS = (2, 3, 4)  # step 1 compiles: its call lies in the `compile` span


def _write_cfg(tmp_path):
    cfg = f"""
    seed: 7
    output_dir: {tmp_path}/out
    model:
      config:
        architectures: [LlamaForCausalLM]
        vocab_size: 128
        hidden_size: 64
        intermediate_size: 128
        num_hidden_layers: 2
        num_attention_heads: 4
        num_key_value_heads: 2
        max_position_embeddings: 128
    distributed:
      dp_shard: 8
    backend:
      dtype: float32
    dataset:
      _target_: automodel_tpu.data.llm.mock.MockSFTDataset
      vocab_size: 128
      seq_len: 32
      num_samples: 128
      seed: 0
    micro_batch_size: 8
    seq_len: 32
    step_scheduler:
      grad_acc_steps: 1
      max_steps: 4
      num_epochs: 10
      handle_sigterm: false
    optimizer:
      lr: 1.0e-3
    checkpoint:
      enabled: false
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


@pytest.fixture(scope="module")
def host_spans(tmp_path_factory, cpu_devices):
    """``[(name, step, start_ns, end_ns)]`` of the program's spans in the trace."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("host_spans")
    recipe = TrainFinetuneRecipeForNextTokenPrediction(load_config(_write_cfg(tmp))).setup()
    with jax.profiler.trace(str(tmp / "trace")):
        recipe.run_train_validation_loop()
    (path,) = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"), recursive=True)
    names = {*_SIBLINGS, "lr_schedule", "compile", "eval", "checkpoint"}
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    stats = dict(ev.stats)
                    step = stats.get("step_num", stats.get("step"))
                    spans.append((ev.name, None if step is None else int(step),
                                  ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda s: s[2])


@pytest.mark.parametrize("name", [*_SIBLINGS, "lr_schedule"])
def test_each_span_once_a_step_with_its_step(host_spans, name):
    for step in _STEPS:
        mine = [s for s in host_spans if s[0] == name and s[1] == step]
        assert len(mine) == 1, (name, step, host_spans)
        assert mine[0][3] > mine[0][2]


def test_the_compiling_step_lies_in_the_compile_span(host_spans):
    (compiled,) = [s for s in host_spans if s[0] == "compile"]
    assert compiled[1] == 1
    assert not [s for s in host_spans if s[0] == "train_step" and s[1] == 1]


def test_siblings_tile_the_iteration_and_the_next_fetch_follows_the_dispatch(host_spans):
    for step in _STEPS:
        at = {s[0]: s for s in host_spans if s[1] == step}
        order = [at[name] for name in _SIBLINGS]
        for (_, _, _, end), (_, _, start, _) in zip(order, order[1:]):
            assert end <= start  # siblings in loop order, none overlapping the next
        # one batch in hand: step N+1's fetch, then the row's learning rate (a host
        # number), lie between step N's dispatch and the first read of its scalars
        (ahead,) = [s for s in host_spans if s[0] == "data_wait" and s[1] == step + 1]
        assert at["train_step"][3] <= ahead[2]
        assert ahead[3] <= at["lr_schedule"][2]
        assert at["lr_schedule"][3] <= at["step_hooks"][2]
    # no span wraps a whole iteration: the benchmark gives an idle gap to the host
    # event that overlaps it most, and such a span would take every gap
    first, last = host_spans[0][2], host_spans[-1][3]
    assert all(s[3] - s[2] < 0.9 * (last - first) for s in host_spans if s[0] != "compile")
