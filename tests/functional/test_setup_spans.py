"""The start of a run, in spans and compile requests of its own.

The recipe builds its ``Observability`` first thing in ``setup()`` and every phase of a
start runs inside ``Observability.track`` (docs/observability.md "Spans"): siblings that
tile ``setup()``, ``setup_pipeline`` at the loop's start, and the children of the
``compile`` span. Here a tiny recipe starts twice (fresh, then resuming from the first
run's checkpoint), the first time under a profiler trace opened BEFORE ``setup()``, and
the spans are read back from ``timeline.json``, from the trace's host plane and from the
``setup_summary`` / ``compile_summary`` rows.
"""

import glob
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import (
    TrainFinetuneRecipeForNextTokenPrediction,
)

REPO = Path(__file__).resolve().parents[2]
# in the order the code runs them (the names are the contract: ISSUE 45)
_SETUP = ("setup_mesh", "setup_model", "setup_data", "setup_optimizer", "setup_checkpoint",
          "setup_loggers", "setup_step_fn")
_LOOP_START = ("setup_pipeline", "data_wait", "compile")
_COMPILE_CHILDREN = ("step_lower", "step_compile", "step_analysis", "first_step")
_SETUP_SUMMARY_KEYS = {
    "before_setup_s", "setup_s_inside", "loop_start_s", "spans", "unspanned_s",
    "step_trace_s", "step_mlir_s", "compile_requests", "cache_hits", "cache_misses",
    "missed", "slowest_jits"}
_COMPILE_SUMMARY_KEYS = {
    "compile_aot", "compile_jit_fallback", "compile_aot_variant", "compile_aot_shape_fallback",
    "compile_cache_hits", "compile_cache_misses", "compile_requests", "compile_trace_s",
    "compile_lower_s", "compile_backend_s", "cache_retrieval_s", "missed"}


def _write_cfg(tmp_path, max_steps):
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
      max_steps: {max_steps}
      num_epochs: 10
      handle_sigterm: false
    optimizer:
      lr: 1.0e-3
    checkpoint:
      enabled: true
      checkpoint_dir: {tmp_path}/ckpt
      save_consolidated: false
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


def _artifacts(out):
    rows = [json.loads(line) for line in open(out / "training.jsonl")]
    timeline = json.load(open(out / "timeline.json"))
    return rows, timeline["traceEvents"]


@pytest.fixture(scope="module")
def starts(tmp_path_factory, cpu_devices):
    """``{"fresh" | "resumed": (rows, timeline events)}`` and the fresh start's
    ``host_plane``: ``[(name, start_ns, end_ns)]`` of its trace."""
    from jax.profiler import ProfileData

    tmp = tmp_path_factory.mktemp("setup_spans")
    jax.profiler.start_trace(str(tmp / "trace"))  # before setup(): the start is in it
    try:
        recipe = TrainFinetuneRecipeForNextTokenPrediction(
            load_config(_write_cfg(tmp, max_steps=2))).setup()
        recipe.run_train_validation_loop()
    finally:
        jax.profiler.stop_trace()
    out = {"fresh": _artifacts(tmp / "out")}
    (tmp / "out" / "training.jsonl").unlink()
    recipe = TrainFinetuneRecipeForNextTokenPrediction(
        load_config(_write_cfg(tmp, max_steps=4))).setup()
    assert recipe.step_scheduler.step == 2  # it did resume
    recipe.run_train_validation_loop()
    out["resumed"] = _artifacts(tmp / "out")
    (path,) = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"), recursive=True)
    out["host_plane"] = [
        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
        for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events]
    return out


def _spans(events, name):
    return [e for e in events if e.get("cat") == "span" and e["name"] == name]


def _row(rows, event):
    (row,) = [r for r in rows if r.get("event") == event]
    return row


@pytest.mark.parametrize("name", [*_SETUP, "setup_pipeline", "compile", *_COMPILE_CHILDREN])
def test_each_setup_span_once_a_start(starts, name):
    for which in ("fresh", "resumed"):
        rows, events = starts[which]
        (span,) = _spans(events, name)
        assert span["dur"] > 0
        # the row an operator reads has the same span, the same seconds
        assert _row(rows, "setup_summary")["spans"][name] == pytest.approx(
            span["dur"] / 1e6, abs=2e-3)


def test_siblings_tile_setup_and_the_loops_start_in_the_codes_order(starts):
    _, events = starts["fresh"]
    # before the first step's end a start has one `data_wait`: the pass's first fetch
    order = [_spans(events, name)[0] for name in (*_SETUP, *_LOOP_START)]
    for before, after in zip(order, order[1:]):
        assert before["ts"] + before["dur"] <= after["ts"] + 1  # microseconds, rounded
    (compiled,) = _spans(events, "compile")
    children = [_spans(events, name)[0] for name in _COMPILE_CHILDREN]
    for before, after in zip(children, children[1:]):
        assert before["ts"] + before["dur"] <= after["ts"] + 1
    for child in children:  # its parent by nesting
        assert compiled["ts"] <= child["ts"] + 1
        assert child["ts"] + child["dur"] <= compiled["ts"] + compiled["dur"] + 1


@pytest.mark.parametrize("which", ["fresh", "resumed"])
def test_the_spans_leave_little_of_the_two_walls_uncovered(starts, which):
    row = _row(starts[which][0], "setup_summary")
    walls = row["setup_s_inside"] + row["loop_start_s"]
    assert 0 <= row["unspanned_s"] < 0.1 * walls
    top = sum(row["spans"][n] for n in (*_SETUP, *_LOOP_START))
    assert top + row["unspanned_s"] == pytest.approx(walls, abs=0.02)
    # the interpreter, the imports and the backend's start lie before any span
    assert row["before_setup_s"] > 0
    # JAX's own split of `step_lower`, for the step's function alone
    assert 0 < row["step_trace_s"] + row["step_mlir_s"] <= row["spans"]["step_lower"]


@pytest.mark.parametrize("name", [*_COMPILE_CHILDREN, "setup_model", "setup_mesh"])
def test_a_trace_opened_before_setup_holds_the_span_on_the_host_plane(starts, name):
    mine = [s for s in starts["host_plane"] if s[0] == name]
    assert len(mine) == 1 and mine[0][2] > mine[0][1], name
    if name in _COMPILE_CHILDREN:  # on the trace's clock, inside their parent there too
        (compiled,) = [s for s in starts["host_plane"] if s[0] == "compile"]
        assert compiled[1] <= mine[0][1] and mine[0][2] <= compiled[2]


def test_the_two_rows_carry_exactly_the_contracts_keys(starts):
    rows, _ = starts["fresh"]
    stamp = {"step", "ts", "event"}
    assert set(_row(rows, "setup_summary")) - stamp == _SETUP_SUMMARY_KEYS
    assert set(_row(rows, "compile_summary")) - stamp == _COMPILE_SUMMARY_KEYS
    # no timer field of its own on the cost row: three spans say what one used to lump
    assert not [k for k in _row(rows, "compile_costs") if k.endswith("_s") and "extract" in k]
    # written once, when the first step has finished: before that step's own row
    events = [r.get("event", "step" if "loss" in r else None) for r in rows]
    assert events.index("setup_summary") < events.index("step")
    # the new keys are bare, under no tracked family: the lint of emitted keys and
    # documented keys still covers both directions
    lint = subprocess.run([sys.executable, str(REPO / "tools" / "check_metric_keys.py")],
                          capture_output=True, text=True)
    assert lint.returncode == 0, lint.stdout + lint.stderr


def test_every_compile_request_of_the_start_is_on_the_timeline(starts):
    rows, events = starts["fresh"]
    requests = [e for e in events if e.get("cat") == "compile" and e["ph"] == "X"]
    summary = _row(rows, "compile_summary")
    assert len(requests) >= summary["compile_requests"] > 0
    assert all({"fun_name", "cache"} <= set(e["args"]) for e in requests)
    # the tests' process keeps the persistent cache off: nothing was asked of it
    assert {e["args"]["cache"] for e in requests} == {"not_asked"}
    assert summary["missed"] == [] and summary["compile_cache_misses"] == 0
    # the step's own request lies inside `step_lower` .. `step_compile`
    (lowered,), (compiled,) = _spans(events, "step_lower"), _spans(events, "step_compile")
    step = [e for e in requests if lowered["ts"] - 1e3 <= e["ts"] <= lowered["ts"] + lowered["dur"]]
    assert step and max(e["ts"] + e["dur"] for e in step) <= compiled["ts"] + compiled["dur"] + 1e3
    setup = _row(rows, "setup_summary")
    assert setup["compile_requests"] <= summary["compile_requests"]
    assert len(setup["slowest_jits"]) <= 8 and all(len(j) == 3 for j in setup["slowest_jits"])


def test_the_restore_is_a_span_inside_setup_checkpoint_and_bills_its_bucket(starts):
    rows, events = starts["resumed"]
    (restore,), (parent,) = _spans(events, "restore"), _spans(events, "setup_checkpoint")
    assert parent["ts"] <= restore["ts"] + 1
    assert restore["ts"] + restore["dur"] <= parent["ts"] + parent["dur"] + 1
    steps = [r for r in rows if "loss" in r]
    assert [r["step"] for r in steps] == [3, 4]
    for r in steps:  # the run ledger reads the bucket as it did: a share of the wall
        restored_s = r["goodput/restore"] * r["goodput_wall_s"]
        assert restored_s == pytest.approx(restore["dur"] / 1e6, rel=0.2, abs=0.02)
        shares = [v for k, v in r.items() if k.startswith("goodput/")]
        assert sum(shares) == pytest.approx(1.0, abs=2e-3)
    # a fresh run looked for a checkpoint and found none: no span, nothing billed
    fresh_rows, fresh_events = starts["fresh"]
    assert not _spans(fresh_events, "restore")
    assert all(r["goodput/restore"] == 0 for r in fresh_rows if "loss" in r)
    # the wall opened at set-up's end, less the restore: model building is not in it
    wall_at_first = steps[0]["goodput_wall_s"]
    summary = _row(rows, "setup_summary")
    assert wall_at_first < summary["setup_s_inside"] + summary["loop_start_s"]


def test_with_observability_off_the_rows_still_carry_compile_time_s(tmp_path, cpu_devices):
    """`observability: {enabled: false}` opens no span and writes neither summary row,
    but the log rows keep `compile_time_s`, as they did when the recipe timed it by hand."""
    cfg = load_config(_write_cfg(tmp_path, max_steps=2))
    cfg.set_by_path("observability", {"enabled": False})
    cfg.set_by_path("checkpoint.enabled", False)
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
    recipe.run_train_validation_loop()
    rows = [json.loads(line) for line in open(tmp_path / "out" / "training.jsonl")]
    steps = [r for r in rows if "loss" in r]
    assert [r["step"] for r in steps] == [1, 2]
    assert all(r["compile_time_s"] > 0 for r in steps)
    assert not [r for r in rows if r.get("event") == "setup_summary"]
    assert not (tmp_path / "out" / "timeline.json").exists()
