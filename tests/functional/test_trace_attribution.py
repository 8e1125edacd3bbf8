"""Measured trace attribution, end to end on the CPU backend: a real recipe
run arms the on-demand profiler, the captured jax.profiler trace window is
machine-read by trace_analysis.py, and the run directory must hold a
self-consistent ``trace_report.json``, a ``trace_summary`` metric row in the
training stream, and a schema-valid ``signals.json``."""

import json
import math
import textwrap

import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.observability.signals import validate_signals
from automodel_tpu.recipes.llm.train_ft import (
    TrainFinetuneRecipeForNextTokenPrediction,
)

from .jsonl import metric_rows, read_rows

_MEASURED_KEYS = (
    "measured_step_time_s", "measured_t_compute_s", "measured_t_comm_s",
    "measured_t_moe_a2a_s", "measured_t_host_s", "measured_t_overlap_s",
    "measured_frac_compute", "measured_frac_comm", "measured_frac_moe_a2a",
    "measured_frac_host", "overlap_frac",
)


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
      dp_shard: 4
      tp: 2
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
      max_steps: 6
      num_epochs: 10
      handle_sigterm: false
    optimizer:
      lr: 1.0e-3
    checkpoint:
      enabled: false
    observability:
      profiling:
        trace_steps: 2
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


@pytest.fixture(scope="module")
def traced_run(tmp_path_factory, cpu_devices, assume_v5e_peaks):
    """One run with a programmatically armed 2-step trace window; the manager
    analyzes the completed window in-line (no test-side parsing plumbing)."""
    tmp = tmp_path_factory.mktemp("traced_run")
    cfg = load_config(_write_cfg(tmp))
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
    recipe.observability.profiler.request_trace()  # SIGUSR1 equivalent
    recipe.run_train_validation_loop()
    out = tmp / "out"
    return {
        "out": out,
        "rows": read_rows(out / "training.jsonl"),
        "report": json.load(open(out / "trace_report.json")),
        "signals": json.load(open(out / "signals.json")),
    }


class TestTraceReport:
    def test_report_written_with_finite_categories(self, traced_run):
        doc = traced_run["report"]
        for key in ("compute_s", "comm_s", "moe_a2a_s", "host_s", "overlap_s",
                    "step_time_s", "window_s", "overlap_frac"):
            assert math.isfinite(doc[key]) and doc[key] >= 0.0, key
        assert doc["num_events"] > 0
        assert doc["step_time_s"] > 0

    def test_categories_sum_to_step_time(self, traced_run):
        """The category identity: compute + comm - overlap + host must equal
        the measured wall step time of the window (well within 20%)."""
        doc = traced_run["report"]
        total = (doc["compute_s"] + doc["comm_s"] - doc["overlap_s"]
                 + doc["host_s"])
        assert total == pytest.approx(doc["step_time_s"], rel=0.2)
        # and in fact exactly: the accounting is an interval-union identity
        assert total == pytest.approx(doc["step_time_s"], rel=1e-6)

    def test_overlap_frac_in_unit_interval(self, traced_run):
        assert 0.0 <= traced_run["report"]["overlap_frac"] <= 1.0

    def test_window_covers_traced_steps(self, traced_run):
        # trace_steps=2, and the profiler hands the exact window coverage to
        # the analyzer as steps_hint — no multiplicity estimation involved
        doc = traced_run["report"]
        assert doc["steps"] == 2
        assert doc["steps_hint"] == 2
        assert doc["window_s"] == pytest.approx(
            doc["step_time_s"] * doc["steps"], rel=1e-9)

    def test_reconciliation_verdict_present(self, traced_run):
        """The analytic roofline exists on CPU runs (compile_costs row), so
        the report must carry the measured-vs-analytic verdict."""
        rec = traced_run["report"]["reconciliation"]
        assert rec["verdict"] == "agree" or \
            rec["verdict"].startswith("disagree")
        assert isinstance(rec["bound_agrees"], bool)
        assert traced_run["report"]["measured_bound"] in (
            "compute", "comms", "moe_a2a", "input")


class TestTraceSummaryRow:
    def test_exactly_one_summary_row_with_measured_keys(self, traced_run):
        rows = [r for r in traced_run["rows"]
                if r.get("event") == "trace_summary"]
        assert len(rows) == 1
        (row,) = rows
        for key in _MEASURED_KEYS:
            assert key in row, key
            assert math.isfinite(row[key]), key
        assert 0.0 <= row["overlap_frac"] <= 1.0
        assert row["trace/steps"] >= 1

    def test_summary_row_does_not_disturb_step_metrics(self, traced_run):
        # per-step rows still parse and carry losses — the event row rides
        # the same stream without breaking metric readers
        assert len(metric_rows(traced_run["out"] / "training.jsonl")) >= 6


class TestSignalsArtifact:
    def test_signals_validates_against_schema(self, traced_run):
        assert validate_signals(traced_run["signals"]) == []

    def test_measured_and_reconciliation_sections_populated(self, traced_run):
        (cell,) = traced_run["signals"]["cells"]
        assert cell["measured"] is not None
        assert cell["measured"]["measured_step_time_s"] > 0
        assert cell["reconciliation"] is not None
        assert isinstance(cell["reconciliation"]["agrees"], bool)
        assert cell["analytic"] is not None
        assert cell["compile_cache"] is not None

    def test_cell_identity_matches_run(self, traced_run):
        (cell,) = traced_run["signals"]["cells"]
        assert cell["cell"]["seq_len"] == 32
        mesh = cell["cell"]["mesh"]
        assert mesh["dp_shard"] == 4 and mesh["tp"] == 2
