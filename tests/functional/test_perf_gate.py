"""Perf-regression gate smoke (tools/bench_gate.py).

Marked ``perf`` (and ``slow``, out of tier-1): run with ``pytest -m perf``.
Drives the real CLI through a subprocess the way CI would: train once on CPU,
write a baseline from the run, gate the same run (exit 0), then gate a
synthetically 10%-slower run (exit non-zero)."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.perf]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GATE = os.path.join(REPO, "tools", "bench_gate.py")


def _gate(*args):
    return subprocess.run([sys.executable, GATE, *args],
                          capture_output=True, text=True, timeout=120)


@pytest.fixture(scope="module")
def train_run(tmp_path_factory, cpu_devices):
    """One tiny CPU training run shared by the gate scenarios."""
    from automodel_tpu.config.loader import load_config
    from automodel_tpu.recipes.llm.train_ft import (
        TrainFinetuneRecipeForNextTokenPrediction,
    )

    tmp_path = tmp_path_factory.mktemp("perf_gate")
    cfg_text = f"""
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
      num_samples: 256
      seed: 0
      pattern: arith
    micro_batch_size: 8
    seq_len: 32
    step_scheduler:
      grad_acc_steps: 1
      max_steps: 8
      num_epochs: 10
      handle_sigterm: false
    optimizer:
      lr: 1.0e-2
    checkpoint:
      enabled: false
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg_text))
    recipe = TrainFinetuneRecipeForNextTokenPrediction(load_config(p)).setup()
    recipe.run_train_validation_loop()
    return tmp_path


def test_gate_passes_on_matching_run_and_fails_on_10pct_regression(train_run):
    run = str(train_run / "out" / "training.jsonl")
    baseline = str(train_run / "baseline.json")

    wrote = _gate("--run", run, "--baseline", baseline, "--write-baseline")
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    base = json.load(open(baseline))
    assert "tps" in base["metrics"]

    same = _gate("--run", run, "--baseline", baseline)
    assert same.returncode == 0, same.stdout + same.stderr
    assert "[gate] PASS" in same.stdout

    # synthetic regression: scale every row's tps down 10%
    slower = str(train_run / "regressed.jsonl")
    with open(run) as src, open(slower, "w") as dst:
        for line in src:
            row = json.loads(line)
            if row.get("tps") is not None:
                row["tps"] *= 0.9
            dst.write(json.dumps(row) + "\n")
    bad = _gate("--run", slower, "--baseline", baseline)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "REGRESSION" in bad.stdout and "tps" in bad.stdout


def test_mem_plan_keys_ride_run_header(train_run):
    """The memory-plan smoke: a real recipe run's header must carry the full
    ``mem_plan/*`` budget, and its compile_costs row the measured ``mem/*``
    attribution — the keys the memory gate and OOM report build on."""
    rows = [json.loads(line)
            for line in open(train_run / "out" / "training.jsonl")]
    h = [r for r in rows if r.get("run_header")][0]
    for key in ("mem_plan/params_gib", "mem_plan/opt_gib", "mem_plan/batch_gib",
                "mem_plan/act_est_gib", "mem_plan/total_gib"):
        assert h[key] > 0, key
    c = [r for r in rows if r.get("event") == "compile_costs"][0]
    assert c["mem/args_gib"] > 0 and c["mem/peak_est_gib"] > 0
    assert c["mem_plan/recon_rel_err"] is not None


def test_gate_memory_keys_direction(tmp_path):
    """hbm_gib_peak gates lower-is-better through the real CLI — including
    matrix-namespaced cells, which resolve direction by basename."""
    baseline = tmp_path / "b.json"
    baseline.write_text(json.dumps({"metrics": {
        "tps": 1000.0, "hbm_gib_peak": 10.0,
        "matrix/dense_s2048_pfon/hbm_gib_peak": 3.0,
    }}))
    ok_run = tmp_path / "ok.json"
    ok_run.write_text(json.dumps({"metrics": {
        "tps": 1010.0, "hbm_gib_peak": 9.5,
        "matrix/dense_s2048_pfon/hbm_gib_peak": 2.9,
    }}))
    assert _gate("--run", str(ok_run), "--baseline", str(baseline)).returncode == 0

    bad_run = tmp_path / "bad.json"
    bad_run.write_text(json.dumps({"metrics": {
        "tps": 1010.0, "hbm_gib_peak": 12.0,  # footprint GREW 20%
        "matrix/dense_s2048_pfon/hbm_gib_peak": 2.9,
    }}))
    bad = _gate("--run", str(bad_run), "--baseline", str(baseline))
    assert bad.returncode == 1
    assert "hbm_gib_peak" in bad.stdout
