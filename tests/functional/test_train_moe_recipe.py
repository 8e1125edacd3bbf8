"""End-to-end MoE training on the virtual 8-device mesh: EP-sharded experts, aux loss,
gate-bias loss-free balancing, load-balance metrics in the JSONL stream.

The two Qwen3-MoE configurations (EP and PP x EP) each compile once in a
module-scoped fixture and every assertion class reads the captured artifacts —
the compile dominates these tests' wall time, and sharing the run is what
keeps the tier-1 budget honest as the telemetry assertions grow.
"""

import json
import textwrap

import numpy as np
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction

_QWEN3_MOE_FIELDS = (
    "num_experts: 8\n        num_experts_per_tok: 2\n        "
    "norm_topk_prob: true\n        router_aux_loss_coef: 0.01"
)


def _write_cfg(tmp_path, arch="Qwen3MoeForCausalLM", extra_model="", extra="", max_steps=6):
    cfg = f"""
    seed: 7
    output_dir: {tmp_path}/out
    model:
      config:
        architectures: [{arch}]
        vocab_size: 128
        hidden_size: 64
        intermediate_size: 96
        moe_intermediate_size: 32
        num_hidden_layers: 2
        num_attention_heads: 4
        num_key_value_heads: 2
        head_dim: 16
        max_position_embeddings: 128
        {extra_model}
    distributed:
      dp_shard: 2
      ep: 2
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
      grad_acc_steps: 2
      max_steps: {max_steps}
      num_epochs: 10
      handle_sigterm: false
      ckpt_every_steps: 0
    optimizer:
      lr: 1.0e-2
      weight_decay: 0.0
      max_grad_norm: 1.0
    lr_scheduler:
      lr_warmup_steps: 2
    checkpoint:
      enabled: false
    {extra}
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


def _read_jsonl(path):
    from tests.functional.jsonl import metric_rows

    return metric_rows(path)


def _run_and_capture(tmp_path, cfg):
    """One full train run; artifacts captured eagerly so later tests stay
    independent of any filesystem mutation by siblings."""
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
    recipe.run_train_validation_loop()
    raw = [json.loads(line) for line in open(tmp_path / "out" / "training.jsonl")]
    timeline = json.load(open(tmp_path / "out" / "timeline.json"))
    return {
        "recipe": recipe,
        "raw": raw,
        "rows": [r for r in raw if "loss" in r],
        "timeline": timeline,
    }


@pytest.fixture(scope="module")
def qwen3_moe_run(tmp_path_factory, cpu_devices, assume_v5e_peaks):
    """The canonical Qwen3-MoE EP run (dp_shard=2 x ep=2 x tp=2, aux loss on),
    compiled once and shared by the loss and telemetry assertions."""
    tmp = tmp_path_factory.mktemp("qwen3_moe")
    cfg = load_config(_write_cfg(tmp, extra_model=_QWEN3_MOE_FIELDS))
    return _run_and_capture(tmp, cfg)


@pytest.fixture(scope="module")
def qwen3_moe_pp_run(tmp_path_factory, cpu_devices):
    """PP x EP x DP composition: 4 moe layers pipelined over pp=2, with the
    router aux loss riding the per-stage accumulators (a round-1 fence).
    Shared by the trajectory, sharding, and aux-loss assertions."""
    tmp = tmp_path_factory.mktemp("qwen3_moe_pp")
    cfg = load_config(_write_cfg(tmp, extra_model=_QWEN3_MOE_FIELDS, max_steps=6))
    cfg.set_by_path("model.config.num_hidden_layers", 4)
    cfg.set_by_path("distributed.pp", 2)
    cfg.set_by_path("distributed.tp", 1)
    cfg.set_by_path("step_scheduler.grad_acc_steps", 4)
    return _run_and_capture(tmp, cfg)


class TestMoERecipeE2E:
    def test_qwen3_moe_loss_decreases(self, qwen3_moe_run):
        rows = qwen3_moe_run["rows"]
        losses = [r["loss"] for r in rows]
        assert losses[0] > 4.0
        assert losses[-1] < losses[0] - 0.3
        # MoE load-balance metrics flow into the metric stream
        assert "moe_load/max_util_mean" in rows[0]
        assert rows[0]["moe_load/max_util_mean"] >= 1.0

    def test_qwen3_moe_pp_loss_decreases(self, qwen3_moe_pp_run):
        rows = qwen3_moe_pp_run["rows"]
        losses = [r["loss"] for r in rows]
        assert losses[0] > 4.0
        assert losses[-1] < losses[0] - 0.3
        assert "moe_load/max_util_mean" in rows[0]
        # moe layer params actually pp-sharded: 4 layers over pp=2 -> 2 local
        wq = qwen3_moe_pp_run["recipe"].params["moe_layers"]["wq"]
        assert wq.sharding.shard_shape(wq.shape)[0] == 2

    def test_dsv3_pp_gate_bias_updates(self, tmp_path, cpu_devices):
        """MLA + PP: dense prefix replicated, moe stack pipelined, bias balancing on."""
        cfg = load_config(_write_cfg(
            tmp_path,
            arch="DeepseekV3ForCausalLM",
            extra_model=(
                "q_lora_rank: 24\n        kv_lora_rank: 32\n        qk_nope_head_dim: 16\n"
                "        qk_rope_head_dim: 8\n        v_head_dim: 16\n"
                "        n_routed_experts: 8\n        num_experts_per_tok: 2\n"
                "        n_shared_experts: 1\n        norm_topk_prob: true\n"
                "        first_k_dense_replace: 1"
            ),
            max_steps=4,
        ))
        cfg.set_by_path("model.config.num_hidden_layers", 5)  # 1 dense + 4 moe
        cfg.set_by_path("distributed.pp", 2)
        cfg.set_by_path("distributed.tp", 1)
        cfg.set_by_path("step_scheduler.grad_acc_steps", 4)
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        bias0 = np.asarray(
            recipe.params["moe_layers"]["moe"]["gate"]["score_correction_bias"]
        ).copy()
        recipe.run_train_validation_loop()
        bias1 = np.asarray(recipe.params["moe_layers"]["moe"]["gate"]["score_correction_bias"])
        assert np.abs(bias1 - bias0).max() > 0
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert np.isfinite([r["loss"] for r in rows]).all()

    def test_dsv3_gate_bias_updates(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(
            tmp_path,
            arch="DeepseekV3ForCausalLM",
            extra_model=(
                "q_lora_rank: 24\n        kv_lora_rank: 32\n        qk_nope_head_dim: 16\n"
                "        qk_rope_head_dim: 8\n        v_head_dim: 16\n"
                "        n_routed_experts: 8\n        num_experts_per_tok: 2\n"
                "        n_shared_experts: 1\n        n_group: 2\n        topk_group: 1\n"
                "        routed_scaling_factor: 1.0\n        norm_topk_prob: true\n"
                "        first_k_dense_replace: 1"
            ),
            max_steps=4,
        ))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        bias0 = np.asarray(
            recipe.params["moe_layers"]["moe"]["gate"]["score_correction_bias"]
        ).copy()
        recipe.run_train_validation_loop()
        bias1 = np.asarray(recipe.params["moe_layers"]["moe"]["gate"]["score_correction_bias"])
        # loss-free balancing must have moved the correction bias (factor 0.001/step)
        assert np.abs(bias1 - bias0).max() > 0
        assert bias1.dtype == np.float32
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert np.isfinite([r["loss"] for r in rows]).all()


class TestPPAuxLoss:
    def test_pp_aux_loss_balancing(self, qwen3_moe_pp_run):
        """pp + router aux-loss (a round-1 fence): the aux term now rides the
        pipeline's per-stage accumulators and joins the loss; trajectory stays
        finite and falls with balancing on."""
        losses = [r["loss"] for r in qwen3_moe_pp_run["rows"]]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] - 0.3

    def test_pp_emits_moe_aux_loss_telemetry(self, qwen3_moe_pp_run):
        """The unscaled balance loss rides the pp accumulators into moe/* rows."""
        rows = qwen3_moe_pp_run["rows"]
        assert all("moe/aux_loss" in r for r in rows)
        assert all(r["moe/aux_loss"] > 0 for r in rows)


class TestMoETelemetry:
    """The tentpole's row family on a real EP run: moe/* metrics, the a2a
    roofline category, compile-cache counters, and dispatch/combine spans."""

    def test_moe_row_family(self, qwen3_moe_run):
        rows = qwen3_moe_run["rows"]
        for r in rows:
            assert 0.0 <= r["moe/routing_entropy"] <= 1.0
            assert r["moe/routing_entropy_min"] <= r["moe/routing_entropy"]
            assert r["moe/max_util_mean"] >= 1.0
            assert r["moe/zero_expert_frac"] < 1.0
            assert r["moe/aux_loss"] > 0  # router_aux_loss_coef is on
            assert "moe/aux_loss_trend" in r
        # trend seeds at zero on the first observed aux loss
        assert rows[0]["moe/aux_loss_trend"] == 0.0
        # routed-copy throughput appears once a step time exists
        assert any(r.get("moe/tokens_per_sec_per_chip", 0) > 0 for r in rows)

    def test_run_header_and_compile_summary_counters(self, qwen3_moe_run):
        raw = qwen3_moe_run["raw"]
        headers = [r for r in raw if r.get("run_header")]
        assert len(headers) == 1
        cc = headers[0]["compile_cache"]
        assert cc["listener"] is True
        assert cc["hits"] >= 0 and cc["misses"] >= 0
        assert "persistent_enabled" in cc
        summaries = [r for r in raw if r.get("event") == "compile_summary"]
        assert len(summaries) == 1
        s = summaries[0]
        assert s["compile_aot"] >= 1
        assert s["compile_jit_fallback"] == 0
        assert s["compile_cache_hits"] >= 0

    def test_compile_costs_attribute_moe_a2a(self, qwen3_moe_run):
        compiles = [r for r in qwen3_moe_run["raw"] if r.get("event") == "compile_costs"]
        assert len(compiles) == 1
        c = compiles[0]
        # per-axis attribution: the ep axis exists and the moe_a2a category is
        # split out (the EP dispatch/combine reshards carry the scope labels)
        assert c["comm_bytes_axis_ep"] > 0
        assert c["comm_bytes_moe_a2a"] > 0
        assert c["comm_bytes_moe_a2a"] <= c["comm_bytes_total"]
        assert c["roofline_t_moe_a2a_s"] >= 0
        assert c["roofline_bound"] in ("compute", "memory", "comms", "moe_a2a")

    def test_compiled_step_and_timeline_carry_the_real_labels(self, qwen3_moe_run):
        """The labels a trace reader joins on, not spans drawn from byte counts: the
        compiled step's instruction -> op_name table (``step_scopes.json``) holds every
        MoE scope, and ``timeline.json`` the measured spans of each iteration."""
        import re

        scopes_path = qwen3_moe_run["recipe"].output_dir + "/step_scopes.json"
        labels = {label for op_name in json.load(open(scopes_path)).values()
                  for label in re.findall(r"[A-Za-z_]\w*", op_name)}
        assert {"moe", "moe_gate", "moe_dispatch", "moe_experts", "moe_combine", "attention",
                "embed", "lm_head_loss", "optimizer", "layer_stack"} <= labels
        events = qwen3_moe_run["timeline"]["traceEvents"]
        assert not [e for e in events if e.get("cat") in ("moe", "measured")]
        spans = [e for e in events if e.get("cat") == "span"]
        steps = {r["_step"] if "_step" in r else r["step"] for r in qwen3_moe_run["rows"]}
        for name in ("train_step", "log_row"):
            mine = [e for e in spans if e["name"] == name]
            assert all(e["ph"] == "X" and e["dur"] > 0 for e in mine)
            # the first step compiles: its call lies in the `compile` span
            assert steps - {min(steps)} <= {e["args"]["step"] for e in mine} | (
                set() if name == "train_step" else {min(steps)})
        assert qwen3_moe_run["timeline"]["t0_unix_s"] > 1e9
