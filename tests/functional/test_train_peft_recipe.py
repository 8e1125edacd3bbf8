"""PEFT recipe end-to-end (analogue of reference hf_peft functional scenarios):
LoRA finetune on the virtual mesh — loss falls, checkpoints are adapter-only,
resume is exact, consolidated export merges the adapter."""

import json
import textwrap

import numpy as np
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction


def _write_cfg(tmp_path, peft_extra="", max_steps=6, ckpt=False, consolidated=False, lr="3.0e-2"):
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
    peft:
      dim: 8
      alpha: 32
      {peft_extra}
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
      ckpt_every_steps: {3 if ckpt else 0}
    optimizer:
      lr: {lr}
      weight_decay: 0.0
      max_grad_norm: 1.0
    lr_scheduler:
      lr_warmup_steps: 2
    checkpoint:
      enabled: {str(ckpt).lower()}
      checkpoint_dir: {tmp_path}/ckpt
      save_consolidated: {str(consolidated).lower()}
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


def _read_jsonl(path):
    from tests.functional.jsonl import metric_rows

    return metric_rows(path)


class TestPeftRecipeE2E:
    def test_lora_loss_decreases_and_base_frozen(self, tmp_path, cpu_devices):
        # match_all_linear covers lm_head — the mock arith task is head-dominated,
        # so attention/MLP-only adapters barely move loss in 20 steps
        cfg = load_config(_write_cfg(
            tmp_path, max_steps=20, lr="2.0e-2",
            peft_extra="dim: 16\n      match_all_linear: true",
        ))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        base_before = np.asarray(recipe.params["layers"]["wq"]).copy()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        losses = [r["loss"] for r in rows]
        assert losses[0] > 4.0
        assert losses[-1] < losses[0] - 0.1  # rank-8 adapter learns slower than full FT
        # base weights untouched; adapter b no longer zero
        np.testing.assert_array_equal(np.asarray(recipe.params["layers"]["wq"]), base_before)
        assert np.abs(np.asarray(recipe.train_params["layers"]["wq"]["lora_b"])).max() > 0

    def test_adapter_only_checkpoint_and_resume(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, ckpt=True))
        r1 = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        r1.run_train_validation_loop()
        rows1 = _read_jsonl(tmp_path / "out" / "training.jsonl")
        # checkpoint holds the adapter tree only: rank-r sized, no full weights
        import glob
        import os

        model_dir = tmp_path / "ckpt" / "step_3" / "model"
        assert model_dir.exists()
        sz = sum(os.path.getsize(f) for f in glob.glob(str(model_dir / "**"), recursive=True)
                 if os.path.isfile(f))
        n_full = sum(int(np.prod(p.shape)) for p in np.asarray(r1.params["layers"]["wq"])[None])
        assert sz < 4 * 1024 * 1024  # adapter is tiny; full model would be ~4MB+
        client = json.load(open(tmp_path / "ckpt" / "step_3" / "client.json"))
        assert client["peft_config"]["dim"] == 8

        import shutil

        shutil.rmtree(tmp_path / "ckpt" / "step_6")
        (tmp_path / "ckpt" / "latest").unlink()
        (tmp_path / "out" / "training.jsonl").unlink()
        cfg2 = load_config(_write_cfg(tmp_path, ckpt=True))
        r2 = TrainFinetuneRecipeForNextTokenPrediction(cfg2).setup()
        assert r2.step_scheduler.step == 3
        r2.run_train_validation_loop()
        rows2 = _read_jsonl(tmp_path / "out" / "training.jsonl")
        l1 = {r["step"]: r["loss"] for r in rows1}
        l2 = {r["step"]: r["loss"] for r in rows2}
        for s in (4, 5, 6):
            assert l2[s] == pytest.approx(l1[s], rel=1e-5), f"step {s} diverged"

    def test_qlora_int8_runs_and_base_stays_quantized(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, peft_extra="qlora: int8", max_steps=4))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        from automodel_tpu.quantization.qlora import is_quantized_leaf

        assert is_quantized_leaf(recipe.params["layers"]["wq"])
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert all(np.isfinite(r["loss"]) for r in rows)
        assert is_quantized_leaf(recipe.params["layers"]["wq"])  # still int8 at rest

    def test_qat_fake_quant_runs(self, tmp_path, cpu_devices):
        # QAT without peft: fake-quantize weights in the forward, full finetune
        cfg_path = _write_cfg(tmp_path, max_steps=4)
        import re

        text = re.sub(
            r"peft:\n((?:  .*)?\n)+?(?=\S)",
            "qat:\n  enabled: true\n  weight_bits: 8\n  group_size: 16\n",
            cfg_path.read_text(),
        )
        cfg_path.write_text(text)
        cfg = load_config(cfg_path)
        assert cfg.get("peft") is None
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert all(np.isfinite(r["loss"]) for r in rows)
        assert rows[-1]["loss"] < rows[0]["loss"] + 0.1  # training not destabilized

    def test_dora_runs(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, peft_extra="use_dora: true", max_steps=3))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert all(np.isfinite(r["loss"]) for r in rows)
        assert "magnitude" in recipe.train_params["layers"]["wq"]


class TestCompositions:
    """The reference composes peft/qat/kd/pp through one sequencing path
    (infrastructure.py:303); every former fence now has a bit-exact
    pipelined-vs-unpipelined trajectory test."""

    def test_peft_pp_matches_unpipelined_trajectory(self, tmp_path, cpu_devices):
        """peft + pp gradient correctness: the pp=2 LoRA training trajectory must
        reproduce the pp=1 (plain dp/tp) trajectory step for step — a far
        stronger check than loss-falls (the adapter merge happens outside the
        manual region, so schedules must not perturb grads)."""
        import json as _json

        def run(tag, dist):
            cfg_text = _write_cfg(
                tmp_path, max_steps=8, lr="2.0e-2",
                peft_extra="dim: 16\n      match_all_linear: true",
            ).read_text().replace("dp_shard: 4\n  tp: 2", dist)
            cfg_text = cfg_text.replace("num_hidden_layers: 2", "num_hidden_layers: 4")
            cfg_text = cfg_text.replace(f"output_dir: {tmp_path}/out", f"output_dir: {tmp_path}/{tag}")
            p = tmp_path / f"cfg_{tag}.yaml"
            p.write_text(cfg_text)
            r = TrainFinetuneRecipeForNextTokenPrediction(load_config(str(p)))
            r.setup()
            from automodel_tpu.peft.lora import count_lora_params

            assert count_lora_params(r.train_params) < 200_000
            r.run_train_validation_loop()
            return [row["loss"] for row in _read_jsonl(tmp_path / tag / "training.jsonl")]

        ref = run("pp1", "dp_shard: 4\n  tp: 2")
        got = run("pp2", "dp_shard: 2\n  tp: 2\n  pp: 2")
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_qat_pp_matches_unpipelined_trajectory(self, tmp_path, cpu_devices):
        """qat x pp (a round-2 fence): fake-quant is a param-level transform
        applied before the manual region, so the pp=2 trajectory must reproduce
        the unpipelined one step for step."""
        def run(tag, dist):
            cfg_text = _write_cfg(tmp_path, max_steps=6, lr="1.0e-2").read_text()
            cfg_text = cfg_text.replace("peft:\n  dim: 8\n  alpha: 32",
                                        "qat:\n  weight_bits: 8")
            cfg_text = cfg_text.replace("dp_shard: 4\n  tp: 2", dist)
            cfg_text = cfg_text.replace(f"output_dir: {tmp_path}/out",
                                        f"output_dir: {tmp_path}/{tag}")
            p = tmp_path / f"cfg_{tag}.yaml"
            p.write_text(cfg_text)
            r = TrainFinetuneRecipeForNextTokenPrediction(load_config(str(p)))
            r.setup()
            assert r.cfg.get("qat") is not None
            r.run_train_validation_loop()
            return [row["loss"] for row in _read_jsonl(tmp_path / tag / "training.jsonl")]

        ref = run("qat_pp1", "dp_shard: 4\n  tp: 2")
        got = run("qat_pp2", "dp_shard: 2\n  tp: 2\n  pp: 2")
        assert ref[-1] < ref[0]
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_qat_peft_composes_and_matches_pipelined(self, tmp_path, cpu_devices):
        """qat x peft (and x pp — the full stack of round-2 fences): the adapter
        trains in full precision over a fake-quantized base; pp=2 must match the
        unpipelined trajectory exactly."""

        def run(tag, dist):
            cfg_text = _write_cfg(
                tmp_path, max_steps=6, lr="5.0e-3",
                peft_extra="match_all_linear: true",
            ).read_text()
            cfg_text = cfg_text.replace("backend:", "qat:\n  weight_bits: 8\nbackend:")
            cfg_text = cfg_text.replace("dp_shard: 4\n  tp: 2", dist)
            cfg_text = cfg_text.replace(f"output_dir: {tmp_path}/out",
                                        f"output_dir: {tmp_path}/{tag}")
            p = tmp_path / f"cfg_{tag}.yaml"
            p.write_text(cfg_text)
            r = TrainFinetuneRecipeForNextTokenPrediction(load_config(str(p)))
            r.setup()
            assert r.peft is not None and r.cfg.get("qat") is not None
            r.run_train_validation_loop()
            return [row["loss"] for row in _read_jsonl(tmp_path / tag / "training.jsonl")]

        ref = run("qp_pp1", "dp_shard: 4\n  tp: 2")
        got = run("qp_pp2", "dp_shard: 2\n  tp: 2\n  pp: 2")
        assert np.isfinite(ref).all()
        assert ref[-1] < ref[0] + 0.1  # quantization noise: not destabilized
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_peft_dropout_pp_matches_unpipelined_trajectory(self, tmp_path, cpu_devices):
        """peft dropout x pp (a round-3 fence): the dropout rng threads through
        the pp step; with one microbatch per step the pp key derivation
        (split(rng, n_micro)[0]) coincides with the grad-accum path's
        per-microbatch keys, so the trajectories must match bit-exactly."""

        def run(tag, dist):
            cfg_text = _write_cfg(
                tmp_path, max_steps=8, lr="2.0e-2",
                peft_extra="dim: 16\n      match_all_linear: true\n      dropout: 0.15",
            ).read_text().replace("dp_shard: 4\n  tp: 2", dist)
            cfg_text = cfg_text.replace("num_hidden_layers: 2", "num_hidden_layers: 4")
            cfg_text = cfg_text.replace("grad_acc_steps: 2", "grad_acc_steps: 1")
            cfg_text = cfg_text.replace(f"output_dir: {tmp_path}/out",
                                        f"output_dir: {tmp_path}/{tag}")
            p = tmp_path / f"cfg_{tag}.yaml"
            p.write_text(cfg_text)
            r = TrainFinetuneRecipeForNextTokenPrediction(load_config(str(p)))
            r.setup()
            assert r.peft.dropout == 0.15 and r._step_needs_rng
            r.run_train_validation_loop()
            return [row["loss"] for row in _read_jsonl(tmp_path / tag / "training.jsonl")]

        ref = run("do_pp1", "dp_shard: 4\n  tp: 2")
        got = run("do_pp2", "dp_shard: 2\n  tp: 2\n  pp: 2")
        # dropout at lr 2e-2 makes the 8-step trajectory noisy — the parity
        # below (identical stochastic trajectories) is the actual check
        assert np.isfinite(ref).all()
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_qat_peft_quantizes_base_not_adapter(self, tmp_path, cpu_devices):
        """Semantic pin: the qat x peft step-0 loss equals CE on
        merge(fake_quant(base), adapter) — quantized base, full-precision
        adapter (reference QLoRA-style QAT semantics)."""
        cfg = load_config(_write_cfg(tmp_path, max_steps=1, peft_extra="match_all_linear: true"))
        cfg["qat"] = {"weight_bits": 8}
        r = TrainFinetuneRecipeForNextTokenPrediction(cfg)
        r.setup()
        import jax

        from automodel_tpu.peft.lora import merge_lora_params

        mb = next(iter(r.dataloader))
        n = int((np.asarray(mb["labels"]) != -100).sum())
        qfn = r._qat_param_fn()
        merged_q = merge_lora_params(qfn(r.params), r.train_params, r.peft)
        want = float(r._forward_loss(merged_q, jax.tree.map(np.asarray, mb), n))
        merged_plain = merge_lora_params(r.params, r.train_params, r.peft)
        plain = float(r._forward_loss(merged_plain, jax.tree.map(np.asarray, mb), n))
        assert want != plain  # quantization must actually bite
        # the compiled step must see the quantized-base loss
        got = r._train_step(
            r.train_params, r.opt_state,
            {k: np.asarray(v)[None] for k, v in mb.items()}, r.params,
        )[2]["loss"]
        np.testing.assert_allclose(float(got), want, rtol=2e-5)
