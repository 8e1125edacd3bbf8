"""End-to-end recipe runs on the virtual 8-device mesh — the analogue of the
reference's 2-GPU L2 functional tests (SURVEY.md §4): tiny model, few steps, real
SPMD semantics, loss must fall, checkpoints must resume exactly."""

import json
import textwrap

import numpy as np
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction


def _write_cfg(tmp_path, extra="", dp_shard=4, tp=2, pp=1, n_layers=2, max_steps=6,
               grad_acc=2, ckpt=False):
    cfg = f"""
    seed: 7
    output_dir: {tmp_path}/out
    model:
      config:
        architectures: [LlamaForCausalLM]
        vocab_size: 128
        hidden_size: 64
        intermediate_size: 128
        num_hidden_layers: {n_layers}
        num_attention_heads: 4
        num_key_value_heads: 2
        max_position_embeddings: 128
    distributed:
      dp_shard: {dp_shard}
      tp: {tp}
      pp: {pp}
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
      grad_acc_steps: {grad_acc}
      max_steps: {max_steps}
      num_epochs: 10
      handle_sigterm: false
      ckpt_every_steps: {3 if ckpt else 0}
    optimizer:
      lr: 1.0e-2
      weight_decay: 0.0
      max_grad_norm: 1.0
    lr_scheduler:
      lr_warmup_steps: 2
    checkpoint:
      enabled: {str(ckpt).lower()}
      checkpoint_dir: {tmp_path}/ckpt
    {extra}
    """
    p = tmp_path / "cfg.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


def _read_jsonl(path):
    rows = [json.loads(line) for line in open(path)]
    # run-header and compile-accounting rows are stream metadata; resilience
    # event rows stay — TestResilience asserts on them
    return [r for r in rows
            if "run_header" not in r
            and r.get("event") not in ("compile_costs", "compile_summary", "setup_summary")]


@pytest.fixture(scope="module")
def base_run(tmp_path_factory, cpu_devices, assume_v5e_peaks):
    """The canonical dense run (dp_shard=4 x tp=2, ckpt at 3 and 6), compiled
    once and shared by the loss/observability/resume assertions — the compile
    dominates these tests' wall time. Artifacts are captured eagerly;
    test_resume_exact may mutate the directory afterwards."""
    tmp = tmp_path_factory.mktemp("base_run")
    cfg = load_config(_write_cfg(tmp, ckpt=True))
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
    recipe.run_train_validation_loop()
    raw = [json.loads(line) for line in open(tmp / "out" / "training.jsonl")]
    timeline = json.load(open(tmp / "out" / "timeline.json"))
    return {
        "tmp": tmp,
        "raw": raw,
        "rows": _read_jsonl(tmp / "out" / "training.jsonl"),
        "timeline": timeline,
    }


class TestTrainRecipeE2E:
    def test_loss_decreases_sharded(self, base_run):
        rows = base_run["rows"]
        assert len(rows) == 6
        losses = [r["loss"] for r in rows]
        # 128-vocab: initial loss ~ln(128)=4.85; learnable data must drop w/ lr=1e-2
        assert losses[0] > 4.0
        assert losses[-1] < losses[0] - 0.3
        assert all(np.isfinite(r["grad_norm"]) for r in rows)
        # observability: every row carries compile time and goodput fractions
        for r in rows:
            assert r["compile_time_s"] > 0.0
            assert 0.0 <= r["goodput"] <= 1.0
            for bucket in ("compile", "data_wait", "device_step", "idle"):
                assert 0.0 <= r[f"goodput/{bucket}"] <= 1.0
        # a CPU has no peak: no row of a CPU run carries an mfu (the achieved
        # model FLOP/s, a count over a host time, is still there)
        assert all("mfu" not in r for r in rows)
        assert rows[0]["tflops_per_chip"] is None
        assert all(r["tflops_per_chip"] >= 0 for r in rows[1:])
        # the first log window holds only the compile step: throughput is null,
        # never inf/0-division garbage
        assert rows[0]["tps"] is None
        assert all(r["tps"] > 0 for r in rows[1:])

    def test_run_header_compile_costs_and_timeline(self, base_run):
        """The perf-observability artifacts of one training run: the one-time
        run-header row, the per-compile analytic cost/roofline row, per-step
        bound diagnosis, and a Perfetto-loadable timeline.json."""
        raw = base_run["raw"]

        headers = [r for r in raw if r.get("run_header")]
        assert len(headers) == 1
        h = headers[0]
        assert h["jax_version"] and h["jaxlib_version"]
        assert h["n_devices"] == 8 and h["process_count"] == 1
        assert h["mesh"]["dp_shard"] == 4 and h["mesh"]["tp"] == 2
        assert h["model_id"] == "LlamaForCausalLM"
        assert "git_sha" in h and len(h["config_digest"]) == 16
        # XLA compile-cache counters ride the header (written once the step is
        # traced, before it compiles; run totals land in compile_summary)
        cc = h["compile_cache"]
        assert cc["listener"] is True and "persistent_enabled" in cc
        # which kernels the step got (this config asks for none: ops/kernels.py
        # has nothing to report beyond "nothing interpreted, nothing fell back")
        assert h["kernels"]["interpret"] is False and h["kernels"]["reasons"] == {}

        compiles = [r for r in raw if r.get("event") == "compile_costs"]
        assert len(compiles) == 1
        c = compiles[0]
        assert c["hlo_flops"] > 0
        assert c["hlo_bytes_accessed"] > 0
        assert c["comm_bytes_total"] > 0  # dp=4 x tp=2 sharding emits collectives
        assert c["roofline_step_time_s"] > 0
        assert c["roofline_bound"] in ("compute", "memory", "comms")

        metric = [r for r in raw if "loss" in r]
        assert len(metric) == 6
        # per-row diagnosis on every post-compile row (row 0 has no step time)
        for r in metric[1:]:
            assert r["bound"] in ("compute", "memory", "comms", "input")
            assert r["roofline_frac"] > 0

        summaries = [r for r in raw if r.get("event") == "compile_summary"]
        assert len(summaries) == 1
        assert summaries[0]["compile_aot"] >= 1
        assert summaries[0]["compile_jit_fallback"] == 0

        doc = base_run["timeline"]
        assert doc["displayTimeUnit"] == "ms"
        for e in doc["traceEvents"]:
            assert {"name", "cat", "ph", "ts", "pid", "tid"} <= set(e)
        names = {e["name"] for e in doc["traceEvents"]}
        assert {"compile", "compile_costs", "step", "checkpoint"} <= names
        # by category too: a compile request is an event named by its function, and a jit
        # called ``step`` earlier in this process (another test's) is in the record as well
        steps = [e for e in doc["traceEvents"] if e["name"] == "step" and e.get("cat") == "step"]
        assert len(steps) == 6
        assert all(e["ph"] == "X" and e["dur"] > 0 for e in steps)

    def test_memory_plan_rides_header_and_reconciles(self, base_run):
        """The memory pillar's two halves on a real run: the analytic
        ``mem_plan/*`` budget in the run_header (written BEFORE the first
        compile), and the compile_costs row carrying XLA's measured ``mem/*``
        attribution reconciled against it within the documented tolerance."""
        from automodel_tpu.observability.memory_plan import RECON_TOLERANCE

        raw = base_run["raw"]
        h = [r for r in raw if r.get("run_header")][0]
        assert h["mem_plan/params_gib"] > 0
        assert h["mem_plan/opt_gib"] > 0
        assert h["mem_plan/batch_gib"] > 0
        assert h["mem_plan/act_est_gib"] > 0
        assert h["mem_plan/total_gib"] == pytest.approx(
            h["mem_plan/params_gib"] + h["mem_plan/opt_gib"]
            + h["mem_plan/batch_gib"] + h["mem_plan/act_est_gib"], abs=5e-6)
        # CPU: no allocator bytes_limit and no override => no verdict keys
        assert "mem_plan/fits" not in h

        c = [r for r in raw if r.get("event") == "compile_costs"][0]
        assert c["mem/args_gib"] > 0 and c["mem/peak_est_gib"] > 0
        # XLA's identity: peak = args + out + temp + code - alias
        assert c["mem/peak_est_gib"] == pytest.approx(
            c["mem/args_gib"] + c["mem/out_gib"] + c["mem/temp_gib"]
            + c["mem/code_gib"] - c["mem/alias_gib"], abs=5e-6)
        # the acceptance bar: analytic args (params+opt+batch) within the
        # documented tolerance of what the compiled program actually takes
        assert c["mem_plan/recon_rel_err"] <= RECON_TOLERANCE
        # the hbm_plan_gib counter landed on the timeline at compile time
        counters = [e for e in base_run["timeline"]["traceEvents"]
                    if e["ph"] == "C" and e["name"] == "hbm_plan_gib"]
        assert len(counters) == 1
        assert counters[0]["args"]["params"] == h["mem_plan/params_gib"]

    def test_hsdp_matches_fsdp_trajectory(self, tmp_path, cpu_devices):
        """HSDP (dp_replicate=2 x dp_shard=2 x tp=2 — reference
        mesh_utils.py:173-190) end-to-end: params replicate across the replica
        axis, the global batch still shards 4 ways, so the trajectory must
        reproduce the pure-fsdp dp_shard=4 run step for step."""

        def run(tag, dist):
            cfg_text = _write_cfg(tmp_path).read_text()
            cfg_text = cfg_text.replace("dp_shard: 4\n  tp: 2", dist)
            cfg_text = cfg_text.replace(f"output_dir: {tmp_path}/out",
                                        f"output_dir: {tmp_path}/{tag}")
            p = tmp_path / f"cfg_{tag}.yaml"
            p.write_text(cfg_text)
            r = TrainFinetuneRecipeForNextTokenPrediction(load_config(str(p)))
            r.setup()
            if tag == "hsdp":
                assert r.mesh.shape["dp_replicate"] == 2
                # model params actually replicate over dp_replicate and shard
                # over dp_shard: local shard = L/1 x rows/(dp_shard) x ...
                wq = r.params["layers"]["wq"]
                spec = wq.sharding.spec
                flat = [a for ax in spec if ax is not None
                        for a in ((ax,) if isinstance(ax, str) else ax)]
                assert "dp_replicate" not in flat, spec
                assert "dp_shard" in flat, spec
            r.run_train_validation_loop()
            return [row["loss"] for row in _read_jsonl(tmp_path / tag / "training.jsonl")]

        ref = run("fsdp", "dp_shard: 4\n  tp: 2")
        got = run("hsdp", "dp_replicate: 2\n  dp_shard: 2\n  tp: 2")
        assert np.isfinite(ref).all() and ref[-1] < ref[0]
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_granite_pp_matches_unpipelined_trajectory(self, tmp_path, cpu_devices):
        """Granite's mup scalars under pp: the pipeline embeds OUTSIDE
        decoder_forward, so embedding_multiplier must ride embed_lookup itself
        (a review-caught silent-wrong-math bug) — pp=2 must reproduce the
        unpipelined trajectory exactly with non-trivial scalars."""

        def run(tag, dist):
            cfg_text = _write_cfg(tmp_path, n_layers=4).read_text()
            cfg_text = cfg_text.replace("architectures: [LlamaForCausalLM]",
                                        "architectures: [GraniteForCausalLM]")
            cfg_text = cfg_text.replace(
                "max_position_embeddings: 128",
                "max_position_embeddings: 128\n    embedding_multiplier: 6.0\n"
                "    residual_multiplier: 0.25\n"
                "    attention_multiplier: 0.0883883\n"
                "    logits_scaling: 4.0")
            cfg_text = cfg_text.replace("dp_shard: 4\n  tp: 2\n  pp: 1", dist)
            cfg_text = cfg_text.replace(f"output_dir: {tmp_path}/out",
                                        f"output_dir: {tmp_path}/{tag}")
            p = tmp_path / f"cfg_{tag}.yaml"
            p.write_text(cfg_text)
            r = TrainFinetuneRecipeForNextTokenPrediction(load_config(str(p)))
            r.setup()
            assert r.model.config.embedding_multiplier == 6.0
            r.run_train_validation_loop()
            return [row["loss"] for row in _read_jsonl(tmp_path / tag / "training.jsonl")]

        ref = run("gr_pp1", "dp_shard: 4\n  tp: 2\n  pp: 1")
        got = run("gr_pp2", "dp_shard: 2\n  tp: 2\n  pp: 2")
        assert np.isfinite(ref).all() and ref[-1] < ref[0]
        np.testing.assert_allclose(got, ref, rtol=1e-4)

    def test_resume_exact(self, base_run):
        # run 1 is the shared fixture: 6 steps with ckpt at 3 and final at 6
        tmp_path = base_run["tmp"]
        rows1 = base_run["rows"]

        # run 2: resume from step 3 checkpoint by removing later ckpts
        import shutil

        shutil.rmtree(tmp_path / "ckpt" / "step_6")
        (tmp_path / "ckpt" / "latest").unlink()
        (tmp_path / "out" / "training.jsonl").unlink()
        cfg2 = load_config(_write_cfg(tmp_path, ckpt=True))
        r2 = TrainFinetuneRecipeForNextTokenPrediction(cfg2).setup()
        assert r2.step_scheduler.step == 3
        r2.run_train_validation_loop()
        rows2 = _read_jsonl(tmp_path / "out" / "training.jsonl")
        # steps 4..6 must reproduce run 1 exactly (same data order, same params)
        l1 = {r["step"]: r["loss"] for r in rows1}
        l2 = {r["step"]: r["loss"] for r in rows2}
        for s in (4, 5, 6):
            assert l2[s] == pytest.approx(l1[s], rel=1e-5), f"step {s} diverged"

    def test_pipeline_parallel_loss_decreases(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, dp_shard=2, tp=2, pp=2, n_layers=4, grad_acc=4))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        losses = [r["loss"] for r in rows]
        assert losses[0] > 4.0
        assert losses[-1] < losses[0] - 0.3
        # layer params actually pp-sharded: 4 layers over pp=2 -> 2 local
        wq = recipe.params["layers"]["wq"]
        assert wq.sharding.shard_shape(wq.shape)[0] == 2

    def test_packed_sequence_loss_decreases(self, tmp_path, cpu_devices):
        extra = textwrap.dedent("""\
        packed_sequence:
          packed_sequence_size: 64
        """).replace("\n", "\n    ")
        cfg = load_config(_write_cfg(tmp_path, extra=extra))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        assert recipe.seq_len == 64  # packs override seq_len
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        losses = [r["loss"] for r in rows]
        assert losses[0] > 4.0
        assert losses[-1] < losses[0] - 0.3

    def test_packed_sequence_with_cp(self, tmp_path, cpu_devices):
        extra = textwrap.dedent("""\
        packed_sequence:
          packed_sequence_size: 64
        """).replace("\n", "\n    ")
        cfg = load_config(_write_cfg(tmp_path, extra=extra, dp_shard=2, tp=2, max_steps=3))
        cfg.set_by_path("distributed.cp", 2)
        cfg.set_by_path("distributed.tp", 1)
        cfg.set_by_path("distributed.dp_shard", 4)
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert all(np.isfinite(r["loss"]) for r in rows)

    def test_linear_ce_loss_matches(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, extra="loss:\n      name: linear_ce", max_steps=2))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        assert rows[0]["loss"] > 4.0  # sane CE for random data


class TestResilience:
    """Chaos-driven recovery end-to-end on the mock recipe (docs/resilience.md):
    an injected NaN step must roll back to the last checkpoint and finish with
    a loss matching the uninterrupted baseline to within the skipped window,
    and a truncated latest checkpoint must fall back to an older verifiable one
    at resume."""

    _resilience = textwrap.dedent("""\
    resilience:
      enabled: true
      anomaly: {window: 20, min_history: 5}
      max_skipped_updates: 0
      rollback: {max_rollbacks: 2, skip_steps: 0}
      chaos:
        enabled: true
        nan_grad_steps: [6]
        corrupt_ckpt_steps: [8]
    """).replace("\n", "\n    ")

    def test_chaos_rollback_recovers_and_falls_back_on_resume(self, tmp_path, cpu_devices):
        # uninterrupted baseline: same seed/data, no faults
        base_dir = tmp_path / "base"
        base_dir.mkdir()
        cfg = load_config(_write_cfg(base_dir, ckpt=False, max_steps=10, grad_acc=1))
        TrainFinetuneRecipeForNextTokenPrediction(cfg).setup().run_train_validation_loop()
        base_rows = _read_jsonl(base_dir / "out" / "training.jsonl")

        # chaos run: NaN-poisoned params at step 6, checkpoint truncated at 8
        cfg = load_config(_write_cfg(tmp_path, extra=self._resilience, ckpt=True,
                                     max_steps=10, grad_acc=1))
        cfg["step_scheduler"]["ckpt_every_steps"] = 4
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")

        events = [r["resilience/event"] for r in rows if "resilience/event" in r]
        assert "rollback" in events and "rollback_done" in events
        done = next(r for r in rows if r.get("resilience/event") == "rollback_done")
        assert done["resilience/from_step"] == 6
        assert done["resilience/to_step"] == 4

        losses = {r["step"]: r["loss"] for r in rows if "loss" in r}
        assert 6 not in losses  # the poisoned step never logs a metric row
        assert all(np.isfinite(v) for v in losses.values())
        base_losses = {r["step"]: r["loss"] for r in base_rows}
        # rollback dropped the step-5..6 updates, so trajectories differ by the
        # skipped window only — the final loss must land close to the baseline
        assert losses[10] == pytest.approx(base_losses[10], abs=0.35)

        # the rollback must also land on the unified timeline as an instant
        tl = json.load(open(tmp_path / "out" / "timeline.json"))
        tl_names = {e["name"] for e in tl["traceEvents"]}
        assert "rollback" in tl_names

        # resume leg: drop the clean tail checkpoints so the truncated step_8
        # is newest — setup must reject it and walk back to step_4
        import shutil

        for d in ("step_10", "step_12"):
            if (tmp_path / "ckpt" / d).exists():
                shutil.rmtree(tmp_path / "ckpt" / d)
        (tmp_path / "ckpt" / "latest").unlink()
        cfg2 = load_config(_write_cfg(tmp_path, extra=self._resilience, ckpt=True,
                                      max_steps=10, grad_acc=1))
        r2 = TrainFinetuneRecipeForNextTokenPrediction(cfg2).setup()
        assert r2.step_scheduler.step == 4

    def test_resilience_abort_when_budget_exhausted(self, tmp_path, cpu_devices):
        # no checkpoints at all: a rollback request has nothing to restore and
        # must abort loudly rather than loop on poisoned params
        extra = textwrap.dedent("""\
        resilience:
          enabled: true
          anomaly: {min_history: 5}
          max_skipped_updates: 0
          chaos:
            enabled: true
            nan_grad_steps: [3]
        """).replace("\n", "\n    ")
        cfg = load_config(_write_cfg(tmp_path, extra=extra, ckpt=False, max_steps=6))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        with pytest.raises(RuntimeError, match="unrecoverable"):
            recipe.run_train_validation_loop()


class TestNanGuard:
    def test_nonfinite_grad_raises(self, tmp_path, cpu_devices):
        """distributed.check_for_nan_in_grad stops loudly on a non-finite signal
        (reference check_for_nan_in_grad, distributed/config.py:129) — forced here
        with an absurd lr that overflows bf16 within a few steps."""
        import pytest

        from automodel_tpu.config.loader import load_config
        from automodel_tpu.recipes.llm.train_ft import (
            TrainFinetuneRecipeForNextTokenPrediction,
        )

        cfg = load_config(_write_cfg(tmp_path))
        cfg["optimizer"]["lr"] = 1.0e12
        cfg["optimizer"]["max_grad_norm"] = None
        cfg["distributed"]["check_for_nan_in_grad"] = True
        cfg["step_scheduler"]["max_steps"] = 10
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        with pytest.raises(RuntimeError, match="non-finite"):
            recipe.run_train_validation_loop()


class TestContextParallelRing:
    def test_cp_ring_recipe_loss_decreases(self, tmp_path, cpu_devices):
        """cp=4 ring attention end-to-end through the recipe: loss must decrease,
        and a cp-sharded forward must match the single-device forward."""
        from automodel_tpu.config.loader import load_config
        from automodel_tpu.recipes.llm.train_ft import (
            TrainFinetuneRecipeForNextTokenPrediction,
        )

        import jax
        import jax.numpy as jnp

        cfg = load_config(_write_cfg(tmp_path, dp_shard=2, tp=1))
        cfg["distributed"]["cp"] = 4
        cfg["distributed"]["dp_shard"] = 2
        cfg["backend"]["context_parallel"] = "ring"
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()

        # parity: the cp-ring forward must match the plain xla forward exactly
        ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (4, 32)))
        with jax.sharding.set_mesh(recipe.mesh):
            ring_logits = recipe.model(recipe.params, ids, rules=recipe.rules)
        import dataclasses as _dc

        plain_backend = _dc.replace(recipe.backend, context_parallel="allgather")
        plain_model = type(recipe.model)(recipe.model.config, plain_backend)
        plain_logits = plain_model(recipe.params, ids)
        np.testing.assert_allclose(
            np.asarray(ring_logits), np.asarray(plain_logits), atol=2e-5
        )

        recipe.run_train_validation_loop()
        rows = _read_jsonl(tmp_path / "out" / "training.jsonl")
        losses = [r["loss"] for r in rows]
        assert losses[-1] < losses[0] * 0.95, losses
