"""Elastic topology end-to-end on the virtual 8-device mesh
(docs/resilience.md "Elastic restore & warm restart"): a checkpoint saved on
one mesh shape must restore onto a different one with bitwise-identical
params and a continuous data stream, the AOT warmup must keep epoch-tail
shapes out of the jit-fallback path, and chaos topology injection must drive
the whole loop."""

import json
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction


def _write_cfg(tmp_path, name="cfg", *, dp_shard=8, tp=1, max_steps=6,
               grad_acc=1, num_samples=256, ckpt_dir=None, ckpt_every=3,
               extra=""):
    cfg = f"""
    seed: 7
    output_dir: {tmp_path}/{name}_out
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
      dp_shard: {dp_shard}
      tp: {tp}
    backend:
      dtype: float32
    dataset:
      _target_: automodel_tpu.data.llm.mock.MockSFTDataset
      vocab_size: 128
      seq_len: 32
      num_samples: {num_samples}
      seed: 0
      pattern: arith
    micro_batch_size: 8
    seq_len: 32
    step_scheduler:
      grad_acc_steps: {grad_acc}
      max_steps: {max_steps}
      num_epochs: 100
      handle_sigterm: false
      ckpt_every_steps: {ckpt_every if ckpt_dir else 0}
    optimizer:
      lr: 1.0e-2
      weight_decay: 0.0
      max_grad_norm: 1.0
    lr_scheduler:
      lr_warmup_steps: 2
    checkpoint:
      enabled: {str(ckpt_dir is not None).lower()}
      checkpoint_dir: {ckpt_dir or f"{tmp_path}/{name}_ckpt"}
    {extra}
    """
    p = tmp_path / f"{name}.yaml"
    p.write_text(textwrap.dedent(cfg))
    return p


def _rows(tmp_path, name):
    with open(tmp_path / f"{name}_out" / "training.jsonl") as f:
        return [json.loads(line) for line in f]


def _flat(params):
    return {jax.tree_util.keystr(k): np.asarray(jax.device_get(v))
            for k, v in jax.tree_util.tree_flatten_with_path(params)[0]}


class TestElasticReshapeResume:
    def test_dp8_to_dp4_tp2_resume_is_bitwise_and_continuous(self, tmp_path, cpu_devices):
        """The headline elastic scenario: save on a dp_shard=8 slice, restart
        on dp_shard=4 x tp=2 — the restore must classify as a mesh change (not
        a model change), hand back bitwise-identical params, re-partition the
        dataloader cursor, and keep training for 10 more steps."""
        ckpt = tmp_path / "shared_ckpt"
        elastic = textwrap.dedent("""\
        resilience:
          enabled: true
          anomaly: {enabled: false}
          elastic: {enabled: true, allow_joiners: true}
        """).replace("\n", "\n    ")

        cfg_a = load_config(_write_cfg(tmp_path, "a", dp_shard=8, tp=1,
                                       max_steps=6, ckpt_dir=ckpt, extra=elastic))
        ra = TrainFinetuneRecipeForNextTokenPrediction(cfg_a).setup()
        ra.run_train_validation_loop()
        rows_a = _rows(tmp_path, "a")
        last_loss_a = [r["loss"] for r in rows_a if "loss" in r][-1]
        params_a = _flat(ra.train_params)

        cfg_b = load_config(_write_cfg(tmp_path, "b", dp_shard=4, tp=2,
                                       max_steps=16, ckpt_dir=ckpt, extra=elastic))
        rb = TrainFinetuneRecipeForNextTokenPrediction(cfg_b).setup()
        assert rb.step_scheduler.step == 6
        assert rb.mesh.shape["dp_shard"] == 4 and rb.mesh.shape["tp"] == 2

        # bitwise: orbax resharded into the new mesh's templates, values intact
        params_b = _flat(rb.train_params)
        assert params_a.keys() == params_b.keys()
        for k in params_a:
            np.testing.assert_array_equal(params_a[k], params_b[k], err_msg=k)

        rb.run_train_validation_loop()
        rows_b = _rows(tmp_path, "b")

        restore = [r for r in rows_b
                   if r.get("resilience/event") == "elastic_restore"]
        assert len(restore) == 1
        assert "dp_shard 8->4" in restore[0]["resilience/delta"]
        assert "tp 1->2" in restore[0]["resilience/delta"]

        repart = [r for r in rows_b
                  if r.get("event") == "elastic_data_repartition"]
        assert len(repart) == 1
        # single-process: the global batch size did not change, so the reshape
        # is example-exact — nothing re-fed, nothing dropped
        assert "refed_examples" not in repart[0]
        assert repart[0]["new_cursor"] * repart[0]["new_batch_size"] \
            == repart[0]["consumed_examples"]

        losses = {r["step"]: r["loss"] for r in rows_b if "loss" in r}
        assert sorted(losses) == list(range(7, 17))  # 10 continued steps
        assert all(np.isfinite(v) for v in losses.values())
        # continuity: the first resumed step continues A's trajectory (tp=2
        # changes reduction order, so tolerance — not equality)
        assert abs(losses[7] - last_loss_a) < 0.5

    def test_same_mesh_resume_is_not_elastic(self, tmp_path, cpu_devices):
        ckpt = tmp_path / "ckpt"
        cfg = _write_cfg(tmp_path, "s1", dp_shard=8, max_steps=3, ckpt_dir=ckpt)
        TrainFinetuneRecipeForNextTokenPrediction(load_config(cfg)).setup() \
            .run_train_validation_loop()
        cfg2 = _write_cfg(tmp_path, "s2", dp_shard=8, max_steps=6, ckpt_dir=ckpt)
        r2 = TrainFinetuneRecipeForNextTokenPrediction(load_config(cfg2)).setup()
        assert r2.step_scheduler.step == 3
        r2.run_train_validation_loop()
        rows = _rows(tmp_path, "s2")
        assert not any(r.get("resilience/event") == "elastic_restore"
                       for r in rows)
        assert not any(r.get("event") == "elastic_data_repartition"
                       for r in rows)


class TestPPStackToPureFSDP:
    def test_pp_ep_checkpoint_reshards_into_fsdp(self, tmp_path, cpu_devices):
        """Checkpoint-level half of the pp-stacked -> pure-FSDP reshape: params
        laid out over a pp=2 x dp_shard=2 x ep=2 mesh restore bitwise onto a
        dp_shard=8 mesh. (Training under pp is exercised elsewhere —
        tests/functional/test_train_recipe.py; the reshard itself is
        mesh-math only.)"""
        from automodel_tpu.checkpoint.checkpointing import (
            Checkpointer, CheckpointingConfig,
        )
        from automodel_tpu.checkpoint.reshard import build_topology
        from automodel_tpu.parallel.mesh import MeshContext

        ctx_a = MeshContext(pp=2, dp_shard=2, ep=2)
        ctx_b = MeshContext(dp_shard=8)
        mesh_a, mesh_b = ctx_a.build_mesh(), ctx_b.build_mesh()

        rng = np.random.RandomState(3)
        host = {
            "embed": np.asarray(rng.randn(16, 8), np.float32),
            "layers": {"wq": np.asarray(rng.randn(4, 8, 8), np.float32)},
        }
        spec_a = {"embed": P("dp_shard", None),
                  "layers": {"wq": P("pp", ("dp_shard", "ep"), None)}}
        spec_b = {"embed": P("dp_shard", None),
                  "layers": {"wq": P(None, "dp_shard", None)}}
        params_a = jax.tree.map(
            lambda v, s: jax.device_put(jnp.asarray(v), NamedSharding(mesh_a, s)),
            host, spec_a, is_leaf=lambda x: isinstance(x, np.ndarray))

        ck = Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck")))
        ck.topology = build_topology(ctx_a)
        ck.save(1, params_a)

        events = []
        ck2 = Checkpointer(CheckpointingConfig(checkpoint_dir=str(tmp_path / "ck")))
        ck2.topology = build_topology(ctx_b)
        ck2.event_sink = lambda step, event, **f: events.append((event, f))
        template = jax.tree.map(
            lambda v, s: jax.device_put(jnp.zeros_like(jnp.asarray(v)),
                                        NamedSharding(mesh_b, s)),
            host, spec_b, is_leaf=lambda x: isinstance(x, np.ndarray))
        restored, _, client = ck2.load(template, step=1)

        delta = client["__elastic__"]["delta"]
        assert delta["pp"] == [2, 1] and delta["ep"] == [2, 1]
        assert delta["dp_shard"] == [2, 8]
        assert [e for e, _ in events] == ["elastic_restore"]

        wq = restored["layers"]["wq"]
        assert wq.sharding.mesh.shape["dp_shard"] == 8
        assert wq.sharding.spec == spec_b["layers"]["wq"]
        np.testing.assert_array_equal(np.asarray(jax.device_get(wq)),
                                      host["layers"]["wq"])
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored["embed"])), host["embed"])


class TestWarmRestartWarmup:
    """AOT warmup of the epoch-tail shape: 40 samples / batch 8 = 5 batches,
    grad_acc 2 -> steps of 2,2,1 microbatches per epoch. The 1-micro trailing
    stack is a second step shape: without warmup it falls through to jit
    (counted), with warmup it is pre-compiled into the executor's variant
    table and the whole run stays on the AOT path."""

    def _run(self, tmp_path, name, warmup):
        extra = textwrap.dedent(f"""\
        compile_cache:
          warmup: {str(warmup).lower()}
        """).replace("\n", "\n    ")
        cfg = load_config(_write_cfg(tmp_path, name, dp_shard=8, max_steps=6,
                                     grad_acc=2, num_samples=40, extra=extra))
        TrainFinetuneRecipeForNextTokenPrediction(cfg).setup() \
            .run_train_validation_loop()
        rows = _rows(tmp_path, name)
        summary = next(r for r in rows if r.get("event") == "compile_summary")
        return rows, summary

    def test_warmup_precompiles_trailing_shape(self, tmp_path, cpu_devices):
        rows, summary = self._run(tmp_path, "warm", warmup=True)
        assert summary["compile_aot"] >= 1
        assert summary["compile_aot_variant"] == 1  # the 1-micro tail shape
        assert summary["compile_aot_shape_fallback"] == 0
        assert summary["compile_jit_fallback"] == 0
        variant_rows = [r for r in rows if r.get("event") == "compile_variant"]
        assert len(variant_rows) == 1 and variant_rows[0]["variants"] == 2
        losses = [r["loss"] for r in rows if "loss" in r]
        assert len(losses) == 6 and np.isfinite(losses).all()

    def test_without_warmup_tail_shape_falls_back(self, tmp_path, cpu_devices):
        _, summary = self._run(tmp_path, "cold", warmup=False)
        assert summary["compile_aot_variant"] == 0
        # every epoch tail (steps 3 and 6) ran the fallback path, and each
        # occurrence is counted — silent jit demotion was the bug
        assert summary["compile_aot_shape_fallback"] >= 1


@pytest.mark.chaos
@pytest.mark.elastic
class TestChaosElastic:
    def test_injected_topology_change_drives_elastic_resume(self, tmp_path, cpu_devices):
        """Deterministic chaos (resilience/chaos.py): at step 4 the injector
        checkpoints and raises ElasticTopologyChange carrying the resized
        mesh; the harness (this test) restarts the recipe on that mesh and
        resume takes the elastic path."""
        from automodel_tpu.resilience.elastic import ElasticTopologyChange

        ckpt = tmp_path / "ckpt"
        chaos = textwrap.dedent("""\
        resilience:
          enabled: true
          anomaly: {enabled: false}
          elastic: {enabled: true, allow_joiners: true}
          chaos:
            enabled: true
            elastic_steps: [4]
            elastic_mesh: {dp_shard: 4, tp: 2}
        """).replace("\n", "\n    ")
        cfg = load_config(_write_cfg(tmp_path, "c1", dp_shard=8, max_steps=8,
                                     ckpt_dir=ckpt, ckpt_every=100, extra=chaos))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        with pytest.raises(ElasticTopologyChange) as exc_info:
            recipe.run_train_validation_loop()
        exc = exc_info.value
        assert exc.step == 4
        assert exc.new_mesh == {"dp_shard": 4, "tp": 2}
        # the injector checkpointed before dying — that is the contract that
        # makes the restart lossless
        assert (ckpt / "step_4").is_dir()
        assert recipe.checkpointer.latest_step() == 4

        # "restart" on the mesh the exception prescribes
        elastic = textwrap.dedent("""\
        resilience:
          enabled: true
          anomaly: {enabled: false}
          elastic: {enabled: true, allow_joiners: true}
        """).replace("\n", "\n    ")
        cfg2 = load_config(_write_cfg(
            tmp_path, "c2", dp_shard=exc.new_mesh["dp_shard"],
            tp=exc.new_mesh["tp"], max_steps=8, ckpt_dir=ckpt, ckpt_every=100,
            extra=elastic))
        r2 = TrainFinetuneRecipeForNextTokenPrediction(cfg2).setup()
        assert r2.step_scheduler.step == 4
        r2.run_train_validation_loop()
        rows = _rows(tmp_path, "c2")
        restore = [r for r in rows
                   if r.get("resilience/event") == "elastic_restore"]
        assert len(restore) == 1
        assert "dp_shard 8->4" in restore[0]["resilience/delta"]
        losses = {r["step"]: r["loss"] for r in rows if "loss" in r}
        assert sorted(losses) == [5, 6, 7, 8]
        assert all(np.isfinite(v) for v in losses.values())
