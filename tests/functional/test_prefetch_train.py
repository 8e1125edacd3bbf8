"""Overlapped input pipeline end-to-end: with ``dataloader.prefetch.enabled``
the recipe must produce the identical loss trajectory (same batches, same
order), resume exactly through in-flight batches, and survive the resilience
paths (chaos rollback, SIGTERM preemption) without deadlocking the worker."""

import json
import textwrap
import threading
import time

import numpy as np
import pytest

from automodel_tpu.config.loader import load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction

PREFETCH = textwrap.dedent("""\
dataloader:
  prefetch:
    enabled: true
    host_depth: 3
    device_depth: 2
""").replace("\n", "\n    ")


def _write_cfg(tmp_path, extra="", max_steps=6, grad_acc=2, ckpt=False,
               ckpt_every=3, name="cfg.yaml"):
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
      ckpt_every_steps: {ckpt_every if ckpt else 0}
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
    p = tmp_path / name
    p.write_text(textwrap.dedent(cfg))
    return p


def _rows(tmp_path):
    rows = [json.loads(line) for line in open(tmp_path / "out" / "training.jsonl")]
    return [r for r in rows
            if "run_header" not in r
            and r.get("event") not in ("compile_costs", "compile_summary", "setup_summary")]


class TestPrefetchTrajectory:
    def test_identical_losses_and_depth_logged(self, tmp_path, cpu_devices):
        sync_dir = tmp_path / "sync"
        sync_dir.mkdir()
        cfg = load_config(_write_cfg(sync_dir))
        TrainFinetuneRecipeForNextTokenPrediction(cfg).setup().run_train_validation_loop()
        sync_rows = _rows(sync_dir)

        pf_dir = tmp_path / "prefetch"
        pf_dir.mkdir()
        cfg = load_config(_write_cfg(pf_dir, extra=PREFETCH))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        pf_rows = _rows(pf_dir)

        assert [r["step"] for r in pf_rows] == [r["step"] for r in sync_rows]
        for s, p in zip(sync_rows, pf_rows):
            # identical batches in identical order -> bitwise-identical math
            assert p["loss"] == s["loss"], f"step {p['step']} diverged"
        # observability satellite: every prefetch row reports pipeline depth
        assert all("prefetch_depth" in r for r in pf_rows)
        assert all("prefetch_depth" not in r for r in sync_rows)
        # the pipeline must be torn down with the pass
        assert recipe._pipeline is None

    def test_resume_exact_with_in_flight_batches(self, tmp_path, cpu_devices):
        """The step-3 checkpoint is written while the worker has run ahead;
        the persisted state must be the consumed position, so the resumed run
        replays steps 4..6 bit-identically."""
        cfg = load_config(_write_cfg(tmp_path, extra=PREFETCH, ckpt=True))
        TrainFinetuneRecipeForNextTokenPrediction(cfg).setup().run_train_validation_loop()
        rows1 = _rows(tmp_path)

        import shutil

        shutil.rmtree(tmp_path / "ckpt" / "step_6")
        (tmp_path / "ckpt" / "latest").unlink()
        (tmp_path / "out" / "training.jsonl").unlink()
        cfg2 = load_config(_write_cfg(tmp_path, extra=PREFETCH, name="cfg2.yaml",
                                      ckpt=True))
        r2 = TrainFinetuneRecipeForNextTokenPrediction(cfg2).setup()
        assert r2.step_scheduler.step == 3
        r2.run_train_validation_loop()
        rows2 = _rows(tmp_path)

        l1 = {r["step"]: r["loss"] for r in rows1}
        l2 = {r["step"]: r["loss"] for r in rows2}
        for s in (4, 5, 6):
            assert l2[s] == pytest.approx(l1[s], rel=1e-6), f"step {s} diverged"


class TestPrefetchResilience:
    _resilience = textwrap.dedent("""\
    resilience:
      enabled: true
      anomaly: {window: 20, min_history: 5}
      max_skipped_updates: 0
      rollback: {max_rollbacks: 2, skip_steps: 0}
      chaos:
        enabled: true
        nan_grad_steps: [6]
    """).replace("\n", "\n    ")

    def test_chaos_rollback_with_pipeline_active(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, extra=self._resilience + "\n    " + PREFETCH,
                                     ckpt=True, ckpt_every=4, max_steps=10, grad_acc=1))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
        recipe.run_train_validation_loop()
        rows = _rows(tmp_path)

        events = [r["resilience/event"] for r in rows if "resilience/event" in r]
        assert "rollback" in events and "rollback_done" in events
        done = next(r for r in rows if r.get("resilience/event") == "rollback_done")
        assert done["resilience/from_step"] == 6
        assert done["resilience/to_step"] == 4

        losses = {r["step"]: r["loss"] for r in rows if "loss" in r}
        assert 6 not in losses
        assert all(np.isfinite(v) for v in losses.values())
        assert max(losses) == 10  # recovered and finished the run
        # the replacement pass got its own pipeline; the old worker is gone
        assert recipe._pipeline is None

    def test_empty_buffer_truncation_takes_preemption_path(self, tmp_path,
                                                           cpu_devices, monkeypatch):
        """The input-bound deadlock case: the flag lands AFTER the consumer's
        step-K agreed check but BEFORE the worker's flag check behind the last
        batch the loop holds (K+1: the loop takes it while step K computes),
        with nothing buffered ahead — the worker ends the stream and step
        K+1's look-ahead pipeline.get() returns None. The loop must not
        conclude "done" (on a pod the other hosts are still stepping and their
        agreed allgather would hang); it rebuilds the pipeline, takes step
        K+2's batch, and the agreed check preempts the run at K+1."""
        from automodel_tpu.data import prefetch as prefetch_mod

        K = 3
        release = threading.Event()
        pause_at = {"n": K + 1}
        real_iter_source = prefetch_mod.HostPrefetcher._iter_source

        def paused_iter_source(self):
            inner = real_iter_source(self)

            def gen():
                produced = 0
                for item in inner:
                    produced += 1
                    yield item
                    # resumed here only when the worker asks for the NEXT
                    # item, i.e. after it stacked+enqueued this one and
                    # before the underlying iterator's post-yield flag
                    # check — exactly the window the race needs
                    if pause_at["n"] is not None and produced == pause_at["n"]:
                        pause_at["n"] = None
                        release.wait(timeout=30.0)

            return gen()

        monkeypatch.setattr(prefetch_mod.HostPrefetcher, "_iter_source",
                            paused_iter_source)

        cfg = load_config(_write_cfg(tmp_path, extra=PREFETCH, ckpt=True,
                                     ckpt_every=50, max_steps=50, grad_acc=1))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()

        real_agreed = recipe.step_scheduler.sigterm_agreed_at
        fired = {}

        def agreed(step):
            out = real_agreed(step)
            if step == K and not out and "at" not in fired:
                # consumer just cleared step K; raise the flag and only then
                # let the paused worker reach its flag check — it truncates
                # with the buffers empty
                fired["at"] = step
                recipe.step_scheduler._sigterm.set()
                recipe.step_scheduler.sigterm_time = time.monotonic()
                release.set()
            return out

        monkeypatch.setattr(recipe.step_scheduler, "sigterm_agreed_at", agreed)
        recipe.run_train_validation_loop()
        assert fired.get("at") == K

        rows = _rows(tmp_path)
        steps = [r["step"] for r in rows if "loss" in r]
        # one more consumed step, one rebuild in its look-ahead, then the
        # agreed preemption save
        assert max(steps) == K + 1
        import os

        latest = os.path.realpath(tmp_path / "ckpt" / "latest")
        assert latest.endswith(f"step_{K + 1}")
        assert recipe._pipeline is None
        live = [th for th in threading.enumerate() if th.name == "host-prefetch"]
        assert not live, "prefetch worker leaked past truncation recovery"

    def test_sigterm_preemption_drains_without_deadlock(self, tmp_path, cpu_devices):
        cfg = load_config(_write_cfg(tmp_path, extra=PREFETCH, ckpt=True,
                                     ckpt_every=50, max_steps=50, grad_acc=1))
        recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()

        fired = {}

        def fire_sigterm():
            # raise the local flag mid-run, as the cluster's SIGTERM would
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if (tmp_path / "out" / "training.jsonl").exists() and _rows(tmp_path):
                    recipe.step_scheduler._sigterm.set()
                    recipe.step_scheduler.sigterm_time = time.monotonic()
                    fired["at"] = time.monotonic()
                    return
                time.sleep(0.02)

        t = threading.Thread(target=fire_sigterm, daemon=True)
        t.start()
        recipe.run_train_validation_loop()
        t.join(timeout=5.0)
        assert "at" in fired, "sigterm thread never fired"

        rows = _rows(tmp_path)
        steps = [r["step"] for r in rows if "loss" in r]
        assert steps, "no steps completed before preemption"
        last = max(steps)
        assert last < 50, "run was not preempted"
        # the preemption checkpoint holds the consumed step, not the worker's
        import os

        latest = os.path.realpath(tmp_path / "ckpt" / "latest")
        assert latest.endswith(f"step_{last}")
        # worker thread exited with the pipeline
        assert recipe._pipeline is None
        live = [th for th in threading.enumerate() if th.name == "host-prefetch"]
        assert not live, "prefetch worker leaked past preemption"
