#!/usr/bin/env python
"""Self-checking CPU smoke for elastic topology (docs/resilience.md).

Simulates a slice resize the only way a single box can: the XLA host-platform
device count is fixed per process, so each phase runs in its own interpreter
with a different ``--xla_force_host_platform_device_count``. Four phases:

1. baseline: 8 virtual devices, ``dp_shard=8``, trains uninterrupted;
2. phase A: same mesh, checkpoints every 3 steps, stops at step 6;
3. phase B: 4 virtual devices, ``dp_shard=4``, resumes from phase A's
   checkpoint directory — the elastic restore path;
4. warm restart: two identical fresh runs sharing a persistent XLA compile
   cache — the second must report zero cache misses and zero jit demotions
   in its ``compile_summary`` row.

Asserts phase B classified the restore as elastic (an ``elastic_restore``
event naming the dp_shard 8->4 delta), re-partitioned the dataloader cursor
(an ``elastic_data_repartition`` event with zero re-fed examples — the global
batch size is process-count-bound and did not change), and finished with a
final loss matching the uninterrupted baseline (same data order, so the
trajectory continues rather than restarts).

Usage:  python tools/elastic_smoke.py [--workdir DIR]

The same scenario runs under pytest as ``pytest -m elastic``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

MAX_STEPS = 16
SWITCH_STEP = 6
CKPT_EVERY = 3
LOSS_TOL = 0.5


def _write_cfg(root: str, name: str, *, dp_shard: int, ckpt_dir: str | None,
               max_steps: int, cache_dir: str | None = None) -> str:
    text = textwrap.dedent(f"""\
    seed: 11
    output_dir: {root}/{name}/out
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
      max_steps: {max_steps}
      num_epochs: 10
      handle_sigterm: false
      ckpt_every_steps: {CKPT_EVERY if ckpt_dir else 0}
    optimizer:
      lr: 1.0e-2
      weight_decay: 0.0
      max_grad_norm: 1.0
    lr_scheduler:
      lr_warmup_steps: 2
    checkpoint:
      enabled: {str(ckpt_dir is not None).lower()}
      checkpoint_dir: {ckpt_dir or f"{root}/{name}/ckpt"}
    resilience:
      enabled: true
      anomaly: {{enabled: false}}
      elastic: {{enabled: true, allow_joiners: true}}
    """)
    if cache_dir:
        text += textwrap.dedent(f"""\
        compile_cache:
          dir: {cache_dir}
          min_entry_size_bytes: 0
          min_compile_time_secs: 0
        """)
    path = os.path.join(root, f"{name}.yaml")
    os.makedirs(os.path.join(root, name), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)
    return path


def _run_phase(cfg_path: str, devices: int) -> None:
    """One training phase in a fresh interpreter pinned to ``devices`` virtual
    CPU devices (the whole point: device count is per-process)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--run", cfg_path],
        env=env, timeout=900,
    )
    if proc.returncode != 0:
        raise RuntimeError(f"phase {cfg_path} failed with rc={proc.returncode}")


def _run_child(cfg_path: str) -> int:
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from automodel_tpu.config.loader import load_config
    from automodel_tpu.recipes.llm.train_ft import (
        TrainFinetuneRecipeForNextTokenPrediction,
    )

    cfg = load_config(cfg_path)
    recipe = TrainFinetuneRecipeForNextTokenPrediction(cfg).setup()
    recipe.run_train_validation_loop()
    return 0


def _rows(root: str, name: str) -> list[dict]:
    with open(os.path.join(root, name, "out", "training.jsonl")) as f:
        return [json.loads(line) for line in f]


def main(workdir: str | None = None) -> int:
    owns_workdir = workdir is None
    root = workdir or tempfile.mkdtemp(prefix="elastic_smoke_")
    try:
        print(f"[elastic_smoke] workdir {root}")

        print("[elastic_smoke] 1/3 uninterrupted baseline on 8 devices ...")
        _run_phase(_write_cfg(root, "base", dp_shard=8, ckpt_dir=None,
                              max_steps=MAX_STEPS), devices=8)
        base_losses = {r["step"]: r["loss"] for r in _rows(root, "base") if "loss" in r}

        ckpt_dir = os.path.join(root, "shared_ckpt")
        print(f"[elastic_smoke] 2/3 phase A: dp_shard=8, checkpoint every "
              f"{CKPT_EVERY}, stop at step {SWITCH_STEP} ...")
        _run_phase(_write_cfg(root, "phase_a", dp_shard=8, ckpt_dir=ckpt_dir,
                              max_steps=SWITCH_STEP), devices=8)

        print("[elastic_smoke] 3/3 phase B: resume on 4 devices, dp_shard=4 ...")
        _run_phase(_write_cfg(root, "phase_b", dp_shard=4, ckpt_dir=ckpt_dir,
                              max_steps=MAX_STEPS), devices=4)
        rows = _rows(root, "phase_b")

        events = [r.get("resilience/event") for r in rows if "resilience/event" in r]
        assert "elastic_restore" in events, f"no elastic_restore event; saw {events}"
        restore = next(r for r in rows
                       if r.get("resilience/event") == "elastic_restore")
        assert "dp_shard 8->4" in restore["resilience/delta"], restore

        repart = next((r for r in rows
                       if r.get("event") == "elastic_data_repartition"), None)
        assert repart is not None, "dataloader state was not re-partitioned"
        # single-process smoke: the global batch size is process-count-bound,
        # so the reshape must be example-exact — nothing re-fed
        assert "refed_examples" not in repart, repart
        assert repart["new_cursor"] * repart["new_batch_size"] == \
            repart["consumed_examples"], repart

        losses = {r["step"]: r["loss"] for r in rows if "loss" in r}
        assert min(losses) == SWITCH_STEP + 1, (
            f"phase B first step {min(losses)}, expected {SWITCH_STEP + 1}"
        )
        bad = {s: v for s, v in losses.items() if v != v}
        assert not bad, f"non-finite losses after elastic resume: {bad}"
        drift = abs(losses[MAX_STEPS] - base_losses[MAX_STEPS])
        assert drift < LOSS_TOL, (
            f"final loss {losses[MAX_STEPS]:.3f} drifted {drift:.3f} from "
            f"baseline {base_losses[MAX_STEPS]:.3f}: the trajectory restarted "
            "instead of continuing"
        )
        print(f"[elastic_smoke]     resumed {SWITCH_STEP}->{min(losses)}, "
              f"delta '{restore['resilience/delta']}', final loss "
              f"{losses[MAX_STEPS]:.3f} (baseline {base_losses[MAX_STEPS]:.3f})")

        # --- warm restart: two identical fresh runs sharing a persistent XLA
        # cache; the second must deserialize every compile (the other half of
        # "instant warm restart" — the elastic half is asserted above)
        cache_dir = os.path.join(root, "xla_cache")
        print("[elastic_smoke] 4/4 warm restart: cold run then warm run "
              "sharing a persistent compile cache ...")
        _run_phase(_write_cfg(root, "cold", dp_shard=8, ckpt_dir=None,
                              max_steps=4, cache_dir=cache_dir), devices=8)
        _run_phase(_write_cfg(root, "warm", dp_shard=8, ckpt_dir=None,
                              max_steps=4, cache_dir=cache_dir), devices=8)
        cold = next(r for r in _rows(root, "cold")
                    if r.get("event") == "compile_summary")
        warm = next(r for r in _rows(root, "warm")
                    if r.get("event") == "compile_summary")
        assert cold["compile_cache_misses"] > 0, cold  # cache was actually live
        assert warm["compile_cache_misses"] == 0, (
            f"warm restart recompiled: {warm}"
        )
        assert warm["compile_cache_hits"] > 0, warm
        # and nothing fell off the AOT path mid-run
        assert warm["compile_jit_fallback"] == 0, warm
        print(f"[elastic_smoke]     warm run: {warm['compile_cache_hits']} "
              "cache hits, 0 misses, 0 demotions")
        print("[elastic_smoke] PASS")
        return 0
    finally:
        if owns_workdir:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workdir", default=None,
                        help="keep artifacts here instead of a temp dir")
    parser.add_argument("--run", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.run:
        sys.exit(_run_child(args.run))
    sys.exit(main(args.workdir))
