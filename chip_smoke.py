#!/usr/bin/env python3
"""The quickest proof that the train path still starts on the chip.

    python chip_smoke.py               # one TPU chip (what the driver runs)
    python chip_smoke.py --four-chips  # FSDP over four chips vs one device

Drives the trainer through its normal entry point (``automodel finetune llm -c
examples/llm_finetune/llama3_2_1b_chip_smoke.yaml``) in this process: Llama-3.2-1B
at full width and depth, random init from the YAML's seed, mock ``arith`` data,
8 optimizer steps. It then checks, and fails on: a non-finite loss; a loss that
did not fall by ``LOSS_MARGIN``; a second compile of the train step; a step
whose HLO holds no ``tpu_custom_call``; a kernel that gave way to its stand-in
(the run header's ``kernels``).

Standard output is one JSON object per line; the last is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU the script exits non-zero before anything runs: there is no CPU
run of it. One process, nothing spawned: a chip belongs to one process.
"""

from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
YAML = os.path.join(HERE, "examples", "llm_finetune", "llama3_2_1b_chip_smoke.yaml")
OUT_ROOT = os.path.join(HERE, "chiprun_out")  # the chip tool brings this directory back
STEPS = 8
# loss at step 8 must be below loss at step 1 by this much (nats). On random-init
# tied embeddings the first loss sits above ln(vocab) = 11.76; eight adafactor
# steps at lr 1e-3 on one repeated batch took it from 12.18 to 8.70 on the chip
# (PERF.md, PR 22).
LOSS_MARGIN = 1.0
# FSDP-4 vs one device, same seed and global batch. The two programs differ in
# reduction order and in the CE (XLA blockwise on the mesh, fused Pallas on one
# device; the one-device step with the XLA CE compiles to 15.61 of the chip's
# 15.75 GiB, too tight to sit beside anything else), in bf16. Compared: steps 1
# to FOUR_CHIP_STEPS_COMPARED, three sharded updates deep, where a wrong gradient
# reduction or a wrong shard would move the loss by tenths. Later steps are
# printed and NOT bounded: from step 5 on this one repeated batch at lr 1e-3
# stops falling monotonically even on one device, and pure-bf16 weights turn a
# last-bit difference between two correct programs into tenths of a nat
# (PERF.md, PR 22) — no bound there could tell a right run from a wrong one.
FOUR_CHIP_STEPS_COMPARED = 4
FOUR_CHIP_ATOL = 0.01
# a backend compile this long after step 1 can only be the train step again
LONG_COMPILE_S = 5.0
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def require_tpu(count: int) -> list:
    """The devices, or SystemExit: no stand-in for the chip."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {devices[0].platform!r}); "
            "this script runs nothing on a CPU")
    if len(devices) != count:
        raise SystemExit(f"chip_smoke: needs {count} chip(s), JAX reports {len(devices)}")
    return devices


class _CompileClock:
    """Wall-clock stamps of every backend compile of the process."""

    def __init__(self):
        import jax.monitoring

        self.events: list[tuple[float, float]] = []  # (finished at, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, seconds: float, **_kw) -> None:
        if event == _COMPILE_EVENT:
            self.events.append((time.time(), float(seconds)))

    def long_compiles_after(self, t: float) -> list[float]:
        return [s for at, s in self.events if at > t and s >= LONG_COMPILE_S]


def train_phase(name: str, overrides: list[str], clock: _CompileClock,
                recipe_cls=None, steps: int = STEPS, loss_margin: float = LOSS_MARGIN):
    """Run the recipe for ``steps`` steps; print its lines; check what any
    platform can show. Returns ``(recipe, result)``."""
    from automodel_tpu.observability import compile_cache

    out_dir = os.path.join(OUT_ROOT, f"chip_smoke_{name}")
    jsonl = os.path.join(out_dir, "training.jsonl")
    if os.path.exists(jsonl):
        os.remove(jsonl)  # the stream appends; this run's rows only
    argv = ["-c", YAML, "--output_dir", out_dir,
            "--step_scheduler.max_steps", str(steps), *overrides]
    cache_before = compile_cache.counts()
    t0 = time.time()
    if recipe_cls is None:
        from automodel_tpu.cli.app import main as automodel

        recipe = automodel(["finetune", "llm", *argv])
    else:
        from automodel_tpu.config.cli_overrides import parse_args_and_load_config

        recipe = recipe_cls(parse_args_and_load_config(argv))
        recipe.setup()
        recipe.run_train_validation_loop()
    wall_s = time.time() - t0

    with open(jsonl) as f:
        rows = [json.loads(line) for line in f]
    header = next(r for r in rows if r.get("run_header"))
    summary = next(r for r in rows if r.get("event") == "compile_summary")
    step_rows = [r for r in rows if "loss" in r and "event" not in r]
    for r in step_rows:
        emit({"phase": name, "step": r["step"], "loss": r["loss"],
              "step_time_s": r.get("step_time_s")})
    losses = [r["loss"] for r in step_rows]
    cache_now = compile_cache.counts()
    result = {
        "phase": name,
        "losses": losses,
        "kernels": header["kernels"],
        "mesh": header["mesh"],
        "device_order": recipe.mesh_ctx.device_order,
        "compile_s": recipe.observability.compile_time_s,
        "wall_s": round(wall_s, 1),
        "compile_counts": dict(recipe.observability.compile_counts),
        "long_compiles_after_step_1": clock.long_compiles_after(step_rows[0]["ts"]),
        "compile_cache": {
            "dir": compile_cache.snapshot().get("dir"),
            "hits": cache_now["hits"] - cache_before["hits"],
            "misses": cache_now["misses"] - cache_before["misses"],
        },
        "peak_hbm_bytes": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in recipe.mesh.devices.flat
        ],
    }
    emit(result)

    if len(losses) != steps:
        raise RuntimeError(f"{name}: {len(losses)} loss rows for {steps} steps")
    if not all(isinstance(x, float) and math.isfinite(x) for x in losses):
        raise RuntimeError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0] - loss_margin:
        raise RuntimeError(
            f"{name}: loss did not fall by {loss_margin}: {losses[0]} -> {losses[-1]}")
    counts = result["compile_counts"]
    if counts["aot"] != 1 or counts["jit_fallback"] or counts["aot_shape_fallback"]:
        raise RuntimeError(f"{name}: the train step did not compile exactly once, "
                           f"ahead of time: {counts}")
    if result["long_compiles_after_step_1"]:
        raise RuntimeError(f"{name}: compiles after step 1: "
                           f"{result['long_compiles_after_step_1']} s")
    if summary.get("compile_jit_fallback"):
        raise RuntimeError(f"{name}: compile_summary reports a jit fallback: {summary}")
    return recipe, result


def check_on_chip(name: str, recipe, result: dict, hlo_has: tuple[str, ...] = (),
                  **expected_kernels: str) -> None:
    """What only the chip's compiler can show: the kernels asked for are in the
    step, compiled, and none gave way to its stand-in."""
    from automodel_tpu.ops import kernels

    kernels.require_compiled(result["kernels"], **expected_kernels)
    hlo = recipe.observability._hlo_text or ""
    for op in ("tpu_custom_call", *hlo_has):
        if op not in hlo:
            raise RuntimeError(f"{name}: no {op} in the step's optimized HLO")


def check_spread(recipe) -> None:
    """FSDP really spread the work: every parameter leaf over 1 MiB lives in
    four equal shards on four devices, and the devices hold about the same."""
    import jax

    big_leaves = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(recipe.train_params)[0]:
        if leaf.nbytes <= 1 << 20:
            continue
        big_leaves += 1
        owners = {s.device for s in leaf.addressable_shards}
        shard_bytes = {s.data.nbytes for s in leaf.addressable_shards}
        if len(owners) != 4 or shard_bytes != {leaf.nbytes // 4}:
            raise RuntimeError(
                f"fsdp4: {jax.tree_util.keystr(path)} is not split over four devices "
                f"({len(owners)} devices, shard bytes {shard_bytes})")
    in_use = [d.memory_stats()["bytes_in_use"] for d in recipe.mesh.devices.flat]
    emit({"phase": "fsdp4", "big_leaves_in_four_shards": big_leaves,
          "bytes_in_use": in_use})
    if max(in_use) - min(in_use) >= 0.2 * max(in_use):
        raise RuntimeError(f"fsdp4: per-device bytes_in_use differ by 20% or more: {in_use}")


def one_chip() -> None:
    clock = _CompileClock()
    recipe, result = train_phase("one_chip", [], clock)
    check_on_chip("one_chip", recipe, result,
                  attention="flash", attention_bwd="fused", loss="pallas")


def four_chips() -> None:
    import jax

    from automodel_tpu.parallel.mesh import MeshContext
    from automodel_tpu.recipes.llm.train_ft import (
        TrainFinetuneRecipeForNextTokenPrediction as Recipe,
    )

    clock = _CompileClock()
    recipe, fsdp = train_phase("fsdp4", ["--distributed.dp_shard", "4"], clock)
    check_on_chip("fsdp4", recipe, fsdp, hlo_has=("all-gather", "reduce-scatter"),
                  attention="flash", attention_bwd="fused", loss="xla")
    if fsdp["device_order"] != "topology":
        raise RuntimeError("fsdp4: the mesh fell back to enumeration device order")
    check_spread(recipe)

    # the same eight steps on ONE device of this process. The recipe meshes all
    # of jax.devices(); the smoke hands it the first one.
    del recipe
    gc.collect()

    class OneDeviceRecipe(Recipe):
        def _build_mesh(self, dist_cfg):
            ctx = MeshContext(**{**dist_cfg, "dp_shard": 1}, world_size=1)
            return ctx, ctx.build_mesh(jax.devices()[:1])

    recipe, one = train_phase("one_device", [], clock, recipe_cls=OneDeviceRecipe)
    check_on_chip("one_device", recipe, one,
                  attention="flash", attention_bwd="fused", loss="pallas")
    diffs = [abs(a - b) for a, b in zip(fsdp["losses"], one["losses"])]
    emit({"phase": "compare", "abs_loss_diff": diffs,
          "steps_compared": FOUR_CHIP_STEPS_COMPARED, "atol": FOUR_CHIP_ATOL})
    if any(d > FOUR_CHIP_ATOL for d in diffs[:FOUR_CHIP_STEPS_COMPARED]):
        raise RuntimeError(
            f"fsdp4 and one-device losses differ by {diffs[:FOUR_CHIP_STEPS_COMPARED]} "
            f"over the first {FOUR_CHIP_STEPS_COMPARED} steps (allowed {FOUR_CHIP_ATOL}): "
            f"{fsdp['losses']} vs {one['losses']}")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run ONLY the FSDP-over-four-chips phase and the one-device "
                         "run it is compared with")
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.four_chips else 1)
    (four_chips if args.four_chips else one_chip)()
    emit({"ok": True, "device": {"platform": devices[0].platform,
                                 "kind": devices[0].device_kind,
                                 "count": len(devices)}})
    return 0


if __name__ == "__main__":
    sys.exit(main())
