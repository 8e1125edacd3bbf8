"""Benchmark recipe (reference BenchmarkingRecipeForNextTokenPrediction,
recipes/llm/benchmark.py:34): warmup + timed steps on mock data, reporting
tokens/sec(/chip), model TFLOPs/sec(/chip), and MFU vs the device's peak
(``_log_benchmark_summary`` parity, benchmark.py:342). Optional jax.profiler trace
windows replace the reference's nsys capture (cfg keys profile_start/profile_end).
"""

from __future__ import annotations

import json
import logging
import os
import time

import jax
import numpy as np

from automodel_tpu.config.loader import ConfigNode
from automodel_tpu.config.cli_overrides import parse_args_and_load_config
from automodel_tpu.recipes.llm.train_ft import TrainFinetuneRecipeForNextTokenPrediction
from automodel_tpu.utils.flops import flops_per_token, mfu

logger = logging.getLogger(__name__)

__all__ = ["BenchmarkingRecipeForNextTokenPrediction", "main"]


class BenchmarkingRecipeForNextTokenPrediction(TrainFinetuneRecipeForNextTokenPrediction):
    def run_benchmark(self) -> dict:
        cfg = self.cfg
        warmup = int(cfg.get("benchmark.warmup_steps", 3))
        steps = int(cfg.get("benchmark.timed_steps", 10))
        profile_start = cfg.get("benchmark.profile_start")
        profile_end = cfg.get("benchmark.profile_end")
        profile_dir = cfg.get("benchmark.profile_dir") or os.path.join(
            getattr(self, "output_dir", "."), "jax_trace")

        from automodel_tpu.data.collate import stack_batches

        # pre-stage every batch on device BEFORE the timed window: per-step
        # device_put round-trips would otherwise bill host I/O to the step
        # time being measured
        it = iter(self.step_scheduler)
        staged = [
            {
                k: jax.device_put(v, self.rules.sharding((None, "batch", None)))
                for k, v in stack_batches(next(it)).items()
            }
            for _ in range(warmup + steps)
        ]
        staged_it = iter(staged)
        get = lambda: next(staged_it)

        tracing = False
        with self.mesh:
            m = None
            for _ in range(warmup):
                self.params, self.opt_state, m = self._train_step(self.params, self.opt_state, get())
            if m is not None:
                jax.block_until_ready(m["loss"])

            # time the whole window with ONE sync at each end: a per-step host
            # sync stalls the device pipeline every step
            t0 = time.perf_counter()
            for i in range(steps):
                if profile_start is not None and i == int(profile_start):
                    jax.profiler.start_trace(profile_dir)
                    tracing = True
                self.params, self.opt_state, m = self._train_step(self.params, self.opt_state, get())
                if tracing and profile_end is not None and i >= int(profile_end):
                    jax.block_until_ready(m["loss"])  # flush before closing the trace
                    jax.profiler.stop_trace()
                    tracing = False
                    logger.info("profile written to %s", profile_dir)
            jax.block_until_ready(m["loss"])
            window = time.perf_counter() - t0
            step_times = [window / steps]
            if tracing:
                jax.profiler.stop_trace()
                logger.info("profile written to %s", profile_dir)

        n_micro = self.step_scheduler.grad_acc_steps
        tokens_per_step = n_micro * self.micro_batch_size * self.seq_len * jax.process_count()
        mean_t = float(np.mean(step_times))
        tps = tokens_per_step / mean_t
        n_chips = jax.device_count()
        fpt = flops_per_token(self.hf_config, self.seq_len)
        device_kind = jax.devices()[0].device_kind
        result = {
            "step_time_s": round(mean_t, 4),
            "tokens_per_sec": round(tps, 1),
            "tokens_per_sec_per_chip": round(tps / n_chips, 1),
            "model_tflops_per_sec_per_chip": round(tps * fpt / 1e12 / n_chips, 2),
            "device_kind": device_kind,
            "n_chips": n_chips,
            "loss": float(m["loss"]),
        }
        util = mfu(tps, fpt, device_kind, n_chips)
        if util is not None:  # a CPU has no peak: no mfu in its result
            result["mfu"] = round(util, 4)
        logger.info("benchmark: %s", result)
        # setup() resolved (or generated) the run dir once — benchmark.json
        # must land next to training.jsonl, not in a second timestamped dir
        out_dir = getattr(self, "output_dir", None) or cfg.get("output_dir", ".")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "benchmark.json"), "w") as f:
            json.dump(result, f, indent=2)
        return result


def main(cfg: ConfigNode | None = None, argv=None):
    if cfg is None:
        cfg = parse_args_and_load_config(argv)
    recipe = BenchmarkingRecipeForNextTokenPrediction(cfg)
    recipe.setup()
    result = recipe.run_benchmark()
    print(json.dumps(result))
    return result


if __name__ == "__main__":
    main()
