"""Everything a cell is, read from data files found by the names in BENCHMARK.json."""

from __future__ import annotations

import copy
import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmarks")
# HF keys of a configuration file; every other key is the benchmark's own note
NOTE_KEYS = ("source", "published", "reduced", "family", "reference", "assumed", "deployment",
              "why", "recipe", "expected_kernels", "control", "tiny")
# the plain model of a configuration that names none (``benchmarks/reference/<name>.py``)
DEFAULT_REFERENCE = "decoder"
# notes of a traffic file; every other key is a parameter of its generator
TRAFFIC_NOTE_KEYS = ("kind", "padding", "why", "tiny")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark_json() -> dict:
    return _load(os.path.join(ROOT, "BENCHMARK.json"))


def _deep_update(base: dict, extra: dict) -> dict:
    for key, value in extra.items():
        if isinstance(value, dict) and isinstance(base.get(key), dict):
            _deep_update(base[key], value)
        else:
            base[key] = copy.deepcopy(value)
    return base


class Cell:
    """One entry of ``workloads``: configuration, traffic and the cell's own file."""

    def __init__(self, name: str, tiny: bool = False, control: bool = False):
        bench = benchmark_json()
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in bench["workloads"])
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has: {known}")
        config_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.name = name
        self.tiny = tiny
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.config = _load(os.path.join(ROOT, config_entry["file"]))
        self.workload = _load(os.path.join(BENCH_DIR, "workloads", name + ".json"))
        self.traffic = _load(os.path.join(BENCH_DIR, "traffic", entry["traffic"] + ".json"))
        if tiny:
            for part in (self.config, self.workload, self.traffic):
                _deep_update(part, part.get("tiny", {}))
        self.family = self.config["family"]
        # the one module that answers for everything that depends on the architecture
        self.reference = importlib.import_module(
            "benchmarks.reference." + self.config.get("reference", DEFAULT_REFERENCE))
        self.model = {k: v for k, v in self.config.items() if k not in NOTE_KEYS}
        # {group: [layer indices]}: the stacks both sides' per-leaf sums are kept by
        self.layer_groups = self.reference.layer_groups(self.model)
        self.recipe = copy.deepcopy(self.config["recipe"])
        _deep_update(self.recipe, self.workload.get("recipe", {}))
        if control:
            _deep_update(self.recipe, self.config["control"])
        self.limits = self.workload["limits"]
        # the generator's parameters: the traffic file without its notes
        self.generator = self.traffic["kind"]
        self.traffic_params = {k: v for k, v in self.traffic.items()
                               if k not in TRAFFIC_NOTE_KEYS}
        self.seq_len = int(self.traffic["seq_len"])
        self.micro_batch = int(self.workload["micro_batch_size"])
        self.grad_acc = int(self.workload.get("grad_acc_steps", 1))
        self.tokens_per_step = self.seq_len * self.micro_batch * self.grad_acc
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def kernel_cost(self, kernel: str) -> dict[str, float]:
        """Operations and bytes one optimizer step of this cell needs of ``kernel``, as
        the cell's reference reckons them for its architecture."""
        return self.reference.kernel_costs(self.model, self.micro_batch * self.grad_acc,
                                           self.seq_len)[kernel]

    def recipe_config(self, seed: int, out_dir: str) -> dict:
        """The YAML a user would write for this cell, as a dict."""
        cfg = copy.deepcopy(self.recipe)
        cfg["seed"] = int(seed) % (2**31 - 1)
        cfg.setdefault("model", {})["config"] = self.model
        cfg["distributed"] = self.workload.get("distributed", {"dp_shard": self.chips})
        cfg["dataset"] = {
            "_target_": f"benchmarks.generators.{self.generator}.Dataset",
            "vocab_size": self.model["vocab_size"],
            "seed": int(seed),
            **self.traffic_params,
        }
        cfg["micro_batch_size"] = self.micro_batch
        cfg["seq_len"] = self.seq_len
        cfg["step_scheduler"] = {"grad_acc_steps": self.grad_acc, "max_steps": 10**9,
                                 "num_epochs": 1, "ckpt_every_steps": 0, "log_every_steps": 1}
        cfg["checkpoint"] = {"enabled": False}
        # every program of the run goes to the persistent cache, the small ones too:
        # a second run in the same checkout compiles nothing, so set-up is steady
        cfg["compile_cache"] = {"min_compile_time_secs": 0, "min_entry_size_bytes": 0}
        cfg["output_dir"] = out_dir
        return cfg
