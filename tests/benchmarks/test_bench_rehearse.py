"""``benchmarks/run.py --rehearse``: the whole of a run at each configuration's tiny
sizes on the CPU — the recipe entered the normal way, the benchmark's weights and
stream, the window, the reference's two float32 steps, the comparison — and the
contract's result object on the last line. No device metric comes from a CPU."""

import importlib
import json
import os
import shutil

import pytest
import yaml

from benchmarks.harness import spec
from tests.benchmarks.rehearsal import rehearse, run_py

# a generator of a new kind, as the file a later benchmark PR would add: the Zipf stream
# with the first ``prompt_len`` tokens of every sequence masked out of the loss
PROMPTED_STREAM = '''
from benchmarks.generators import token_stream
from benchmarks.generators.token_stream import loss_floor  # noqa: F401


def batch(params, vocab_size, seed, step, rows):
    ids, labels = token_stream.batch(params, vocab_size, seed, step, rows)
    labels = labels.copy()
    labels[:, : params["prompt_len"] - 1] = -100  # target t predicts token t + 1
    return ids, labels


class Dataset(token_stream.Dataset):
    def __init__(self, vocab_size, seed, seq_len, zipf_exponent, prompt_len):
        super().__init__(vocab_size, seed, seq_len, zipf_exponent)
        self.prompt_len = prompt_len

    def __iter__(self):
        for example in super().__iter__():
            yield {**example, "prompt_len": self.prompt_len}
'''


@pytest.mark.parametrize("workload", [w["name"] for w in spec.benchmark_json()["workloads"]])
def test_a_rehearsed_run_prints_the_contracts_object(workload, capsys, tmp_path):
    result, lines, _ = rehearse(capsys, "--workload", workload, "--seed", str(2**31 + 7),
                             "--out", str(tmp_path))
    assert set(result) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert result["correct"] is True and result["failed"] == 0 and result["attempted"] > 0
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == 1
    # no device metric from a CPU: no mfu, no busy time
    assert set(result["metrics"]) == {"tokens_per_s_per_chip", "step_ms_p90", "setup_s"}
    assert "busy_s" not in result["device"]
    for value in result["metrics"].values():
        assert value["value"] > 0 and value["unit"]
    # every number compared is printed beside its limit
    checks = [line for line in lines if line.startswith("check ")]
    assert {line.split()[1].rstrip(":") for line in checks} >= {
        "loss_step_1_gap", "loss_step_2_gap", "first_gradient_norm_gap",
        "parameter_change_norm_gap_after_2", "compiles_in_window"}
    assert all("limit" in line for line in checks)


def test_without_a_tpu_and_without_rehearse_the_command_fails(capsys, tmp_path):
    with pytest.raises(SystemExit) as stop:
        run_py.main(["--workload", "mistral7b_pretrain_4k", "--out", str(tmp_path)])
    assert stop.value.code not in (0, None)
    assert not capsys.readouterr().out.strip().startswith("{")


def test_an_unknown_workload_is_refused():
    with pytest.raises(SystemExit):
        run_py.main(["--workload", "no_such_cell", "--rehearse"])


def test_a_cell_a_configuration_and_a_metric_added_as_files_alone(capsys, tmp_path, monkeypatch):
    """A later PR adds files and entries and edits nothing: a new configuration, a new
    traffic mix of a new kind (its generator masks a prompt out of the loss), a new cell
    with recipe sections and a mesh of its own, and a new per-layer reader are found by
    their names."""
    bench_dir = tmp_path / "benchmarks"
    for sub in ("configs", "workloads", "traffic"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, sub), bench_dir / sub)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*.json")}
    config = json.loads((bench_dir / "configs" / "mistral-7b-v0.3-d4.json").read_text())
    config["tiny"]["num_hidden_layers"] = 1
    (bench_dir / "configs" / "mistral-7b-v0.3-d1.json").write_text(json.dumps(config))
    (bench_dir / "traffic" / "pretrain_short.json").write_text(json.dumps(
        {"kind": "prompted_stream", "seq_len": 512, "zipf_exponent": 1.3, "prompt_len": 128,
         "why": "test", "tiny": {"seq_len": 64, "prompt_len": 16}}))
    (bench_dir / "generators").mkdir()
    (bench_dir / "generators" / "prompted_stream.py").write_text(PROMPTED_STREAM)
    cell = json.loads((bench_dir / "workloads" / "mistral7b_pretrain_4k.json").read_text())
    cell.update(config="mistral-7b-v0.3-d1", traffic="pretrain_short", micro_batch_size=2,
                recipe={"optimizer": {"lr": 2e-4}}, distributed={"dp_shard": 1, "tp": 1})
    # a new cell reads its own limits: at twice the lr the second loss read 0.009 off
    cell["tiny"]["limits"]["loss_later"] = 0.03
    (bench_dir / "workloads" / "mistral7b_d1_short.json").write_text(json.dumps(cell))
    (bench_dir / "metrics").mkdir()
    (bench_dir / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return run['steps']\n")
    (bench_dir / "metrics" / "never_there.py").write_text("def read(run):\n    return None\n")
    bench = spec.benchmark_json()
    bench["configs"].append({"name": "mistral-7b-v0.3-d1", "source": config["source"],
                             "file": "benchmarks/configs/mistral-7b-v0.3-d1.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "mistral7b_d1_short", "config": "mistral-7b-v0.3-d1",
                               "traffic": "pretrain_short", "chips": 1, "why": "test"})
    for name in ("steps_in_window", "never_there"):
        bench["per_layer"].append({"name": name, "unit": "count", "better": "higher",
                                   "source": "program_counter", "layer": "step",
                                   "moves": "tokens_per_s_per_chip",
                                   "workloads": ["mistral7b_d1_short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    for sub in ("metrics", "generators"):
        package = importlib.import_module("benchmarks." + sub)
        monkeypatch.setattr(package, "__path__", [*package.__path__, str(bench_dir / sub)])

    result, _, failed = rehearse(capsys, "--workload", "mistral7b_d1_short", "--seed", "11",
                                     "--trace", "1", "--out", "out")
    assert result["correct"] is True, failed
    assert result["metrics"]["steps_in_window"]["value"] == result["attempted"]
    # a reader that finds nothing is left out; device readers find no trace on a CPU
    assert set(result["metrics"]) == {"data_wait_ms", "compiles_in_window", "step_hbm_gib",
                                      "steps_in_window"}
    assert {p: p.read_bytes() for p in before} == before
    assert list((tmp_path / "out" / "mistral7b_d1_short").glob("*/training.jsonl"))
    # the recipe the run entered: the new kind's dataset, the cell's own sections and mesh
    (written,) = (tmp_path / "out" / "mistral7b_d1_short").glob("*/recipe.yaml")
    recipe = yaml.safe_load(written.read_text())
    assert recipe["dataset"] == {"_target_": "benchmarks.generators.prompted_stream.Dataset",
                                 "vocab_size": config["tiny"]["vocab_size"], "seed": 11,
                                 "seq_len": 64, "zipf_exponent": 1.3, "prompt_len": 16}
    assert recipe["optimizer"]["lr"] == 2e-4 and recipe["distributed"] == {"dp_shard": 1, "tp": 1}
    # the older cells do not report the new cell's metric
    assert "steps_in_window" not in [m["name"] for m in spec.Cell("mistral7b_pretrain_4k").per_layer]
