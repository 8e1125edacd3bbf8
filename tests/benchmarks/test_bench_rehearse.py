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

from benchmarks.harness import spec, weights
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

# a family with two stacks, as the files a later model_config PR would add. The reference:
# layers named in `mlp_only_layers` get a dense SwiGLU MLP, the others the experts and a
# zero buffer (the place of a router's selection bias, whose gradient is identically zero);
# two layer groups in the order of the program's stacks; counts and kernel costs of its own.
# `decoder.layer_block` picks a layer's MLP by the leaves the layer holds, so the sweep is
# decoder's. `WINDOW` makes this module's score count differ from decoder's: the test reads
# which of the two a roofline reader's cost was built from, nothing in a run compares it.
TWO_STACK_REFERENCE = '''
from benchmarks.harness import gemm_costs
from benchmarks.reference import decoder
from benchmarks.reference.decoder import loss_and_grads  # noqa: F401

WINDOW = 16
_EXPERT_LEAVES = ("router", "experts_gate_up", "experts_down")


def _split(m):
    dense = sorted(m["mlp_only_layers"])
    return dense, [i for i in range(m["num_hidden_layers"]) if i not in dense]


def block_shapes(m):
    shapes, d = decoder.block_shapes(m), decoder.dims(m)
    dense, sparse = _split(m)
    for i in dense:
        layer = {k: v for k, v in shapes[f"layer_{i}"].items() if k not in _EXPERT_LEAVES}
        shapes[f"layer_{i}"] = layer | {"w_gate": ((d["D"], d["F"]), "normal"),
                                        "w_up": ((d["D"], d["F"]), "normal"),
                                        "w_down": ((d["F"], d["D"]), "normal")}
    for i in sparse:
        shapes[f"layer_{i}"]["expert_bias"] = ((d["E"],), "zeros")
    return shapes


def layer_groups(m):
    dense, sparse = _split(m)
    return {"dense_layers": dense, "moe_layers": sparse}


def matrix_params_per_token(m):
    d, (dense, sparse) = decoder.dims(m), _split(m)
    parts = decoder.matrix_params_per_token(m)
    parts["mlp"] = len(sparse) * d["K"] * 3 * d["D"] * d["I"] + len(dense) * 3 * d["D"] * d["F"]
    parts["router"] = len(sparse) * d["E"] * d["D"]
    return parts


def score_flops_per_token(m, seq_len):
    d = decoder.dims(m)
    keys = sum(min(t + 1, WINDOW) for t in range(seq_len)) / seq_len
    return d["L"] * 12.0 * d["n"] * d["h"] * keys


def parameter_count(m):
    d, (dense, sparse) = decoder.dims(m), _split(m)
    experts = d["E"] * d["D"] + d["E"] * 3 * d["D"] * d["I"]
    return (decoder.parameter_count(m) + len(dense) * (3 * d["D"] * d["F"] - experts)
            + len(sparse) * d["E"])


def kernel_costs(m, rows, seq_len):
    d, (_, sparse) = decoder.dims(m), _split(m)
    out = decoder.kernel_costs(m, rows, seq_len)
    out["flash_attention"]["flops"] = score_flops_per_token(m, seq_len) * rows * seq_len
    out["expert_gemms"] = gemm_costs.expert_gemms_step(rows * seq_len * d["K"], d["D"], d["I"],
                                                       d["E"], len(sparse))
    return out
'''

# its adapter: the sparse stack as `moe_gqa` maps it, the dense stack name for name. The
# program holds no buffer like `expert_bias`: it is dropped on the way in and comes back as
# zeros (no gradient reached it, nothing of it changed), which is what the reference reads.
TWO_STACK_ADAPTER = '''
import jax.numpy as jnp

from benchmarks.adapters import moe_gqa

_BUFFER = "moe_layers.expert_bias"


def from_reference(flat):
    sparse = {k.replace("moe_layers.", "layers.", 1): v for k, v in flat.items()
              if not k.startswith("dense_layers.") and k != _BUFFER}
    tree = moe_gqa.from_reference(sparse)
    tree["dense_layers"] = {k.split(".", 1)[1]: v for k, v in flat.items()
                            if k.startswith("dense_layers.")}
    return tree


def to_reference(tree):
    sparse = moe_gqa.to_reference({k: v for k, v in tree.items() if k != "dense_layers"})
    flat = {k.replace("layers.", "moe_layers.", 1) if k.startswith("layers.") else k: v
            for k, v in sparse.items()}
    flat.update({"dense_layers." + k: v for k, v in tree["dense_layers"].items()})
    flat[_BUFFER] = jnp.zeros(flat["moe_layers.router"].shape[:2], jnp.float32)
    return flat
'''


def _copy_of_the_benchmark(tmp_path, monkeypatch, new_code=()):
    """A temporary copy of the benchmark's data files for a test to add files to, with
    ``spec`` pointed at it and an empty directory for each package in ``new_code`` put on
    that package's path. Returns the copy's directory, the bytes of the files that were
    there, and ``BENCHMARK.json`` as a dict for the test to add entries to and write."""
    bench = spec.benchmark_json()
    bench_dir = tmp_path / "benchmarks"
    for sub in ("configs", "workloads", "traffic"):
        shutil.copytree(os.path.join(spec.BENCH_DIR, sub), bench_dir / sub)
    before = {p: p.read_bytes() for p in bench_dir.rglob("*.json")}
    for sub in new_code:
        (bench_dir / sub).mkdir()
        package = importlib.import_module("benchmarks." + sub)
        monkeypatch.setattr(package, "__path__", [*package.__path__, str(bench_dir / sub)])
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))
    monkeypatch.setattr(spec, "BENCH_DIR", str(bench_dir))
    return bench_dir, before, bench


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
    # ... and again in the result line, under a key that comes last, and as the last
    # lines of standard error (all the driver keeps of a run that is not correct)
    assert list(result)[-1] == "checks"
    names = [line.split()[1].rstrip(":") for line in checks]
    assert list(result["checks"]) == names
    assert all(set(c) == {"value", "limit", "ok"} and c["ok"] for c in result["checks"].values())
    last = rehearse.stderr.strip().splitlines()[-len(names):]
    assert [line.split()[0] for line in last] == names
    assert all(" limit " in line and line.endswith(" ok") for line in last)


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
    bench_dir, before, bench = _copy_of_the_benchmark(tmp_path, monkeypatch,
                                                      new_code=("metrics", "generators"))
    config = json.loads((bench_dir / "configs" / "mistral-7b-v0.3-d4.json").read_text())
    config["tiny"]["num_hidden_layers"] = 1
    (bench_dir / "configs" / "mistral-7b-v0.3-d1.json").write_text(json.dumps(config))
    (bench_dir / "traffic" / "pretrain_short.json").write_text(json.dumps(
        {"kind": "prompted_stream", "seq_len": 512, "zipf_exponent": 1.3, "prompt_len": 128,
         "why": "test", "tiny": {"seq_len": 64, "prompt_len": 16}}))
    (bench_dir / "generators" / "prompted_stream.py").write_text(PROMPTED_STREAM)
    cell = json.loads((bench_dir / "workloads" / "mistral7b_pretrain_4k.json").read_text())
    cell.update(config="mistral-7b-v0.3-d1", traffic="pretrain_short", micro_batch_size=2,
                recipe={"optimizer": {"lr": 2e-4}}, distributed={"dp_shard": 1, "tp": 1})
    # a new cell reads its own limits: at twice the lr the second loss read 0.009 off
    cell["tiny"]["limits"]["loss_later"] = 0.03
    (bench_dir / "workloads" / "mistral7b_d1_short.json").write_text(json.dumps(cell))
    (bench_dir / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return run['steps']\n")
    (bench_dir / "metrics" / "never_there.py").write_text("def read(run):\n    return None\n")
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


def test_a_family_with_two_stacks_added_as_files_alone(capsys, tmp_path, monkeypatch):
    """What a `model_config` PR brings for an architecture the plain decoder cannot
    express: `reference/<name>.py`, `adapters/<family>.py`, a configuration that names
    both, a cell. No file that is there is edited; the harness finds the model, its layer
    groups, its FLOP count and its kernel costs through the configuration's `reference`."""
    from benchmarks.harness import check, flops, kernel_costs
    from benchmarks.harness.peaks import peaks
    from benchmarks.metrics import flash_attention_roofline
    from benchmarks.reference import decoder

    bench_dir, before, bench = _copy_of_the_benchmark(tmp_path, monkeypatch,
                                                      new_code=("reference", "adapters"))
    config = json.loads((bench_dir / "configs" / "qwen3-30b-a3b-d2.json").read_text())
    config.update(reference="two_stack", family="two_stack_moe", mlp_only_layers=[0])
    config["tiny"]["num_hidden_layers"] = 3
    (bench_dir / "configs" / "qwen3-30b-a3b-dense-first.json").write_text(json.dumps(config))
    cell = json.loads((bench_dir / "workloads" / "qwen3moe_pretrain_4k.json").read_text())
    cell["config"] = "qwen3-30b-a3b-dense-first"
    for limits in (cell["limits"], cell["tiny"]["limits"]):
        limits["param_change_left_out"] = ["moe_layers.router"]
    (bench_dir / "workloads" / "qwen3moe_dense_first_4k.json").write_text(json.dumps(cell))
    (bench_dir / "reference" / "two_stack.py").write_text(TWO_STACK_REFERENCE)
    (bench_dir / "adapters" / "two_stack_moe.py").write_text(TWO_STACK_ADAPTER)
    bench["configs"].append({"name": "qwen3-30b-a3b-dense-first", "source": config["source"],
                             "file": "benchmarks/configs/qwen3-30b-a3b-dense-first.json",
                             "reduced": ["num_hidden_layers", "mlp_only_layers"], "why": "test"})
    bench["workloads"].append({"name": "qwen3moe_dense_first_4k", "chips": 1, "why": "test",
                               "config": "qwen3-30b-a3b-dense-first", "traffic": "pretrain_4k"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    compared = []  # every (program's, reference's) pair of per-leaf sums that was judged
    real_norm_gap = check.norm_gap
    monkeypatch.setattr(check, "norm_gap", lambda prog, ref, only=None: (
        compared.append((prog, ref)), real_norm_gap(prog, ref, only))[1])

    result, lines, failed = rehearse(capsys, "--workload", "qwen3moe_dense_first_4k",
                                     "--seed", str(2**31 + 27), "--out", "out")
    assert result["correct"] is True, (failed, lines)
    assert {p: p.read_bytes() for p in before} == before
    # both stacks were compared, each leaf at its layer's place within its group
    worst = " ".join(line for line in lines if "worst:" in line or "not compared" in line)
    assert "moe_layers." in worst and "dense_layers." in worst, worst
    for prog, ref in compared:
        assert prog.keys() == ref.keys()
        assert ref["dense_layers.w_gate"].shape == (1,) and ref["moe_layers.router"].shape == (2,)
        assert "dense_layers.router" not in ref and "moe_layers.w_gate" not in ref
    # the zero buffer went through both optimizer rules' place and `norm_gap` like any leaf
    gaps = [real_norm_gap(prog, ref, lambda n: n == "moe_layers.expert_bias")
            for prog, ref in compared]
    assert len(gaps) >= 4 and all(gap == 0.0 for gap, _ in gaps), gaps
    assert all("moe_layers.expert_bias[1]" in where for _, where in gaps)
    # the counts are the fixture's own, found through the cell
    made = spec.Cell("qwen3moe_dense_first_4k", tiny=True)
    two_stack = importlib.import_module("benchmarks.reference.two_stack")
    assert made.reference is two_stack and "reference" not in made.model
    m, rows, seq = made.model, made.micro_batch * made.grad_acc, made.seq_len
    own = two_stack.score_flops_per_token(m, seq)
    assert own < 0.5 * decoder.score_flops_per_token(m, seq)
    assert flops.flops_per_token(made.reference, m, seq) == \
        6.0 * sum(two_stack.matrix_params_per_token(m).values()) + own
    assert made.kernel_cost("flash_attention")["flops"] == own * rows * seq
    assert made.kernel_cost("expert_gemms")["flops"] == \
        decoder.kernel_costs(m, rows, seq)["expert_gemms"]["flops"] * 2 / 3
    capsys.readouterr()
    share = flash_attention_roofline.read({"cell": made, "trace": {"flash_s": 4.0, "steps": 4},
                                           "device_kind": "TPU v5 lite"})
    least, _ = kernel_costs.roofline_seconds(two_stack.kernel_costs(m, rows, seq)["flash_attention"],
                                             peaks("TPU v5 lite"))
    assert share == pytest.approx(100.0 * least) and f"least {1e3 * least:.3f} ms" in capsys.readouterr().out
    # the parameters made are the parameters counted
    import jax

    shapes = jax.eval_shape(weights.maker(two_stack, m, "bfloat16"), weights.seed_key(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == two_stack.parameter_count(m)
