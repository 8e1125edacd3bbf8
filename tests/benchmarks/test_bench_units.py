"""The benchmark's yardstick, checked without a chip: the contract's names, the FLOP and
kernel-cost arithmetic against hand-worked numbers, the peak table, the traffic
generator, and the trace reducer on a trace recorded on a TPU v5e (PR 24)."""

import gzip
import importlib
import json
import os
import re

import numpy as np
import pytest

from benchmarks.generators import token_stream
from benchmarks.harness import flops, kernel_costs, peaks, spec, trace

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.benchmark_json()


def _model(config_name):
    entry = next(c for c in BENCH["configs"] if c["name"] == config_name)
    with open(os.path.join(spec.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    return {k: v for k, v in cfg.items() if k not in spec.NOTE_KEYS}, cfg


def test_benchmark_json_has_exactly_the_contracts_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in BENCH["end_to_end"])


@pytest.mark.parametrize("section", ["configs", "workloads", "end_to_end", "per_layer"])
def test_every_name_and_unit_is_within_the_allowed_characters(section):
    names = [e["name"] for e in BENCH[section]]
    assert len(names) == len(set(names))
    for entry in BENCH[section]:
        assert NAME.match(entry["name"]), entry["name"]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
            assert entry["better"] in ("lower", "higher")
            assert entry["source"] in ("device_trace", "program_span", "program_counter",
                                       "host_clock")
        for key in ("why", "layer", "source"):
            if key in entry:
                assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
        for key in ("config", "traffic"):
            if key in entry:
                assert NAME.match(entry[key])
        for key in entry.get("reduced", []):
            assert NAME.match(key) and not key.endswith(("_dim", "_rank", "_size"))


def test_every_entry_has_its_own_files_and_every_metric_its_reader():
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}
    for config in BENCH["configs"]:
        assert config["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert os.path.isfile(os.path.join(spec.ROOT, config["file"]))
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for workload in BENCH["workloads"]:
        assert workload["chips"] in (1, 4)
        cell = spec.Cell(workload["name"])
        assert cell.chips == workload["chips"] and cell.seq_len > 0
    moved = {m["name"] for m in BENCH["end_to_end"]}
    for metric in BENCH["per_layer"]:
        assert metric["moves"] in moved
        reader = importlib.import_module("benchmarks.metrics." + metric["name"])
        assert callable(reader.read)


@pytest.mark.parametrize("config,params,gflop,seq", [
    ("mistral-7b-v0.3-d4", 1.141e9, 6.44, 4096),
    ("qwen3-30b-a3b-d2", 1.87e9, 2.75, 4096),
])
def test_flops_and_parameters_against_hand_worked_numbers(config, params, gflop, seq):
    model, cfg = _model(config)
    assert flops.parameter_count(model) == pytest.approx(params, rel=5e-3)
    assert flops.flops_per_token(model, seq) / 1e9 == pytest.approx(gflop, rel=5e-3)
    # every width is the published one: only the keys in `reduced` differ
    for key, value in cfg["published"].items():
        assert key in cfg["reduced"] and model[key] != value


def test_mistral_flops_part_by_part():
    model, _ = _model("mistral-7b-v0.3-d4")
    parts = flops.matrix_params_per_token(model)
    # a layer: q and o 4096 x 4096 each, k and v 4096 x 1024 each, three 4096 x 14336
    assert parts["attention_projections"] == 4 * (2 * 4096 * 4096 + 2 * 4096 * 1024)
    assert parts["mlp"] == 4 * 3 * 4096 * 14336
    assert parts["head"] == 4096 * 32768 and parts["router"] == 0
    assert flops.score_flops_per_token(model, 4096) == 4 * 12 * 32 * 128 * 4097 / 2


def test_qwen3_counts_the_routed_experts_not_all():
    model, _ = _model("qwen3-30b-a3b-d2")
    parts = flops.matrix_params_per_token(model)
    assert parts["mlp"] == 2 * 8 * 3 * 2048 * 768
    assert parts["router"] == 2 * 128 * 2048
    assert parts["head"] == 2048 * 151936


def test_flash_attention_costs_by_hand():
    cost = kernel_costs.flash_attention_step(1, 4096, 32, 8, 128, 4)
    pairs = 32 * 4096 * 4097 / 2
    assert cost["flops"] == 4 * 12 * pairs * 128
    assert cost["bytes"] == 4 * (4 * 4096 * 32 * 128 * 2 + 4 * 4096 * 8 * 128 * 2)
    least, bound = kernel_costs.roofline_seconds(cost, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(cost["flops"] / 197e12)


def test_peaks_one_table_and_an_unknown_kind_is_an_error():
    v5e = peaks.peaks("TPU v5 lite")
    assert (v5e["bf16_flops"], v5e["hbm_bytes_per_s"], v5e["hbm_bytes"]) == (197e12, 819e9, 16e9)
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_traffic_same_seed_same_tokens_and_large_seeds():
    big = 2**31 + 12345
    a = token_stream.sequence(32768, 256, 1.1, big, 3)
    b = token_stream.sequence(32768, 256, 1.1, big, 3)
    c = token_stream.sequence(32768, 256, 1.1, big, 4)
    assert a.shape == (257,) and (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 32768
    params = {"seq_len": 256, "zipf_exponent": 1.1}
    ids, labels = token_stream.batch(params, 32768, big, step=2, rows=2)
    assert ids.shape == labels.shape == (2, 256) and (ids[:, 1:] == labels[:, :-1]).all()
    assert (ids[0] == token_stream.sequence(32768, 256, 1.1, big, 2)[:-1]).all()


def test_traffic_entropy_is_the_laws_and_the_stream_stops_on_its_flag():
    h = token_stream.loss_floor({"seq_len": 256, "zipf_exponent": 1.1}, 32768)
    p = token_stream.zipf_law(32768, 1.1)
    assert h == pytest.approx(float(-(p * np.log(p)).sum())) and 5.5 < h < np.log(32768)
    stream = token_stream.Dataset(512, seed=1, seq_len=16, zipf_exponent=1.1)
    it = iter(stream)
    first = next(it)
    assert first["input_ids"].shape == (17,) and first["prompt_len"] == 0
    stream.stop.set()
    assert next(it, None) is None


def test_short_name_keeps_the_instruction_and_its_operation():
    loop = "%while.94 = (s32[]{:T(128)}, (bf16[1,4]{1,0}, f32[2])) while((s32[]) %tuple.1), body=%b"
    assert trace.short_name(loop) == "%while.94 while"
    call = "%attention.29 = (bf16[32,4096,128]{2,1,0}, bf16[8]) custom-call(bf16[32] %x), custom_call_target=\"tpu_custom_call\""
    assert trace.short_name(call) == "%attention.29 custom-call"
    assert trace.is_flash("%attention.29 custom-call")
    assert trace.short_name("%fusion.3 = bf16[4,4]{1,0} fusion(bf16[4] %p), kind=kLoop") == "%fusion.3 fusion"
    assert not trace.is_flash("%fusion.3 fusion")
    assert trace.short_name("jit_train_step(123)") == "jit_train_step(123)"


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "fixtures", "v5e_mistral7b_trace.json.gz"), "rt") as f:
        return trace.reduce_planes(json.load(f))


def test_trace_reducer_on_the_recorded_v5e_trace(recorded):
    # mistral7b_pretrain_4k, five traced steps, TPU v5e (my chip run, PR 24): the window
    # runs from the first to the last start of jit_train_step, four whole steps
    assert recorded["steps"] == 4
    assert recorded["window_s"] == pytest.approx(0.97650623, rel=1e-6)
    assert recorded["busy_s"] == pytest.approx(0.93225529, rel=1e-6)
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    # two forward calls (one recomputed) and one backward call a layer, four layers
    assert recorded["flash_s"] == pytest.approx(0.09404261, rel=1e-6)


def test_trace_breakdown_names_operations_and_attributes_gaps(recorded):
    ops = recorded["breakdown"]["device_ops"]
    gaps = recorded["breakdown"]["idle_gaps"]
    assert len(ops) == 10 and len(gaps) <= 5
    assert all(not name.endswith(" while") and seconds > 0 for name, seconds in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])
    assert any(trace.is_flash(name) for name, _ in ops)
    # the four gaps between steps: the host evaluates the lr schedule op by op
    assert [name for name, _ in gaps[:4]] == ["$scheduler.py:30 schedule"] * 4
    assert all(0.009 < seconds < 0.013 for _, seconds in gaps[:4])


def test_trace_reducer_refuses_a_trace_without_device_operations():
    with pytest.raises(ValueError):
        trace.reduce_planes({"/host:CPU": {"python3": [["f", 0.0, 1.0]]}})


def test_on_the_chip_a_step_or_a_kernel_that_cannot_be_found_is_an_error_not_a_gap():
    """A rename in the program must stop a chip run, not thin its line: only a rehearsal
    (no analysis, no trace) may leave ``step_hbm_gib`` or the roofline out."""
    from types import SimpleNamespace

    from benchmarks.harness.run_cell import _compiled_step_bytes
    from benchmarks.metrics import flash_attention_roofline

    fell_back_to_jit = SimpleNamespace(_step_executors={1: lambda *a: None})
    renamed = SimpleNamespace()
    for recipe in (fell_back_to_jit, renamed, SimpleNamespace(_step_executors={})):
        with pytest.raises(RuntimeError):
            _compiled_step_bytes(recipe, strict=True)
    assert _compiled_step_bytes(fell_back_to_jit, strict=False) == 0
    assert flash_attention_roofline.read({"trace": None}) is None
    with pytest.raises(RuntimeError):
        flash_attention_roofline.read({"trace": {"flash_s": 0.0, "steps": 4}})
