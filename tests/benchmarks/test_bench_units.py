"""The benchmark's yardstick, checked without a chip: the contract's names, the FLOP and
kernel-cost arithmetic against hand-worked numbers, the peak table, the traffic
generator, and the trace reducer on a trace recorded on a TPU v5e (PR 24)."""

import gzip
import hashlib
import importlib
import json
import os
import re

import numpy as np
import pytest

from benchmarks.generators import token_stream
from benchmarks.harness import check, flops, kernel_costs, peaks, spec, trace, weights

HERE = os.path.dirname(os.path.abspath(__file__))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = spec.benchmark_json()


def _cell(config_name, tiny=False):
    """A cell of the configuration: its file (``config``), the model's keys of it
    (``model``) and the reference that answers for it (``reference``)."""
    workload = next(w["name"] for w in BENCH["workloads"] if w["config"] == config_name)
    return spec.Cell(workload, tiny=tiny)


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
    cell = _cell(config)
    model, cfg, reference = cell.model, cell.config, cell.reference
    assert reference.parameter_count(model) == pytest.approx(params, rel=5e-3)
    assert flops.flops_per_token(reference, model, seq) / 1e9 == pytest.approx(gflop, rel=5e-3)
    # every width is the published one: only the keys in `reduced` differ
    for key, value in cfg["published"].items():
        assert key in cfg["reduced"] and model[key] != value


def test_mistral_flops_part_by_part():
    cell = _cell("mistral-7b-v0.3-d4")
    model, reference = cell.model, cell.reference
    parts = reference.matrix_params_per_token(model)
    # a layer: q and o 4096 x 4096 each, k and v 4096 x 1024 each, three 4096 x 14336
    assert parts["attention_projections"] == 4 * (2 * 4096 * 4096 + 2 * 4096 * 1024)
    assert parts["mlp"] == 4 * 3 * 4096 * 14336
    assert parts["head"] == 4096 * 32768 and parts["router"] == 0
    assert reference.score_flops_per_token(model, 4096) == 4 * 12 * 32 * 128 * 4097 / 2


def test_qwen3_counts_the_routed_experts_not_all():
    cell = _cell("qwen3-30b-a3b-d2")
    parts = cell.reference.matrix_params_per_token(cell.model)
    assert parts["mlp"] == 2 * 8 * 3 * 2048 * 768
    assert parts["router"] == 2 * 128 * 2048
    assert parts["head"] == 2048 * 151936


# Read on the parent of PR 27 (commit 8fafd63, before the harness found a configuration's
# reference by name), so that the move changed no number either cell prints: the seeded
# weights (a digest of every leaf, CPU, bfloat16, the tiny sizes), the stacked tree's names,
# and at the published sizes the FLOP count's parts, the parameter count and the three
# kernels' operations and bytes at the cells' shapes.
SEEDED_WEIGHTS = {
    ("mistral-7b-v0.3-d4", 7): "f81199d24d2ffe68",
    ("mistral-7b-v0.3-d4", 2**31 + 12345): "8c4b4a8525307ff1",
    ("qwen3-30b-a3b-d2", 7): "cfa1f8197993843b",
    ("qwen3-30b-a3b-d2", 2**31 + 12345): "94aae884c1fcbe37",
}
STACKED_TREE = {"mistral-7b-v0.3-d4": "6446bc4292779def", "qwen3-30b-a3b-d2": "a44c314c78e4f3ab"}
PUBLISHED_SIZES = {
    "mistral-7b-v0.3-d4": dict(
        flops_per_token=6442549248.0, score_flops_per_token=402751488.0,
        parameter_count=1140887552,
        matrix_params_per_token={"attention_projections": 167772160, "mlp": 704643072,
                                 "router": 0, "head": 134217728},
        kernel_costs={"flash_attention": {"flops": 1649670094848.0, "bytes": 671088640.0},
                      "linear_ce": {"flops": 3298534883328.0, "bytes": 1207959552.0}}),
    "qwen3-30b-a3b-d2": dict(
        flops_per_token=2750988288.0, score_flops_per_token=201375744.0,
        parameter_count=1868573184,
        matrix_params_per_token={"attention_projections": 37748736, "mlp": 75497472,
                                 "router": 524288, "head": 311164928},
        kernel_costs={"flash_attention": {"flops": 1649670094848.0, "bytes": 603979776.0},
                      "linear_ce": {"flops": 15294378541056.0, "bytes": 2623537152.0},
                      "expert_gemms": {"flops": 3710851743744.0, "bytes": 12280922112.0}}),
}


@pytest.mark.parametrize("config,seed", list(SEEDED_WEIGHTS), ids=lambda v: str(v))
def test_seeded_weights_are_the_parents_leaf_for_leaf(config, seed):
    import jax.numpy as jnp

    cell = _cell(config, tiny=True)
    blocks = weights.make_blocks(cell.reference, cell.model, seed, "bfloat16")
    digest = hashlib.sha256()
    for block, leaves in blocks.items():
        for leaf, x in leaves.items():
            digest.update(f"{block}.{leaf}:{x.dtype}:{tuple(x.shape)}".encode())
            digest.update(np.asarray(x.astype(jnp.float32)).tobytes())
    assert digest.hexdigest()[:16] == SEEDED_WEIGHTS[config, seed]
    flat = weights.stack_layers(blocks, cell.layer_groups)
    names = json.dumps([[k, str(v.dtype), list(v.shape)] for k, v in flat.items()])
    assert hashlib.sha256(names.encode()).hexdigest()[:16] == STACKED_TREE[config]
    # one stack, under the name every limit and `param_change_left_out` entry was read with
    layers = cell.model["num_hidden_layers"]
    assert cell.layer_groups == {"layers": list(range(layers))}
    assert all(v.shape[0] == layers for k, v in flat.items() if k.startswith("layers."))


@pytest.mark.parametrize("config", list(PUBLISHED_SIZES))
def test_flops_parameters_and_kernel_costs_are_the_parents_to_the_digit(config):
    cell, want = _cell(config), PUBLISHED_SIZES[config]
    ref, m = cell.reference, cell.model
    assert flops.flops_per_token(ref, m, cell.seq_len) == want["flops_per_token"]
    assert ref.score_flops_per_token(m, cell.seq_len) == want["score_flops_per_token"]
    assert ref.matrix_params_per_token(m) == want["matrix_params_per_token"]
    assert ref.parameter_count(m) == want["parameter_count"]
    rows = cell.micro_batch * cell.grad_acc
    assert ref.kernel_costs(m, rows, cell.seq_len) == want["kernel_costs"]
    assert {k: cell.kernel_cost(k) for k in want["kernel_costs"]} == want["kernel_costs"]


def test_a_configuration_names_its_reference_and_a_zero_buffer_is_made():
    """``reference`` is a note key: it picks ``benchmarks/reference/<name>.py`` and never
    reaches ``model.config``; every other key does. A ``zeros`` leaf takes a place in the
    key order like any other, so the leaves after it keep their draws."""
    from types import SimpleNamespace

    from benchmarks.reference import decoder

    assert "reference" in spec.NOTE_KEYS and _cell("mistral-7b-v0.3-d4").reference is decoder
    cell = _cell("qwen3-30b-a3b-d2", tiny=True)
    assert "reference" not in cell.model and cell.model["mlp_only_layers"] == []

    def with_buffer(m):
        shapes = decoder.block_shapes(m)
        for block in shapes:
            if block.startswith("layer_"):
                shapes[block] = {"expert_bias": ((m["num_experts"],), "zeros"), **shapes[block]}
        return shapes

    buffered = SimpleNamespace(block_shapes=with_buffer)
    made = weights.maker(buffered, cell.model, "bfloat16")(weights.seed_key(7))
    assert not np.asarray(made["layer_0"]["expert_bias"], np.float32).any()
    assert made["layer_0"]["expert_bias"].dtype == made["layer_0"]["wq"].dtype
    plain = weights.maker(decoder, cell.model, "bfloat16")(weights.seed_key(7))
    assert (np.asarray(made["embed"]["embed"], np.float32)
            == np.asarray(plain["embed"]["embed"], np.float32)).all()
    with pytest.raises(ValueError, match="init"):
        weights.maker(SimpleNamespace(block_shapes=lambda m: {"embed": {"embed": ((2, 2), "uniform")}}),
                      cell.model, "bfloat16")(weights.seed_key(7))


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


# losses as the MoE cell's window read them on the chip (PR 27, seed 2123659143): settled at
# 7.3 from the window's 20th step on, and spiking over its last ten
_CALM = [10.5, 9.9, 11.7, 11.2, 10.1, 8.9, 10.1, 9.7, 9.0, 8.5, 7.7, 11.3, 9.6, 8.8, 8.0,
         7.7, 16.9, 7.9, 7.7, 7.6] + [7.3] * 92
_SPIKE = [14.8, 8.3, 11.4, 8.5, 8.9, 8.6, 10.3, 22.2, 9.0, 13.1]


@pytest.mark.parametrize("name,window,fell", [
    ("calm", _CALM + [7.3] * 10, True),
    ("spike_over_the_last_ten", _CALM + _SPIKE, True),
    ("spike_in_the_middle", _CALM[:60] + _SPIKE + _CALM[60:], True),
    ("dead_optimizer", [12.29 + 0.05 * (-1) ** i for i in range(122)], False),
    ("back_at_the_first_loss_for_most_of_the_window", _CALM[:40] + [12.5] * 82, False),
])
def test_the_loss_fall_is_read_at_the_windows_median(name, window, fell, capsys):
    """A spike of ten steps, wherever it falls, does not hide that the loss fell; a loss
    that never fell, or went back to where it began for most of the window, still fails."""
    verdict = check.Verdict()
    verdict.at_least("loss_fall_first_step_to_window_median",
                     12.286 - check.settled_loss(window), 2.0)
    assert verdict.correct is fell
    assert verdict.as_dict()["loss_fall_first_step_to_window_median"]["limit"] == 2.0
    assert ("FAILED" in verdict.lines()) is not fell
