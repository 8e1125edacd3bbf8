"""``benchmarks/harness/spans.py`` and the readers on it, on records cut from this PR's
traced runs on a TPU v5e (my chip runs, PR 25: both cells, 42 s windows, seeds 2500000011
and 2500000012, five traced steps; ``python3 -m benchmarks.harness.spans <run dir> <out>``)."""

import gzip
import importlib
import json
import os
from types import SimpleNamespace

import pytest

from benchmarks.harness import gemm_costs, kernel_costs, spans, spec, trace
from benchmarks.harness.peaks import peaks

HERE = os.path.dirname(os.path.abspath(__file__))
CELLS = {"dense": ("mistral7b_pretrain_4k", "v5e_mistral7b_spans.json.gz"),
         "moe": ("qwen3moe_pretrain_4k", "v5e_qwen3moe_spans.json.gz")}


@pytest.fixture(scope="module")
def records():
    out = {}
    for key, (_, name) in CELLS.items():
        with gzip.open(os.path.join(HERE, "fixtures", name), "rt") as f:
            out[key] = json.load(f)
    return out


@pytest.fixture(scope="module")
def reduced(records):
    return {key: spans.reduce(rec) for key, rec in records.items()}


def _run(key, reduced):
    """What a reader is handed in a traced run on the chip, the reduction already made."""
    return {"cell": spec.Cell(CELLS[key][0]), "trace": {"steps": reduced[key]["steps"]},
            "device_kind": "TPU v5 lite", "_spans": reduced[key],
            "run": SimpleNamespace(trace_dir="unused")}


@pytest.mark.parametrize("key", ["dense", "moe"])
def test_the_window_is_the_old_reducers_and_both_sums_close(records, reduced, key):
    rec, red = records[key], reduced[key]
    dev = rec["devices"][0]
    old = trace.reduce_planes({dev["name"]: {"XLA Ops": dev["ops"], "XLA Modules": dev["modules"]}})
    assert red["steps"] == old["steps"] == 4
    assert red["window_s"] == pytest.approx(old["window_s"], rel=1e-9)
    assert red["busy_s"] == pytest.approx(old["busy_s"], rel=1e-9)
    # operations of a TPU core run one after another: per-layer sums are the busy time
    assert sum(red["layer_s"].values()) == pytest.approx(red["busy_s"], rel=1e-3)
    # every idle interval went to exactly one name
    assert sum(red["idle_s"].values()) == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert "train_step" in red["span_names"] and red["has_scopes"]
    unscoped = red["layer_s"][None] + red["layer_s"]["embed"]
    assert unscoped < 0.05 * red["busy_s"]
    assert red["idle_s"]["unattributed"] < 0.1 * (red["window_s"] - red["busy_s"])


def test_idle_goes_to_the_innermost_span(reduced):
    # the schedule's round trips lie inside `log_row`; their idle is `lr_schedule`'s
    for key, lr_ms, log_ms in (("dense", 4.844, 0.825), ("moe", 4.958, 2.470)):
        idle = {k: 1e3 * v / 4 for k, v in reduced[key]["idle_s"].items()}
        assert idle["lr_schedule"] == pytest.approx(lr_ms, abs=2e-3)
        assert idle["log_row"] == pytest.approx(log_ms, abs=2e-3)
        assert idle["loss_pull"] > idle["data_wait"] > idle["train_step"] > idle["step_end"]
    spans_ = [("log_row", 10.0, 20.0), ("lr_schedule", 12.0, 15.0), ("data_wait", 21.0, 30.0)]
    assert spans._innermost(spans_, 8.0, 25.0) == {
        "unattributed": 2.0 + 1.0, "log_row": 2.0 + 5.0, "lr_schedule": 3.0, "data_wait": 4.0}


def test_layer_of_matches_labels_by_path_component():
    path = "jit(train_step)/while/body/closed_call/transpose(jvp(layer_stack))/while/body/"
    assert spans.layer_of("fusion.1", path + "closed_call/checkpoint/attention/dot_general") == "attention"
    assert spans.layer_of("fusion.1", path + "dynamic_slice") == "layer_stack"
    assert spans.layer_of("fusion.1", path + "closed_call/moe/moe_experts/moe_combine/mul") == "moe"
    assert spans.layer_of("x.1", "jit(train_step)/jvp(lm_head_loss)/linear_ce_fwd/pallas_call") == "lm_head_loss"
    assert spans.layer_of("x.1", "jit(train_step)/embed_lookup/gather") is None  # a longer word
    assert spans.layer_of("ragged-dot-none.5", "ragged-dot-none") == "moe"  # the compiler's
    assert spans.layer_of("copy.3", None) is None
    assert spans.instruction("%flash_attention_fwd.19 custom-call") == "flash_attention_fwd.19"
    assert spans.kernel("%linear_ce_bwd_dw.2 custom-call") == "linear_ce_bwd_dw"
    assert spans.kernel("%custom-call custom-call") == "custom-call"


# what each reader read in the traced runs the records were cut from (the result lines)
READINGS = [
    ("attention_device_ms", "dense", 59.1464535), ("attention_device_ms", "moe", 46.605408),
    ("mlp_device_ms", "dense", 107.709269), ("moe_device_ms", "moe", 88.487965),
    ("loss_head_device_ms", "dense", 19.854441), ("loss_head_device_ms", "moe", 180.7065125),
    ("optimizer_device_ms", "dense", 26.375835), ("optimizer_device_ms", "moe", 21.1351885),
    ("layer_stack_device_ms", "dense", 11.0964555), ("layer_stack_device_ms", "moe", 39.9146375),
    ("unscoped_device_ms", "dense", 8.87692875), ("unscoped_device_ms", "moe", 10.5632305),
    ("linear_ce_roofline", "moe", 45.752139783193385), ("expert_gemm_roofline", "moe", 42.01432995211287),
    ("idle_log_ms", "dense", 8.34204575), ("idle_log_ms", "moe", 11.39531775),
    ("idle_data_wait_ms", "dense", 2.35168525), ("idle_dispatch_ms", "moe", 0.80781825),
    ("idle_hooks_ms", "dense", 0.03326975), ("idle_unattributed_ms", "moe", 0.32603025),
]


@pytest.mark.parametrize("name,key,value", READINGS,
                         ids=[f"{name}-{key}" for name, key, _ in READINGS])
def test_each_reader_on_the_recorded_runs(reduced, name, key, value, capsys):
    reader = importlib.import_module("benchmarks.metrics." + name)
    got = reader.read(_run(key, reduced))
    assert got == pytest.approx(value, rel=1e-6)
    if name.endswith("_roofline"):
        assert got < 100 and "bound by compute" in capsys.readouterr().out
    entry = next(m for m in spec.benchmark_json()["per_layer"] if m["name"] == name)
    assert CELLS[key][0] in entry.get("workloads", [CELLS[key][0]])
    # a rehearsal has no trace, and a program from before PR 25 no spans: left out, no error
    assert reader.read({"trace": None}) is None
    assert reader.read({"trace": {"steps": 4}, "_spans": None}) is None


def test_the_metrics_sums_close_on_the_recorded_runs(reduced):
    for key, layers in (("dense", ("attention", "mlp", "loss_head", "optimizer", "layer_stack", "unscoped")),
                        ("moe", ("attention", "moe", "loss_head", "optimizer", "layer_stack", "unscoped"))):
        run = _run(key, reduced)
        read = lambda name: importlib.import_module("benchmarks.metrics." + name).read(run)  # noqa: E731
        red = reduced[key]
        device_step_ms = 1e3 * red["busy_s"] / red["steps"]
        assert sum(read(f"{k}_device_ms") for k in layers) == pytest.approx(device_step_ms, rel=1e-2)
        idle = sum(read(f"idle_{k}_ms") for k in ("log", "data_wait", "dispatch", "hooks", "unattributed"))
        assert idle == pytest.approx(1e3 * (red["window_s"] - red["busy_s"]) / red["steps"], rel=1e-2)


def test_a_scope_span_or_kernel_that_moved_is_an_error_and_an_older_program_is_left_out(records, reduced):
    moe = _run("moe", reduced)
    with pytest.raises(RuntimeError, match="scope"):
        spans.layer_ms(moe, "mlp")  # the MoE cell has no dense MLP
    with pytest.raises(RuntimeError, match="custom call"):
        spans.kernels_ms(_run("dense", reduced), ("linear_ce_",))  # nor the dense cell a fused CE
    with pytest.raises(RuntimeError, match="span"):
        spans.idle_ms(moe, "eval", required=("eval",))
    # the parent of PR 25: no program span in the trace, no step_scopes.json beside it
    older = spans.reduce({**records["dense"], "spans": [], "op_names": None})
    assert not older["span_names"] and not older["has_scopes"]
    assert older["idle_s"] == {"unattributed": pytest.approx(older["window_s"] - older["busy_s"])}
    assert set(older["layer_s"]) == {None}


def test_a_scope_is_read_by_its_own_label(reduced):
    """``label_s`` keeps what ``layer_s`` folds away: the MoE block's own scopes can be read
    one by one. ``moe_dispatch`` and ``moe_combine`` are called inside ``moe_experts`` (an
    operation counts under every label of its path), and the ``ragged-dot`` calls carry no
    ``op_name`` at all, so the four labels together stay under the layer kind's sum."""
    moe = _run("moe", reduced)
    gate, dispatch, experts, combine = (spans.scope_ms(moe, label) for label in
                                        ("moe_gate", "moe_dispatch", "moe_experts", "moe_combine"))
    assert (gate, dispatch, experts, combine) == pytest.approx((4.643, 11.393, 39.010, 20.089), abs=2e-3)
    assert spans.scope_ms(moe, "moe_gate", "moe_dispatch", "moe_experts", "moe_combine") \
        == pytest.approx(gate + dispatch + experts + combine)
    assert gate + dispatch + experts + combine <= spans.layer_ms(moe, "moe")
    assert dispatch + combine < experts  # nested: counted under `moe_experts` too
    # what `moe` labels is the gate and the experts; the rest of the layer kind is ragged-dot
    assert spans.scope_ms(moe, "moe") == pytest.approx(gate + experts, abs=2e-3)
    assert spans.scope_ms(moe, "moe") + spans.kernels_ms(moe, ("ragged-dot",)) \
        == pytest.approx(spans.layer_ms(moe, "moe"), rel=1e-6)
    # a label that is a layer kind of its own reads what `layer_ms` reads
    for key, label in (("dense", "mlp"), ("dense", "optimizer"), ("moe", "lm_head_loss")):
        run = _run(key, reduced)
        assert spans.scope_ms(run, label) == pytest.approx(spans.layer_ms(run, label), rel=1e-9)
    with pytest.raises(RuntimeError, match="label"):
        spans.scope_ms(moe, "moe_shared_experts")  # this model has none
    with pytest.raises(RuntimeError, match="label"):
        spans.scope_ms(_run("dense", reduced), "mlp", "moe_gate")
    assert spans.scope_ms({"trace": None}, "moe_gate") is None
    # `layer_s` is computed as before: the labels are kept beside it
    assert "label_s" in reduced["moe"] and set(reduced["moe"]["layer_s"]) == {
        None, "embed", "layer_stack", "attention", "moe", "lm_head_loss", "optimizer"}


def test_gemm_costs_count_what_the_docstrings_say():
    ce = gemm_costs.linear_ce_step(8192, 2048, 151936)
    assert ce["flops"] == 3 * 2 * 8192 * 2048 * 151936
    least, bound = kernel_costs.roofline_seconds(ce, peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(0.077636, rel=1e-3)
    ex = gemm_costs.expert_gemms_step(8192 * 8, 2048, 768, 128, 2)
    assert ex["flops"] == 2 * 3 * (2 * 65536 * 2048 * 1536 + 2 * 65536 * 768 * 2048)
    least, bound = kernel_costs.roofline_seconds(ex, peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(0.018837, rel=1e-3)
