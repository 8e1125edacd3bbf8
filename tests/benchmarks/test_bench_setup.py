"""The eight set-up metrics (``benchmarks/metrics/setup_*.py``): readers of the program's
``setup_summary`` and ``compile_summary`` rows through ``harness/setup_rows.py``. They move
``setup_s`` and need no device trace."""

import importlib
import json
import re
import types

import pytest

from benchmarks.harness import setup_rows, spec
from tests.benchmarks.rehearsal import rehearse

BENCH = spec.benchmark_json()
NAMES = ("setup_build_s", "setup_step_lower_s", "setup_step_compile_s", "setup_step_analysis_s",
         "setup_first_step_s", "setup_unspanned_s", "setup_compile_requests",
         "setup_cache_misses")
_SPANS = {"setup_mesh": 0.1, "setup_model": 1.5, "setup_data": 0.01, "setup_optimizer": 0.2,
          "setup_checkpoint": 0.01, "setup_loggers": 0.02, "setup_step_fn": 0.001,
          "setup_pipeline": 0.01, "data_wait": 0.02, "compile": 4.0, "step_lower": 1.25,
          "step_compile": 2.0, "step_analysis": 0.5, "first_step": 0.25}


def _run(tmp_path, rows):
    """What a reader is handed, with a ``training.jsonl`` of ``rows`` behind it."""
    with open(tmp_path / "training.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in rows)
    recipe = types.SimpleNamespace(output_dir=str(tmp_path))
    return {"run": types.SimpleNamespace(recipe=recipe), "rehearse": False}


def _rows(spans=None, **summary):
    setup = {"step": 1, "event": "setup_summary", "spans": dict(spans or _SPANS),
             "unspanned_s": 0.125, **summary}
    return [{"run_header": True}, {"step": 1, "event": "compile_costs"}, setup,
            {"step": 1, "loss": 5.0},
            {"step": 9, "event": "compile_summary", "compile_requests": 14,
             "compile_cache_hits": 12, "compile_cache_misses": 2, "missed": ["train_step", "f"]}]


def _read(name, run):
    return importlib.import_module("benchmarks.metrics." + name).read(run)


def test_the_entries_move_setup_s_in_every_cell_and_nothing_else_changed():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-8:] == list(NAMES)  # appended, in order
    for name in NAMES:
        entry = entries[name]
        assert entry["moves"] == "setup_s" and entry["better"] == "lower"
        assert "workloads" not in entry  # every cell reports setup_s, so every cell reads them
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}
        counted = name in ("setup_compile_requests", "setup_cache_misses")
        assert entry["unit"] == ("count" if counted else "s")
        assert entry["source"] == ("program_counter" if counted else "program_span")
    # set-up had no per-layer metric before; no other entry moves it now
    assert {m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s"} == set(NAMES)
    assert {entries[n]["layer"] for n in NAMES} == {"entry and input", "step"}


@pytest.mark.parametrize("name,value", [
    ("setup_build_s", 1.851), ("setup_step_lower_s", 1.25), ("setup_step_compile_s", 2.0),
    ("setup_step_analysis_s", 0.5), ("setup_first_step_s", 0.25), ("setup_unspanned_s", 0.125),
    ("setup_compile_requests", 14), ("setup_cache_misses", 2)])
def test_each_reader_on_written_rows(tmp_path, name, value):
    assert _read(name, _run(tmp_path, _rows())) == pytest.approx(value)


@pytest.mark.parametrize("name", NAMES)
def test_a_program_without_the_row_is_left_out(tmp_path, name):
    """The parent of PR 45 writes a ``compile_summary`` row and no ``setup_summary``: every
    one of the eight is left out of its line, the two that read the older row too."""
    rows = [r for r in _rows() if r.get("event") != "setup_summary"]
    assert _read(name, _run(tmp_path, rows)) is None


@pytest.mark.parametrize("name,renamed", [
    ("setup_build_s", "setup_model"), ("setup_build_s", "setup_pipeline"),
    ("setup_step_lower_s", "step_lower"), ("setup_step_compile_s", "step_compile"),
    ("setup_step_analysis_s", "step_analysis"), ("setup_first_step_s", "first_step")])
def test_a_span_renamed_is_an_error_not_a_thinner_line(tmp_path, name, renamed):
    spans = {("moved_" + k if k == renamed else k): v for k, v in _SPANS.items()}
    with pytest.raises(KeyError, match=renamed):
        _read(name, _run(tmp_path, _rows(spans)))


def test_a_key_that_left_a_row_is_an_error_and_so_are_two_rows(tmp_path):
    rows = _rows()
    del rows[2]["unspanned_s"], rows[4]["compile_requests"]
    run = _run(tmp_path, rows)
    for name in ("setup_unspanned_s", "setup_compile_requests"):
        with pytest.raises(KeyError):
            _read(name, run)
    with pytest.raises(RuntimeError, match="2 'setup_summary' rows"):
        setup_rows.row(_run(tmp_path, [*_rows(), _rows()[2]]))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_the_readers_find_all_eight_in_a_rehearsals_rows_and_agree_with_the_harness_marks(
        workload, capsys, tmp_path):
    """Every cell's program writes what the eight readers read. (A rehearsal's own line
    leaves them out: three accepted tests pin what it prints; ``setup_rows``.)"""
    seed = 2**31 + 45
    result, lines, _ = rehearse(capsys, "--workload", workload, "--seed", str(seed),
                             "--trace", "1", "--out", str(tmp_path))
    assert result["correct"] is True and not set(NAMES) & set(result["metrics"])
    run = {"run": types.SimpleNamespace(recipe=types.SimpleNamespace(
        output_dir=str(tmp_path / workload / f"seed_{seed}_trace_1"))), "rehearse": False}
    metrics = {name: _read(name, run) for name in NAMES}
    assert all(value is not None and value >= 0 for value in metrics.values()), metrics
    # the tests' process keeps the persistent cache off: nothing asked, nothing missed
    assert metrics["setup_cache_misses"] == 0
    assert metrics["setup_compile_requests"] >= 3  # init, weights, the step at least
    # the harness times the same layer from outside (its `set-up:` line): its mark "recipe
    # set up" wraps the program's imports (paid by the first run of a process), the
    # configuration's parse and setup(); its last mark wraps the loop's start, five warm
    # steps and its own state readers
    (line,) = [ln for ln in lines if ln.startswith("set-up: ")]
    marks = {m.group(1): float(m.group(2))
             for m in re.finditer(r"(?:: |; )([^;:]+?) (\d+\.\d+) s", line)}
    summary = setup_rows.row(run)
    inside = summary["setup_s_inside"]
    assert inside <= marks["recipe set up"] + 0.01 and marks["recipe set up"] - inside < 1.5
    spans = summary["spans"]
    built = metrics["setup_build_s"] - spans["setup_pipeline"]
    assert built <= inside and inside - built <= metrics["setup_unspanned_s"] + 0.01
    stepped = sum(metrics[f"setup_{n}_s"]
                  for n in ("step_lower", "step_compile", "step_analysis", "first_step"))
    assert stepped <= spans["compile"] + 0.01 <= summary["loop_start_s"] + 0.02
    last = marks["step compiled and warm steps run"]
    # stated tolerance: on a CPU with no cache the harness's two state readers compile
    # inside its last mark (seconds; more where the step itself takes long to compile),
    # beside the five warm steps
    assert 0 <= last + 0.01 - summary["loop_start_s"] < 5.0 + summary["loop_start_s"]
