"""Perf-regression gate (observability/regression.py + tools/bench_gate.py):
run-artifact parsing for all three formats, direction-aware comparison,
tolerance overrides, and CLI exit codes."""

import json

import pytest

from automodel_tpu.observability.regression import (
    DEFAULT_TOLERANCES,
    compare,
    load_baseline,
    load_run_metrics,
    main,
    summarize_rows,
    write_baseline,
)


def _training_rows(tps=1000.0, n=6):
    rows = [
        {"run_header": True, "git_sha": "abc", "jax_version": "0.4.37"},
        {"step": 1, "event": "compile_costs", "hlo_flops": 1e12},
        {"step": 1, "loss": 4.9, "tps": None},  # compile step logs null tps
    ]
    for s in range(2, n + 2):
        rows.append({"step": s, "loss": 4.0, "tps": tps + s, "mfu": 0.5,
                     "step_time_s": 0.1, "goodput": 0.8 + s * 0.01})
    return rows


def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    return str(path)


class TestSummarize:
    def test_median_skips_header_event_and_null_rows(self):
        out = summarize_rows(_training_rows())
        assert out["tps"] == pytest.approx(1004.5)  # median of 1002..1007
        assert out["mfu"] == 0.5
        assert out["goodput"] == pytest.approx(0.87)  # last row, cumulative

    def test_empty_rows(self):
        assert summarize_rows([]) == {}


class TestLoadRunMetrics:
    def test_training_jsonl(self, tmp_path):
        p = _write_jsonl(tmp_path / "training.jsonl", _training_rows())
        assert load_run_metrics(p)["tps"] == pytest.approx(1004.5)

    def test_bench_line(self, tmp_path):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps({
            "ok": True, "metric": "tokens/sec", "value": 14380.0,
            "unit": "tokens/s/chip", "extra": {"mfu": 0.6},
        }))
        out = load_run_metrics(str(p))
        assert out == {"tps": 14380.0, "mfu": 0.6}

    def test_pretty_printed_benchmark_json(self, tmp_path):
        p = tmp_path / "benchmark.json"
        p.write_text(json.dumps({"tokens_per_sec": 9000.0, "mfu": 0.55,
                                 "step_time_s": 0.8}, indent=2))
        out = load_run_metrics(str(p))
        assert out["tps"] == 9000.0 and out["step_time_s"] == 0.8

    def test_baseline_doubles_as_run(self, tmp_path):
        p = tmp_path / "b.json"
        write_baseline(str(p), {"tps": 123.0})
        assert load_run_metrics(str(p)) == {"tps": 123.0}

    def test_empty_artifact_raises(self, tmp_path):
        p = tmp_path / "empty.jsonl"
        p.write_text("")
        with pytest.raises(ValueError, match="empty"):
            load_run_metrics(str(p))


class TestCompare:
    BASE = {"tps": 1000.0, "mfu": 0.5, "step_time_s": 0.1, "goodput": 0.9}

    def test_within_tolerance_passes(self):
        run = {"tps": 960.0, "mfu": 0.49, "step_time_s": 0.104, "goodput": 0.88}
        assert all(c.ok for c in compare(run, self.BASE, DEFAULT_TOLERANCES))

    def test_throughput_drop_fails_but_gain_passes(self):
        drop = compare({"tps": 900.0}, {"tps": 1000.0})
        assert [c.metric for c in drop if not c.ok] == ["tps"]
        gain = compare({"tps": 1200.0}, {"tps": 1000.0})
        assert all(c.ok for c in gain)

    def test_step_time_direction_inverted(self):
        slower = compare({"step_time_s": 0.12}, {"step_time_s": 0.1})
        assert not slower[0].ok
        faster = compare({"step_time_s": 0.08}, {"step_time_s": 0.1})
        assert faster[0].ok

    def test_missing_metric_passes_unless_required(self):
        res = compare({"tps": 1000.0}, self.BASE)
        assert all(c.ok for c in res)
        res = compare({"tps": 1000.0}, self.BASE, require=("mfu",))
        assert [c.metric for c in res if not c.ok] == ["mfu"]

    def test_tolerance_override(self):
        assert not compare({"tps": 900.0}, {"tps": 1000.0})[0].ok
        assert compare({"tps": 900.0}, {"tps": 1000.0}, {"tps": 0.15})[0].ok

    def test_zero_baseline_not_comparable_but_printable(self):
        """A CPU baseline carries mfu=0.0; the row must pass (nothing to
        compare against) and line() must not blow up on change=None."""
        res = compare({"mfu": 0.0}, {"mfu": 0.0})
        assert res[0].ok and res[0].change is None
        assert "not comparable" in res[0].line()
        # `require` guards MISSING metrics, not zero baselines: a present 0.0
        # (overlap_frac on a single-axis run) passes even when required ...
        assert compare({"mfu": 0.0}, {"mfu": 0.0}, require=("mfu",))[0].ok
        # ... while an absent required metric still fails
        assert not compare({}, {"mfu": 0.0}, require=("mfu",))[0].ok


class TestMeasuredKeys:
    """Profiled matrix rows: measured-profile keys flatten per cell and
    gate with the right directions (overlap up = good, comm frac up = bad)."""

    ROW = {
        "matrix_row": True, "model": "gpt", "seq_len": 1024, "prefetch": True,
        "tokens_per_sec_per_chip": 5000.0,
        "measured_step_time_s": 0.2, "overlap_frac": 0.4,
        "measured_frac_compute": 0.7, "measured_frac_comm": 0.2,
        "measured_frac_moe_a2a": 0.0, "measured_frac_host": 0.1,
        "measured_bound": "compute",  # diagnostic string: must NOT flatten
    }

    def test_matrix_flattening(self, tmp_path):
        p = tmp_path / "matrix.json"
        p.write_text(json.dumps({"matrix": [self.ROW]}))
        out = load_run_metrics(str(p))
        key = "matrix/gpt_s1024_pfon"
        assert out[f"{key}/overlap_frac"] == 0.4
        assert out[f"{key}/measured_step_time_s"] == 0.2
        assert out[f"{key}/measured_frac_comm"] == 0.2
        assert f"{key}/measured_bound" not in out

    def test_jsonl_capture_flattening(self, tmp_path):
        off_row = dict(self.ROW, prefetch=False, overlap_frac=0.1)
        p = _write_jsonl(tmp_path / "matrix.jsonl", [self.ROW, off_row])
        out = load_run_metrics(p)
        assert out["matrix/gpt_s1024_pfon/overlap_frac"] == 0.4
        assert out["matrix/gpt_s1024_pfoff/overlap_frac"] == 0.1

    def test_overlap_frac_higher_is_better(self):
        key = "matrix/gpt_s1024_pfon/overlap_frac"
        worse = compare({key: 0.3}, {key: 0.5})
        assert not worse[0].ok
        better = compare({key: 0.7}, {key: 0.5})
        assert better[0].ok

    def test_comm_frac_lower_is_better(self):
        key = "matrix/gpt_s1024_pfon/measured_frac_comm"
        worse = compare({key: 0.4}, {key: 0.2})
        assert not worse[0].ok
        assert compare({key: 0.1}, {key: 0.2})[0].ok

    def test_measured_step_time_lower_is_better(self):
        key = "matrix/gpt_s1024_pfon/measured_step_time_s"
        assert not compare({key: 0.3}, {key: 0.2})[0].ok
        assert compare({key: 0.15}, {key: 0.2})[0].ok

    def test_default_tolerances_present(self):
        for base in ("measured_step_time_s", "overlap_frac",
                     "measured_frac_compute", "measured_frac_comm",
                     "measured_frac_moe_a2a", "measured_frac_host"):
            assert base in DEFAULT_TOLERANCES, base


class TestCli:
    def _artifacts(self, tmp_path, run_tps=1000.0):
        run = _write_jsonl(tmp_path / "run.jsonl", _training_rows(tps=run_tps))
        base = str(tmp_path / "baseline.json")
        return run, base

    def test_write_then_match_exits_0(self, tmp_path):
        run, base = self._artifacts(tmp_path)
        assert main(["--run", run, "--baseline", base, "--write-baseline"]) == 0
        assert set(load_baseline(base)) == {"tps", "mfu", "step_time_s", "goodput"}
        assert main(["--run", run, "--baseline", base]) == 0

    def test_10pct_tps_regression_exits_1(self, tmp_path):
        run, base = self._artifacts(tmp_path)
        main(["--run", run, "--baseline", base, "--write-baseline"])
        regressed = _write_jsonl(tmp_path / "bad.jsonl", _training_rows(tps=900.0))
        assert main(["--run", regressed, "--baseline", base]) == 1

    def test_loose_tolerance_rescues(self, tmp_path):
        run, base = self._artifacts(tmp_path)
        main(["--run", run, "--baseline", base, "--write-baseline"])
        regressed = _write_jsonl(tmp_path / "bad.jsonl", _training_rows(tps=900.0))
        assert main(["--run", regressed, "--baseline", base,
                     "--tolerance", "tps=0.2", "--tolerance", "goodput=0.2"]) == 0

    def test_missing_artifact_exits_2(self, tmp_path):
        assert main(["--run", str(tmp_path / "nope.jsonl"),
                     "--baseline", str(tmp_path / "b.json")]) == 2

    def test_bad_tolerance_exits_2(self, tmp_path):
        run, base = self._artifacts(tmp_path)
        main(["--run", run, "--baseline", base, "--write-baseline"])
        assert main(["--run", run, "--baseline", base, "--tolerance", "oops"]) == 2

    def test_require_missing_metric_exits_1(self, tmp_path):
        p = tmp_path / "bench.json"
        p.write_text(json.dumps({"metric": "x", "value": 100.0}))  # no mfu
        base = str(tmp_path / "b.json")
        write_baseline(base, {"tps": 100.0, "mfu": 0.5})
        assert main(["--run", str(p), "--baseline", base]) == 0
        assert main(["--run", str(p), "--baseline", base, "--require", "mfu"]) == 1
