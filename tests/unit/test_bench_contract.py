"""bench.py's failure contract: the LAST stdout line is ALWAYS parseable JSON.

The driver reads exactly one thing from a bench run — the final stdout line —
so every escape path (BaseException through run_cli, a missing or dead chip,
code bugs) must end stdout with a machine-parseable ``{"ok": false}`` line and
a non-zero exit. There is no CPU stand-in for a measurement, and the parent of
``--matrix`` never holds the chip its children need.
"""

from __future__ import annotations

import json

import pytest

import bench


def _stdout_docs(capsys):
    out = capsys.readouterr().out
    lines = [ln for ln in out.splitlines() if ln.strip()]
    assert lines, "bench printed nothing to stdout"
    return lines, [json.loads(ln) for ln in lines]


def _fake_backend(monkeypatch, name="tpu"):
    """Make bench.main think a non-CPU accelerator is attached."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: name)


class TestRunCliGuard:
    def test_baseexception_still_ends_with_json_line(self, monkeypatch, capsys):
        def boom(argv=None):
            raise KeyboardInterrupt("ctrl-c mid-bench")

        monkeypatch.setattr(bench, "main", boom)
        rc = bench.run_cli([])
        lines, docs = _stdout_docs(capsys)
        assert rc == 1
        assert docs[-1]["ok"] is False
        assert "KeyboardInterrupt" in docs[-1]["error"]

    def test_systemexit_from_library_is_caught(self, monkeypatch, capsys):
        monkeypatch.setattr(
            bench, "main", lambda argv=None: (_ for _ in ()).throw(SystemExit(3))
        )
        rc = bench.run_cli([])
        _, docs = _stdout_docs(capsys)
        assert rc == 1
        assert docs[-1]["ok"] is False

    def test_clean_run_passes_through_rc(self, monkeypatch, capsys):
        monkeypatch.setattr(bench, "main", lambda argv=None: 0)
        assert bench.run_cli([]) == 0


class TestNoStandInForTheChip:
    """A measurement needs the chip: every way of not having one ends in
    ``{"ok": false}`` and a non-zero exit, never in a CPU run."""

    def test_canary_failure_is_ok_false_nonzero_exit(self, monkeypatch, capsys):
        _fake_backend(monkeypatch)
        monkeypatch.setattr(
            bench, "_canary_dispatch",
            lambda: (_ for _ in ()).throw(RuntimeError("wedged chip")),
        )
        monkeypatch.setattr(
            bench, "_full_bench",
            lambda **kw: pytest.fail("the bench ran behind a dead canary"),
        )
        rc = bench.main([])
        _, docs = _stdout_docs(capsys)
        assert rc != 0
        assert docs[-1]["ok"] is False
        assert "wedged chip" in docs[-1]["error"]

    def test_matrix_parent_never_initialises_a_backend(self, monkeypatch, capsys, tmp_path):
        """Every cell is a child that needs the chip, and a chip belongs to one
        process: with the children stubbed, the parent route of --matrix returns
        without a single JAX backend having been created."""
        from jax._src import xla_bridge

        from automodel_tpu.resilience import harness

        monkeypatch.setattr(xla_bridge, "_backends", {})
        for probe in ("_init_backend", "_canary_dispatch"):
            monkeypatch.setattr(
                bench, probe, lambda *a, **k: pytest.fail("parent probed the backend"))
        monkeypatch.setattr(harness, "run_isolated", lambda argv, timeout_s: {
            "docs": [{"ok": True, "device": "stub"}], "timed_out": False,
            "returncode": 0, "stderr_tail": ""})
        monkeypatch.setattr(harness, "run_cells", lambda specs, **kw: {"ran": 0})
        rc = bench.main(["--matrix", "--matrix-dir", str(tmp_path)])
        _, docs = _stdout_docs(capsys)
        assert xla_bridge._backends == {}
        assert rc == 0 and docs[-1]["ok"] is True and docs[-1]["matrix"] == []

    def test_backend_error_late_in_the_bench_is_ok_false(self, monkeypatch, capsys):
        _fake_backend(monkeypatch)
        monkeypatch.setattr(bench, "_canary_dispatch", lambda: None)
        monkeypatch.setattr(
            bench, "_full_bench",
            lambda **kw: (_ for _ in ()).throw(RuntimeError("libtpu crashed late")),
        )
        rc = bench.main([])
        _, docs = _stdout_docs(capsys)
        assert rc != 0
        assert docs[-1]["ok"] is False
        assert "libtpu crashed late" in docs[-1]["error"]
        assert docs[-1]["taxonomy"] and "libtpu crashed late" in docs[-1]["tail"]

    def test_code_bug_is_reported_with_its_error(self, monkeypatch, capsys):
        _fake_backend(monkeypatch)
        monkeypatch.setattr(bench, "_canary_dispatch", lambda: None)
        monkeypatch.setattr(
            bench, "_full_bench",
            lambda **kw: (_ for _ in ()).throw(ValueError("shape mismatch in our code")),
        )
        rc = bench.main([])
        _, docs = _stdout_docs(capsys)
        assert rc == 1
        assert docs[-1]["ok"] is False
        assert "shape mismatch" in docs[-1]["error"]

    def test_cpu_mode_error_keeps_json_contract(self, monkeypatch, capsys):
        monkeypatch.setattr(
            bench, "_matrix_bench",
            lambda **kw: (_ for _ in ()).throw(RuntimeError("rehearsal died")),
        )
        rc = bench.main(["--cpu", "--matrix"])
        _, docs = _stdout_docs(capsys)
        assert rc == 1
        assert docs[-1]["ok"] is False
        assert "rehearsal died" in docs[-1]["error"]

    def test_no_chip_is_ok_false_nonzero_exit(self, monkeypatch, capsys):
        _fake_backend(monkeypatch, name="cpu")
        for ran in ("_full_bench", "_tune_bench", "_canary_dispatch"):
            monkeypatch.setattr(
                bench, ran, lambda *a, **k: pytest.fail("something ran without a chip"))
        for argv in ([], ["--tune"], ["--dynamics"]):
            rc = bench.main(argv)
            _, docs = _stdout_docs(capsys)
            assert rc != 0
            assert docs[-1]["ok"] is False and docs[-1]["platform"] == "cpu"
            assert "value" not in docs[-1]

    def test_cpu_is_an_explicit_rehearsal_under_its_own_names(self, capsys):
        """--cpu alone measures nothing; with --matrix/--tune its rows keep
        their rate away from the device metric's name."""
        rc = bench.main(["--cpu"])
        _, docs = _stdout_docs(capsys)
        assert rc != 0 and docs[-1]["ok"] is False
        assert bench._rate_key(cpu=False) == "tokens_per_sec_per_chip"
        assert bench._rate_key(cpu=True) not in ("tokens_per_sec_per_chip", "tps", "mfu")


class TestMatrixRowShape:
    def test_matrix_summary_doc_flattens_for_the_gate(self):
        from automodel_tpu.observability.regression import load_run_metrics

        rows = [
            {"matrix_row": True, "model": "dense", "seq_len": 2048,
             "prefetch": False, "tokens_per_sec_per_chip": 100.0},
            {"matrix_row": True, "model": "moe", "seq_len": 4096,
             "prefetch": True, "tokens_per_sec_per_chip": 80.0,
             "moe/tokens_per_sec_per_chip": 640.0, "a2a_byte_share": 0.2},
        ]
        doc = {"ok": True, "metric": "m", "value": 100.0, "matrix": rows}
        import json as _json

        for text, label in [
            (_json.dumps(doc), "summary doc"),
            ("\n".join(_json.dumps(r) for r in rows) + "\n" + _json.dumps(doc),
             "stdout capture"),
        ]:
            import tempfile

            with tempfile.NamedTemporaryFile("w", suffix=".json", delete=False) as f:
                f.write(text)
                path = f.name
            got = load_run_metrics(path)
            assert got["matrix/dense_s2048_pfoff/tps"] == 100.0, label
            assert got["matrix/moe_s4096_pfon/tps"] == 80.0, label
            assert got["matrix/moe_s4096_pfon/moe_tps"] == 640.0, label
            assert "matrix/moe_s4096_pfon/a2a_share" not in got, label


class TestProfiledCellStep:
    """bench.py --profile: one traced step per cell -> measured_* row keys and
    a schema-valid signals cell; any failure degrades to empty, never raises."""

    def test_measured_keys_and_signals_cell(self):
        import jax
        import jax.numpy as jnp

        def step(params, opt_state, batch):
            loss = jnp.sum((batch @ params) ** 2) + opt_state
            return params, opt_state, {"loss": loss}

        params = jnp.ones((16, 16), jnp.float32)
        opt_state = jnp.float32(0.0)
        batch = jnp.ones((8, 16), jnp.float32)
        compiled = jax.jit(step).lower(params, opt_state, batch).compile()
        hlo = compiled.as_text()

        measured, cell = bench._profile_cell_step(
            compiled, params, opt_state, batch, hlo,
            {"model": "dense", "seq_len": 2048})
        assert measured, "profiled step produced no measured keys"
        assert measured["measured_step_time_s"] > 0
        assert 0.0 <= measured["overlap_frac"] <= 1.0
        assert measured["measured_bound"] in (
            "compute", "comms", "moe_a2a", "input")
        for key in ("measured_frac_compute", "measured_frac_comm",
                    "measured_frac_moe_a2a", "measured_frac_host"):
            assert key in measured, key

        from automodel_tpu.observability.signals import (
            build_signals,
            validate_signals,
        )

        assert cell is not None
        assert validate_signals(build_signals([cell])) == []
        assert cell["cell"]["seq_len"] == 2048
        assert cell["measured"] is not None

    def test_failure_degrades_to_empty(self, capsys):
        measured, cell = bench._profile_cell_step(
            None, None, None, None, None, {"model": "x", "seq_len": 1})
        assert measured == {} and cell is None
        assert "measured_* keys" in capsys.readouterr().err
