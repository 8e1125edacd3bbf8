"""Unit tests for observability/signals.py (the signals bundle)."""
from __future__ import annotations

import json

import pytest

from automodel_tpu.observability import signals as sig

_ROOFLINE = {
    "roofline_bound": "compute", "roofline_step_time_s": 0.5,
    "roofline_t_compute_s": 0.5, "roofline_t_memory_s": 0.3,
    "roofline_t_comm_s": 0.1,
}
_TRACE_SUMMARY = {
    "measured_bound": "compute", "measured_step_time_s": 0.55,
    "overlap_frac": 0.4, "measured_frac_compute": 0.8,
    "measured_frac_comm": 0.1, "measured_frac_moe_a2a": 0.0,
    "measured_frac_host": 0.15,
    "trace/analytic_bound": "compute", "trace/bound_agrees": True,
    "trace/verdict": "agree",
}


class _Plan:
    total_bytes = 6 * 2**30
    headroom_bytes = 10 * 2**30
    hbm_limit_bytes = 16 * 2**30
    fits = True


def _full_doc():
    return sig.build_signals(
        cell={"model": "m", "seq_len": 2048}, mesh_axes={"dp": 4, "tp": 2},
        roofline=_ROOFLINE, costs={"hlo_flops": 1e12,
                                   "comm_bytes_total": 1e9,
                                   "comm_bytes_moe_a2a": 0},
        trace_summary=_TRACE_SUMMARY, memory_plan=_Plan(),
        compile_summary={"compile_cache_hits": 2, "compile_cache_misses": 1,
                         "compile_aot": 3, "compile_jit_fallback": 0})


class TestBuild:
    def test_full_document_validates(self):
        doc = _full_doc()
        assert sig.validate_signals(doc) == []
        (cell,) = doc["cells"]
        assert cell["cell"] == {"model": "m", "mesh": {"dp": 4, "tp": 2},
                                "seq_len": 2048}
        assert cell["analytic"]["roofline_bound"] == "compute"
        assert cell["measured"]["overlap_frac"] == 0.4
        assert cell["reconciliation"]["agrees"] is True
        assert cell["memory"]["total_gib"] == 6.0
        assert cell["memory"]["hbm_headroom_gib"] == 10.0
        assert cell["compile_cache"] == {"hits": 2, "misses": 1, "aot": 3,
                                         "jit_fallback": 0}

    def test_absent_sources_are_explicit_null(self):
        doc = sig.build_signals(cell={"model": "m", "seq_len": 128})
        assert sig.validate_signals(doc) == []
        (cell,) = doc["cells"]
        for section in ("analytic", "measured", "reconciliation", "memory",
                        "compile_cache"):
            assert section in cell and cell[section] is None

    def test_prebuilt_cells_list(self):
        c = sig.build_cell(cell={"model": "a", "seq_len": 1})
        doc = sig.build_signals([c, c])
        assert len(doc["cells"]) == 2
        assert sig.validate_signals(doc) == []

    def test_partial_roofline_degrades_to_null(self):
        # missing roofline_t_* keys must not produce a half-filled section
        doc = sig.build_signals(cell={}, roofline={"roofline_bound": "compute"})
        assert doc["cells"][0]["analytic"] is None


class TestValidate:
    def test_rejects_wrong_version(self):
        doc = _full_doc()
        doc["version"] = 99
        assert any("version" in p for p in sig.validate_signals(doc))

    def test_rejects_missing_section_key(self):
        doc = _full_doc()
        del doc["cells"][0]["measured"]
        assert any("measured key missing" in p for p in sig.validate_signals(doc))

    def test_rejects_bool_in_numeric_field(self):
        doc = _full_doc()
        doc["cells"][0]["measured"]["overlap_frac"] = True
        assert any("is bool" in p for p in sig.validate_signals(doc))

    def test_rejects_overlap_frac_out_of_range(self):
        doc = _full_doc()
        doc["cells"][0]["measured"]["overlap_frac"] = 1.5
        assert any("outside [0, 1]" in p for p in sig.validate_signals(doc))

    def test_rejects_null_required_field(self):
        doc = _full_doc()
        doc["cells"][0]["reconciliation"]["verdict"] = None
        assert any("null but required" in p for p in sig.validate_signals(doc))

    def test_non_dict_document(self):
        assert sig.validate_signals([1, 2]) != []


class TestWrite:
    def test_atomic_write_and_roundtrip(self, tmp_path):
        path = tmp_path / "signals.json"
        sig.write_signals(str(path), _full_doc())
        loaded = json.loads(path.read_text())
        assert sig.validate_signals(loaded) == []
        assert not list(tmp_path.glob("*.tmp"))

    def test_refuses_invalid_document(self, tmp_path):
        doc = _full_doc()
        doc["cells"][0]["measured"]["overlap_frac"] = 2.0
        with pytest.raises(ValueError, match="schema"):
            sig.write_signals(str(tmp_path / "signals.json"), doc)
        assert not (tmp_path / "signals.json").exists()
