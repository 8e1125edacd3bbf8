"""Unit tests for observability/trace_analysis.py (XPlane traces read through
``jax.profiler.ProfileData``).

Three layers, none touching the profiler:

- the committed golden fixture (tests/fixtures/trace/, regenerate with
  tools/gen_trace_fixture.py) exercises the reader against bytes the
  real jax.profiler wrote;
- hand-encoded synthetic XSpace bytes pin the classification/overlap math to
  values computed by hand;
- randomized interval-set properties check union/intersection against a
  brute-force per-unit-cell count.
"""
from __future__ import annotations

import pathlib
import random
import struct

import pytest

from automodel_tpu.observability import trace_analysis as ta

FIXTURES = pathlib.Path(__file__).resolve().parents[1] / "fixtures" / "trace"


# ------------------------------------------------- wire-format encode helpers
def _vint(v: int) -> bytes:
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        if v:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field_varint(field: int, value: int) -> bytes:
    return _vint(field << 3 | 0) + _vint(value)


def _field_bytes(field: int, payload: bytes) -> bytes:
    return _vint(field << 3 | 2) + _vint(len(payload)) + payload


def _field_str(field: int, s: str) -> bytes:
    return _field_bytes(field, s.encode())


def _event_metadata_entry(meta_id: int, name: str) -> bytes:
    meta = _field_varint(1, meta_id) + _field_str(2, name)
    return _field_varint(1, meta_id) + _field_bytes(2, meta)


def _stat(meta_id: int, *, ref: int | None = None, s: str | None = None,
          i64: int | None = None, dbl: float | None = None) -> bytes:
    out = _field_varint(1, meta_id)
    if ref is not None:
        out += _field_varint(7, ref)
    if s is not None:
        out += _field_str(5, s)
    if i64 is not None:
        out += _field_varint(4, i64 & ((1 << 64) - 1))
    if dbl is not None:
        out += _vint(2 << 3 | 1) + struct.pack("<d", dbl)
    return out


def _event(meta_id: int, offset_ps: int, dur_ps: int,
           stats: tuple[bytes, ...] = ()) -> bytes:
    out = (_field_varint(1, meta_id) + _field_varint(2, offset_ps)
           + _field_varint(3, dur_ps))
    for st in stats:
        out += _field_bytes(4, st)
    return out


def _line(name: str, timestamp_ns: int, events: list[bytes]) -> bytes:
    out = _field_str(2, name) + _field_varint(3, timestamp_ns)
    for ev in events:
        out += _field_bytes(4, ev)
    return out


def _plane(name: str, lines: list[bytes], event_names: dict[int, str],
           stat_names: dict[int, str] | None = None) -> bytes:
    out = _field_str(2, name)
    for ln in lines:
        out += _field_bytes(3, ln)
    for mid, mname in event_names.items():
        out += _field_bytes(4, _event_metadata_entry(mid, mname))
    for mid, mname in (stat_names or {}).items():
        out += _field_bytes(5, _event_metadata_entry(mid, mname))
    return out


def _xspace(*planes: bytes) -> bytes:
    return b"".join(_field_bytes(1, p) for p in planes)


# --------------------------------------------------------------- interval math
class TestIntervalMath:
    def test_merge_basic(self):
        assert ta.merge_intervals([(5, 9), (0, 3), (2, 4)]) == [(0, 4), (5, 9)]

    def test_merge_drops_empty_and_inverted(self):
        assert ta.merge_intervals([(3, 3), (7, 2)]) == []

    def test_union_counts_overlap_once(self):
        assert ta.union_total([(0, 10), (5, 15)]) == 15

    def test_intersection_disjoint(self):
        assert ta.intersection_total([(0, 5)], [(5, 10)]) == 0

    def test_intersection_nested(self):
        assert ta.intersection_total([(0, 100)], [(10, 20), (30, 40)]) == 20

    @pytest.mark.parametrize("seed", range(5))
    def test_union_intersection_vs_bruteforce(self, seed):
        """Randomized interval sets vs counting covered unit cells."""
        rng = random.Random(seed)

        def rand_set(n):
            out = []
            for _ in range(n):
                s = rng.randrange(0, 200)
                out.append((s, s + rng.randrange(0, 40)))
            return out

        a, b = rand_set(rng.randrange(1, 12)), rand_set(rng.randrange(1, 12))
        cover_a = {x for s, e in a for x in range(s, e)}
        cover_b = {x for s, e in b for x in range(s, e)}
        assert ta.union_total(a) == len(cover_a)
        assert ta.union_total(b) == len(cover_b)
        assert ta.intersection_total(a, b) == len(cover_a & cover_b)
        # identity the analyzer relies on: |A|+|B|-|A∩B| == |A∪B|
        assert (ta.union_total(a) + ta.union_total(b)
                - ta.intersection_total(a, b)) == ta.union_total(a + b)


# ----------------------------------------------------------- instruction index
_HLO = """\
HloModule jit_step

ENTRY main {
  %fusion.1 = f32[128,128]{1,0} fusion(f32[128,64]{1,0} %p0), kind=kLoop, metadata={op_name="jit(step)/attention/dot_general"}
  %fusion.7 = f32[64,256]{1,0} fusion(f32[64,256]{1,0} %w1), kind=kLoop, metadata={op_name="jit(step)/moe_experts/moe_combine/mul"}
  %all-reduce.2 = f32[128]{0} all-reduce(f32[128]{0} %fusion.1), replica_groups={{0,1,2,3},{4,5,6,7}}, metadata={op_name="jit(step)/mlp/sum"}
  %all-to-all.3 = f32[8]{0} all-to-all(f32[8]{0} %fusion.1), replica_groups={{0,1}}, metadata={op_name="jit(step)/moe_dispatch/a2a"}
  ROOT %all-gather-start.4 = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %fusion.1), replica_groups={{0,1,2,3,4,5,6,7}}, metadata={op_name="jit(step)/mlp/ag"}
}
"""
_MESH = {"dp": 4, "ep": 2, "tp": 8}


class TestInstructionIndex:
    def test_scopes_and_collectives(self):
        idx = ta.build_instruction_index(_HLO, _MESH)
        assert idx["fusion.1"].scope == "attention"
        assert idx["fusion.1"].collective is None
        # innermost scope wins: moe_combine beats moe_experts
        assert idx["fusion.7"].scope == "moe_combine"
        ar = idx["all-reduce.2"]
        assert (ar.collective, ar.axis, ar.moe) == ("all-reduce", "dp", False)
        a2a = idx["all-to-all.3"]
        assert (a2a.collective, a2a.axis, a2a.moe) == ("all-to-all", "ep", True)
        ag = idx["all-gather-start.4"]
        assert (ag.collective, ag.axis) == ("all-gather", "tp")

    def test_classify_async_done_falls_back_to_start(self):
        idx = ta.build_instruction_index(_HLO, _MESH)
        info = ta._classify("all-gather-done.4", idx)
        assert info.collective == "all-gather"
        assert info.axis == "tp"

    def test_classify_without_index_uses_name_prefix(self):
        info = ta._classify("all-to-all.9", None)
        assert info.collective == "all-to-all"
        assert info.moe is True
        assert ta._classify("fusion.3", None).collective is None


# ------------------------------------------------------------ synthetic traces
def _synthetic_space() -> bytes:
    """One device plane, "XLA Ops" line at t0=1000ns, hand-picked intervals::

        fusion.1       [0,      100_000)   compute
        all-reduce.2   [50_000, 150_000)   comm  (overlaps compute by 50_000)
        all-to-all.3   [200_000, 250_000)  comm+moe
        window = 250_000 ps, busy = 200_000, host gap = 50_000
    """
    names = {1: "fusion.1", 2: "all-reduce.2", 3: "all-to-all.3"}
    events = [_event(1, 0, 100_000), _event(2, 50_000, 100_000),
              _event(3, 200_000, 50_000)]
    return _xspace(_plane("/device:TPU:0", [_line("XLA Ops", 1000, events)],
                          names))


class TestSyntheticTrace:
    def test_parse_roundtrip(self):
        planes = ta.read_xspace(_synthetic_space())
        assert [p.name for p in planes] == ["/device:TPU:0"]
        (line,) = planes[0].lines
        assert line.name == "XLA Ops"
        assert [e.name for e in line.events] == [
            "fusion.1", "all-reduce.2", "all-to-all.3"]
        # absolute starts: line timestamp_ns * 1000 + offset_ps
        assert line.events[0].start_ps == 1_000_000
        assert line.events[1].start_ps == 1_050_000
        assert line.events[2].dur_ps == 50_000

    def test_category_math(self, tmp_path):
        p = tmp_path / "host.xplane.pb"
        p.write_bytes(_synthetic_space())
        r = ta.analyze_trace(str(p), hlo_text=_HLO, mesh_axes=_MESH,
                             steps_hint=1)
        assert r is not None and r.steps == 1
        ps = 1e-12
        assert r.window_s == pytest.approx(250_000 * ps)
        assert r.compute_s == pytest.approx(100_000 * ps)
        assert r.comm_s == pytest.approx(150_000 * ps)
        assert r.overlap_s == pytest.approx(50_000 * ps)
        assert r.host_s == pytest.approx(50_000 * ps)
        assert r.moe_a2a_s == pytest.approx(50_000 * ps)
        assert r.overlap_frac == pytest.approx(1 / 3)
        # exact per-step identity
        assert (r.compute_s + r.comm_s - r.overlap_s + r.host_s
                ) == pytest.approx(r.step_time_s, rel=1e-12)
        assert r.comm_axis_s["dp"] == pytest.approx(100_000 * ps)
        assert r.comm_axis_s["ep"] == pytest.approx(50_000 * ps)
        assert r.scope_s["attention"] == pytest.approx(100_000 * ps)
        # host_frac = 0.2 <= 0.25, comm > compute, moe < 0.5*comm -> comms
        assert r.measured_bound == "comms"

    def test_moe_bound_when_a2a_dominates(self, tmp_path):
        names = {1: "fusion.1", 3: "all-to-all.3"}
        events = [_event(1, 0, 50_000), _event(3, 0, 200_000)]
        sp = _xspace(_plane("/device:TPU:0",
                            [_line("XLA Ops", 0, events)], names))
        p = tmp_path / "host.xplane.pb"
        p.write_bytes(sp)
        r = ta.analyze_trace(str(p), steps_hint=1)
        assert r.measured_bound == "moe_a2a"
        assert r.overlap_frac == pytest.approx(0.25)

    def test_summary_row_keys(self, tmp_path):
        p = tmp_path / "host.xplane.pb"
        p.write_bytes(_synthetic_space())
        row = ta.analyze_trace(str(p), hlo_text=_HLO, mesh_axes=_MESH,
                               steps_hint=1).summary_row()
        for key in ("trace/steps", "trace/events", "trace/window_s",
                    "measured_step_time_s", "measured_t_compute_s",
                    "measured_t_comm_s", "measured_t_moe_a2a_s",
                    "measured_t_host_s", "measured_t_overlap_s",
                    "overlap_frac", "measured_bound", "measured_frac_compute",
                    "measured_frac_comm", "measured_frac_moe_a2a",
                    "measured_frac_host", "measured_comm_axis_dp_s",
                    "measured_comm_axis_ep_s", "trace/scope/attention_s"):
            assert key in row, key
        assert 0.0 <= row["overlap_frac"] <= 1.0

    def test_cpu_style_op_events_via_stats(self, tmp_path):
        """CPU thunk-executor lines aren't named "XLA Ops" — op events are
        recognized by hlo stats (with a ref-valued hlo_op resolving through
        the plane's stat_metadata), and the python TraceMe line is ignored."""
        stat_names = {10: "hlo_op", 11: "dot.4", 12: "hlo_module", 13: "jit_f"}
        ev = _event(1, 0, 70_000, stats=(
            _stat(10, ref=11), _stat(12, ref=13)))
        traceme = _event(2, 0, 500_000)  # host-side python span, no hlo stats
        sp = _xspace(_plane(
            "/host:CPU",
            [_line("tf_XLATfrtCpuClient/1", 0, [ev]),
             _line("python", 0, [traceme])],
            {1: "dot.4", 2: "TraceMe"}, stat_names))
        planes = ta.read_xspace(sp)
        evs = ta._op_events(planes)
        assert [e.name for e in evs] == ["dot.4"]
        assert evs[0].stats["hlo_op"] == "dot.4"
        assert evs[0].stats["hlo_module"] == "jit_f"
        r = ta.analyze_trace(str(_write(tmp_path, sp)), steps_hint=1)
        assert r.module == "jit_f"
        assert r.compute_s == pytest.approx(70_000 * 1e-12)

    def test_empty_trace_returns_none(self, tmp_path):
        sp = _xspace(_plane("/host:CPU", [_line("python", 0, [])], {}))
        assert ta.analyze_trace(str(_write(tmp_path, sp))) is None

    def test_dominant_module_sets_window(self, tmp_path):
        """Auxiliary executables outside the step program don't stretch the
        analysis window: the dominant (most device time) module defines it."""
        stat_names = {10: "hlo_module", 11: "jit_step", 12: "jit_aux"}
        evs = [
            _event(1, 0, 400_000, stats=(_stat(10, ref=11),)),
            # tiny helper program 1ms later must not inflate host time
            _event(2, 1_000_000_000, 1_000, stats=(_stat(10, ref=12),)),
        ]
        sp = _xspace(_plane("/device:TPU:0", [_line("XLA Ops", 0, evs)],
                            {1: "fusion.1", 2: "copy.1"}, stat_names))
        r = ta.analyze_trace(str(_write(tmp_path, sp)), steps_hint=1)
        assert r.module == "jit_step"
        assert r.window_s == pytest.approx(400_000 * 1e-12)
        assert r.host_s == 0.0


def _write(tmp_path, data: bytes):
    p = tmp_path / "host.xplane.pb"
    p.write_bytes(data)
    return p


# ------------------------------------------------------------- golden fixture
@pytest.mark.skipif(not (FIXTURES / "golden.xplane.pb").exists(),
                    reason="golden fixture not generated")
class TestGoldenFixture:
    @pytest.fixture(scope="class")
    def report(self):
        hlo = (FIXTURES / "golden_hlo.txt").read_text()
        return ta.analyze_trace(str(FIXTURES / "golden.xplane.pb"),
                                hlo_text=hlo)

    def test_find_xplane_files(self):
        found = ta.find_xplane_files(str(FIXTURES))
        assert str(FIXTURES / "golden.xplane.pb") in found

    def test_read_xspace_planes(self):
        planes = ta.read_xspace(str(FIXTURES / "golden.xplane.pb"))
        assert planes and all(isinstance(p, ta.TracePlane) for p in planes)
        assert any(line.events for p in planes for line in p.lines)

    def test_step_count_detected(self, report):
        # tools/gen_trace_fixture.py runs the jitted step exactly 3 times
        assert report is not None
        assert report.steps == 3
        assert report.module.startswith("jit_")

    def test_scope_attribution(self, report):
        # the fixture step nests named scopes "attention" and "mlp"
        assert report.scope_s.get("attention", 0) > 0
        assert report.scope_s.get("mlp", 0) > 0

    def test_identity_and_ranges(self, report):
        assert (report.compute_s + report.comm_s - report.overlap_s
                + report.host_s) == pytest.approx(report.step_time_s,
                                                  rel=1e-9)
        assert report.comm_s == 0.0  # single-device CPU step: no collectives
        assert 0.0 <= report.overlap_frac <= 1.0
        assert report.window_s > 0 and report.num_events > 0

    def test_steps_hint_overrides(self):
        r = ta.analyze_trace(str(FIXTURES / "golden.xplane.pb"), steps_hint=1)
        assert r.steps == 1 and r.steps_hint == 1
        assert r.step_time_s == pytest.approx(r.window_s)


# -------------------------------------------------------------- reconciliation
def _report(**over):
    base = dict(trace_path="t", num_events=10, module="jit_step", steps=1,
                steps_hint=None, window_s=1.0, step_time_s=1.0, compute_s=0.7,
                comm_s=0.2, moe_a2a_s=0.0, host_s=0.15, overlap_s=0.05,
                overlap_frac=0.25, comm_axis_s={}, scope_s={},
                measured_bound="compute")
    base.update(over)
    return ta.TraceReport(**base)


class TestReconcile:
    def test_agree(self):
        out = ta.reconcile_with_roofline(
            _report(), {"roofline_bound": "compute",
                        "roofline_step_time_s": 0.8})
        assert out["trace/bound_agrees"] is True
        assert out["trace/verdict"] == "agree"
        assert out["trace/roofline_vs_measured"] == pytest.approx(0.8)

    def test_memory_maps_to_compute(self):
        # the trace can't split compute- from memory-bound: both device-busy
        out = ta.reconcile_with_roofline(_report(),
                                         {"roofline_bound": "memory"})
        assert out["trace/bound_agrees"] is True

    def test_disagree_names_both(self):
        out = ta.reconcile_with_roofline(
            _report(measured_bound="comms"), {"roofline_bound": "compute"})
        assert out["trace/bound_agrees"] is False
        assert "analytic=compute" in out["trace/verdict"]
        assert "measured=comms" in out["trace/verdict"]

    def test_no_roofline_is_empty(self):
        assert ta.reconcile_with_roofline(_report(), None) == {}
        assert ta.reconcile_with_roofline(_report(), {}) == {}


def _hlo(*instructions):
    """HLO text of ``(name, op_name, is a Mosaic call)`` instructions."""
    return "\n".join(
        f'  %{name} = bf16[8]{{0}} ' + ('custom-call(%p), custom_call_target="tpu_custom_call"' if mosaic
                                         else "bitcast(%p)") + f', metadata={{op_name="{op_name}"}}'
        for name, op_name, mosaic in instructions)


_SCAN = "jit(train_step)/{}/while/body/closed_call/{}attention/{}"
_FWD, _REPLAY, _BWD = (
    _SCAN.format("jvp()", "", "flash_attention_fwd/pallas_call"),
    _SCAN.format("transpose(jvp())", "checkpoint/rematted_computation/",
                 "flash_attention_fwd/pallas_call"),
    _SCAN.format("transpose(jvp())", "checkpoint/", "flash_attention_bwd/pallas_call"))
_SPLIT = "jit(step)/transpose(jvp())/checkpoint/attention/flash_attention_bwd_{}/pallas_call"
# names and op_names as the chip's compiler wrote them (the dense cell's step, PR 46): a
# kernel's bitcasts (``pallas_call.<n>``) share its op_name and are no calls
_KERNEL_CALL_CASES = {
    "compiled_scan_replayed": (_hlo(
        ("flash_attention_fwd.17", _FWD, True), ("pallas_call.64", _FWD, False),
        ("flash_attention_fwd.18", _REPLAY, True), ("pallas_call.65", _REPLAY, False),
        ("pallas_call.66", _REPLAY, False), ("flash_attention_bwd.10", _BWD, True),
        ("pallas_call.67", _BWD, False), ("fusion.3", _SCAN.format("jvp()", "", "dot_general"), False),
    ), {"flash_attention_fwd": 2, "flash_attention_bwd": 1}),
    "compiled_scan_saved": (_hlo(
        ("flash_attention_fwd.7", _FWD, True), ("pallas_call.48", _FWD, False),
        ("pallas_call.49", _FWD, False), ("flash_attention_bwd.10", _BWD, True),
        ("pallas_call.50", _BWD, False),
    ), {"flash_attention_fwd": 1, "flash_attention_bwd": 1}),
    "compiled_unrolled_saved_split": (_hlo(
        ("flash_attention_fwd.2", "jit(step)/jvp(attention)/flash_attention_fwd/pallas_call", True),
        ("flash_attention_fwd.3", "jit(step)/jvp(attention)/flash_attention_fwd/pallas_call", True),
        ("flash_attention_bwd_dq.1", _SPLIT.format("dq"), True),
        ("flash_attention_bwd_dkv.1", _SPLIT.format("dkv"), True),
        ("flash_attention_bwd_dq.2", _SPLIT.format("dq"), True),
        ("flash_attention_bwd_dkv.2", _SPLIT.format("dkv"), True),
    ), {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 2, "flash_attention_bwd_dkv": 2}),
    "interpreted": (_hlo(
        ("while.1", "jit(step)/jvp()/while/body/closed_call/flash_attention_fwd", False),
        ("add.2", "jit(step)/jvp()/while/body/closed_call/flash_attention_fwd/while/body/cond", False),
        ("while.3", "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
                    "rematted_computation/flash_attention_fwd/while/body", False),
        # outlined: the head of the path is lost
        ("add.4", "checkpoint/rematted_computation/flash_attention_fwd", False),
        ("add.5", "flash_attention_fwd", False),
        ("while.6", "jit(step)/transpose(jvp())/while/body/closed_call/checkpoint/"
                    "flash_attention_bwd/while", False),
    ), {"flash_attention_fwd": 2, "flash_attention_bwd": 1}),
    "no_kernel": (_hlo(("dot.1", "jit(step)/jvp(attention)/dot_general", False),
                       ("add.1", "not_flash_attention_fwd/x", False)), {}),
}


@pytest.mark.parametrize("case", list(_KERNEL_CALL_CASES))
def test_kernel_call_counts(case):
    hlo, want = _KERNEL_CALL_CASES[case]
    assert ta.kernel_call_counts(hlo) == want


_SCATTER = ('  ROOT %{} = {} scatter(%p0, %p1, %p2), update_window_dims={{1}}, to_apply=%region, '
            'metadata={{op_name="{}"}}')
_MOE = "jit(train_step)/while/body/closed_call/{}/while/body/closed_call/{}moe/moe_experts/{}"


# lines as the chip's compiler wrote them (the MoE cell's step at PR 46)
_ROW_SCATTER_CASES = {
    "parent_step": ("\n".join([
        _SCATTER.format("scatter-add.109", "f32[8192,2048]{1,0}",
                        _MOE.format("jvp(layer_stack)", "", "moe_combine/scatter-add")),
        _SCATTER.format("scatter-add.106", "bf16[8192,2048]{1,0}", _MOE.format(
            "transpose(jvp(layer_stack))", "checkpoint/", "moe_dispatch/scatter-add")),
        _SCATTER.format("scatter-add.105", "f32[65536]{0}", _MOE.format(
            "transpose(jvp(layer_stack))", "checkpoint/", "moe_combine/scatter-add")),
        _hlo(("sort.83", _MOE.format("jvp(layer_stack)", "", "moe_combine/scatter-add"), False),
             ("fusion.495", _MOE.format("jvp(layer_stack)", "", "moe_combine/scatter-add"), False))]), 3),
    "under_a_bare_jvp": ("\n".join([
        _SCATTER.format("scatter-add.6", "f32[8192,2048]{1,0}", "jit(loss)/jvp(moe_combine)/scatter-add"),
        _SCATTER.format("scatter-add.9", "bf16[8192,2048]{1,0}",
                        "jit(loss)/transpose(jvp(moe_dispatch))/scatter-add")]), 2),
    "gathers_only": (_hlo(
        ("gather.14", _MOE.format("jvp(layer_stack)", "", "moe_combine/gather"), False),
        ("fusion.2", "jit(step)/moe/not_moe_combine/scatter-add", False)), 0),
    "other_scatters": ("\n".join([
        _SCATTER.format("scatter-add.1", "s32[128]{0}", _MOE.format("jvp(layer_stack)", "", "scatter-add")),
        _SCATTER.format("scatter-add.2", "bf16[151936,2048]{1,0}",
                        "jit(train_step)/transpose(jvp(lm_head_loss))/jit(_take)/scatter-add"),
        _SCATTER.format("scatter-add.3", "f32[128]{0}", "jit(train_step)/moe/moe_gate/scatter-add")]), 0),
}


@pytest.mark.parametrize("case", list(_ROW_SCATTER_CASES))
def test_moe_row_scatter_count(case):
    """The scatter itself is counted, not the gathers, sorts and fusions that share its
    ``op_name``, nor a scatter under another scope."""
    hlo, want = _ROW_SCATTER_CASES[case]
    assert ta.moe_row_scatter_count(hlo) == want
