"""Bench-matrix smoke: `bench.py --matrix --cpu` rows parse and gate correctly.

Marked ``perf`` (and ``slow``, out of tier-1): run with ``pytest -m perf``.
Runs the real matrix in a subprocess the way the driver would, checks the
one-JSON-line-per-row contract (dense AND moe cells, with routed-throughput
and a2a-share fields on the moe rows), then drives tools/bench_gate.py over
the capture: exit 0 against a matching baseline, exit 1 on a synthetic
per-cell regression, exit 2 on a broken artifact.
"""

import json
import os
import subprocess
import sys

import pytest

pytestmark = [pytest.mark.slow, pytest.mark.perf]

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
GATE = os.path.join(REPO, "tools", "bench_gate.py")


def _gate(*args):
    return subprocess.run([sys.executable, GATE, *args],
                          capture_output=True, text=True, timeout=120)


@pytest.fixture(scope="module")
def matrix_run(tmp_path_factory):
    """One CPU matrix run shared by every scenario (the cells dominate time)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = ""  # the --cpu path re-pins jax_platforms itself
    env.pop("XLA_FLAGS", None)  # 8 virtual devices would slow the tiny cells
    result = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--matrix", "--cpu"],
        capture_output=True, text=True, timeout=840, env=env,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    path = tmp_path_factory.mktemp("matrix") / "matrix.jsonl"
    path.write_text(result.stdout)
    return path


def _rows_and_summary(path):
    docs = [json.loads(ln) for ln in path.read_text().splitlines() if ln.strip()]
    rows = [d for d in docs if d.get("matrix_row")]
    return rows, docs[-1]


def test_matrix_emits_one_parseable_row_per_cell(matrix_run):
    rows, summary = _rows_and_summary(matrix_run)
    # {dense, moe} x 3 seq lens x {off, on}, plus the two a2a hot-path
    # cells (moe_a2a, moe_a2a_pallas) at the headline seq x {off, on}
    assert len(rows) == 16
    cells = {(r["model"], r["seq_len"], r["prefetch"]) for r in rows}
    assert len(cells) == 16
    for r in rows:
        # a --cpu rehearsal says so and keeps its rate off the device metric's name
        assert r["platform"] == "cpu" and "tokens_per_sec_per_chip" not in r
        assert r["cpu_tokens_per_sec_per_device"] > 0
        if r["model"].startswith("moe"):
            assert r["moe/cpu_tokens_per_sec_per_device"] > 0
            assert 0.0 <= r["a2a_byte_share"] <= 1.0
        else:
            assert "moe/tokens_per_sec_per_chip" not in r
    # the a2a cells run the explicit ep dispatch: real all_to_alls in the
    # HLO (nonzero byte share) and a profiled step on the prefetch-on row
    for kind in ("moe_a2a", "moe_a2a_pallas"):
        on = next(r for r in rows if r["model"] == kind and r["prefetch"])
        assert on["a2a_byte_share"] > 0
        assert "dropped_token_frac" in on
        if "overlap_frac" in on:  # profiled step is best-effort decoration
            assert 0.0 <= on["overlap_frac"] <= 1.0
    assert summary["ok"] is True
    assert summary["value"] > 0  # headline: dense s2048 prefetch-on
    assert len(summary["matrix"]) == 16


def test_gate_exit_codes_on_matrix_artifact(matrix_run, tmp_path):
    baseline = str(tmp_path / "baseline.json")

    wrote = _gate("--run", str(matrix_run), "--baseline", baseline, "--write-baseline")
    assert wrote.returncode == 0, wrote.stdout + wrote.stderr
    base = json.load(open(baseline))
    assert "matrix/dense_s2048_pfon/cpu_tps" in base["metrics"]
    assert "matrix/moe_s4096_pfoff/cpu_moe_tps" in base["metrics"]
    # a CPU rehearsal never writes a chip-named gate key
    assert not any(k.endswith(("/tps", "/moe_tps")) for k in base["metrics"])

    same = _gate("--run", str(matrix_run), "--baseline", baseline)
    assert same.returncode == 0, same.stdout + same.stderr
    assert "[gate] PASS" in same.stdout

    # synthetic regression in ONE cell: the gate must name it, not average it away
    rows, summary = _rows_and_summary(matrix_run)
    regressed = tmp_path / "regressed.jsonl"
    with open(regressed, "w") as f:
        for r in rows:
            if r["model"] == "moe" and r["seq_len"] == 8192 and r["prefetch"]:
                r = dict(r, **{"cpu_tokens_per_sec_per_device":
                               r["cpu_tokens_per_sec_per_device"] * 0.4})
            f.write(json.dumps(r) + "\n")
    bad = _gate("--run", str(regressed), "--baseline", baseline,
                "--tolerance", "default=0.3")
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "REGRESSION" in bad.stdout
    assert "matrix/moe_s8192_pfon/cpu_tps" in bad.stdout

    # a broken artifact is a usage error (2), not a silent pass
    empty = tmp_path / "empty.json"
    empty.write_text("")
    assert _gate("--run", str(empty), "--baseline", baseline).returncode == 2


def test_committed_baseline_gates_a_fresh_run(matrix_run):
    """BASELINE.json's metrics key is a live gate target for the matrix."""
    committed = os.path.join(REPO, "BASELINE.json")
    doc = json.load(open(committed))
    assert any(k.startswith("matrix/") for k in doc["metrics"])
    # wide default tolerance: CPU-rehearsal cells jitter run to run
    res = _gate("--run", str(matrix_run), "--baseline", committed,
                "--tolerance", "default=0.9")
    assert res.returncode in (0, 1), res.stdout + res.stderr
    assert "[gate]" in res.stdout
