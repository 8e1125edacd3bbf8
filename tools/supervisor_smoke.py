#!/usr/bin/env python
"""Self-checking CPU smoke for supervised runs (docs/resilience.md
"Supervised runs").

Two phases, each independently selectable with ``--phase``:

- ``supervise``: a tiny mock-llama training run under ``tools/supervise.py``
  with two chaos injections — SIGKILL after step 6 and a silent hang at step
  10. Asserts the supervisor classifies the kill as ``crash`` and the hang as
  ``watchdog``, restarts twice from the latest verifiable checkpoint, the
  loss trajectory stays finite through both outages, and
  ``supervisor_report.json`` + the timeline spans tell the story.
- ``torn``: the same run with ``async_save`` and a ``kill_point: save``
  injection — the process dies while step-8 array writes are in flight and
  before the manifest commits. Asserts the restart walks BACK past the torn
  step-8 directory to step 4 (never resumes from unverifiable bytes) and
  still finishes.

Usage:  JAX_PLATFORMS=cpu python tools/supervisor_smoke.py \
            [--workdir DIR] [--phase supervise|torn|all]

The same scenarios run under pytest as ``pytest -m chaos``
(tests/functional/test_supervisor_chaos.py).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import textwrap

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
from chaos_smoke import _write_cfg  # noqa: E402  (shared tiny-llama config)
MAX_STEPS = 14
CKPT_EVERY = 4
KILL_STEP = 6
HANG_STEP = 10
SAVE_KILL_STEP = 8

_KILL_HANG = textwrap.dedent(f"""\
resilience:
  enabled: true
  chaos:
    enabled: true
    kill_at_step: [{KILL_STEP}]
    hang_at_step: [{HANG_STEP}]
    hang_hold_s: 120
""")

_TORN_SAVE = textwrap.dedent(f"""\
resilience:
  enabled: true
  chaos:
    enabled: true
    kill_at_step: [{SAVE_KILL_STEP}]
    kill_point: save
""")


def _env() -> dict[str, str]:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env.setdefault("PYTHONHASHSEED", "0")
    return env


def _supervise(cfg_path: str, out_dir: str, *, max_restarts: int,
               hang_timeout: float = 20.0) -> int:
    argv = [
        sys.executable, os.path.join(REPO, "tools", "supervise.py"),
        "--out-dir", out_dir,
        "--max-restarts", str(max_restarts),
        "--hang-timeout", str(hang_timeout),
        "--poll-interval", "0.2", "--grace", "5",
        "--",
        sys.executable, "-m", "automodel_tpu.recipes.llm.train_ft",
        "-c", cfg_path,
    ]
    return subprocess.run(argv, env=_env(), cwd=REPO).returncode


def _loss_rows(out_dir: str) -> list[dict]:
    with open(os.path.join(out_dir, "training.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    return [r for r in rows if "loss" in r and "step" in r]


def _report(out_dir: str) -> dict:
    with open(os.path.join(out_dir, "supervisor_report.json")) as f:
        return json.load(f)


def phase_supervise(root: str) -> None:
    print(f"[supervisor_smoke] supervise: SIGKILL at step {KILL_STEP}, "
          f"silent hang at step {HANG_STEP} ...")
    cfg = _write_cfg(root, "supervised", ckpt=True, chaos=True,
                     resilience=_KILL_HANG)
    out_dir = os.path.join(root, "supervised", "out")
    rc = _supervise(cfg, out_dir, max_restarts=3)
    assert rc == 0, f"supervised run exited {rc}"

    report = _report(out_dir)
    assert report["status"] == "completed", report["status"]
    assert report["restarts"] == 2, f"restarts={report['restarts']}"
    taxonomies = [e.get("taxonomy") for e in report["episodes"]]
    assert len(report["episodes"]) == 3, taxonomies
    # SIGKILL leaves no stderr marker: classified off the signal death
    assert taxonomies[0] in ("crash", "unknown"), taxonomies
    assert taxonomies[1] == "watchdog", taxonomies
    assert report["episodes"][1]["hang"], "hang episode not flagged as hang"
    assert taxonomies[2] is None, taxonomies

    rows = _loss_rows(out_dir)
    losses = [r["loss"] for r in rows]
    assert losses and all(v == v for v in losses), "non-finite loss logged"
    steps = {r["step"] for r in rows}
    missing = set(range(1, MAX_STEPS + 1)) - steps - {KILL_STEP, HANG_STEP}
    assert not missing, f"loss trajectory has holes: {sorted(missing)}"
    assert MAX_STEPS in steps, "run never reached the final step"

    with open(os.path.join(out_dir, "supervisor_timeline.json")) as f:
        names = {ev.get("name") for ev in json.load(f).get("traceEvents", [])}
    for want in ("supervisor/episode_0", "supervisor/episode_1",
                 "supervisor/episode_2", "supervisor/restart_1",
                 "supervisor/restart_2", "goodput_e2e"):
        assert want in names, f"timeline lacks {want}: {sorted(names)}"

    _check_run_ledger(root, out_dir, report)
    print(f"[supervisor_smoke]     taxonomies {taxonomies}, "
          f"{len(steps)} distinct steps, final loss {losses[-1]:.3f}")


def _check_run_ledger(root: str, out_dir: str, report: dict) -> None:
    """The acceptance criterion end to end: the chaos run left an atomic,
    schema-valid run_ledger.json whose fractions sum to 1 with the kill's
    re-trained steps counted, per-episode classes matching the supervisor's
    taxonomy, finite recovery times — and bench_gate exits non-zero when
    goodput_e2e regresses against a baseline written from the real ledger
    (docs/observability.md "Run-level goodput & SLOs")."""
    from automodel_tpu.observability import regression, runledger

    print("[supervisor_smoke] supervise: run ledger + SLO gate ...")
    ledger = runledger.load_ledger(out_dir)
    problems = runledger.validate_ledger(ledger)
    assert not problems, f"run_ledger.json schema-invalid: {problems}"
    total = ledger["goodput_e2e"] + sum(ledger["badput_frac"].values())
    assert abs(total - 1.0) < 1e-3, f"fractions sum to {total}, not 1"
    # the kill at step 6 forces a resume from step 4: steps 5 (and 6) are
    # re-trained, and the hang at 10 adds more — wasted work must be visible
    assert ledger["wasted_steps"] > 0, "kill+resume left wasted_steps == 0"
    assert ledger["badput"]["wasted_steps"] > 0.0
    assert ledger["restarts"] == 2 and len(ledger["episodes"]) == 3
    assert ledger["run_id"] == report["run_id"]
    # per-episode badput classes line up with the supervisor's taxonomy, and
    # every failed episode has a finite time-to-recovery
    for ep, rep_ep in zip(ledger["episodes"], report["episodes"]):
        assert ep["taxonomy"] == rep_ep.get("taxonomy"), (ep, rep_ep)
        if ep["taxonomy"] is not None:
            assert ep["recovery_s"] is not None and ep["recovery_s"] >= 0.0, ep
    classes = set(ledger["recovery"])
    assert classes == {t for t in (e.get("taxonomy")
                                   for e in report["episodes"]) if t}, classes
    # the resume paths billed restore time (satellite: no longer idle)
    assert ledger["badput"]["restore"] > 0.0, ledger["badput"]
    # the supervisor metric stream carries the flat ledger/badput row
    with open(os.path.join(out_dir, "supervisor.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    ledger_rows = [r for r in rows if "ledger/goodput_e2e" in r]
    assert ledger_rows and ledger_rows[-1]["ledger/episodes"] == 3

    # SLO gate: baseline from the real ledger gates itself clean, then a
    # degraded copy (half the goodput, idle absorbing) must exit 1
    ledger_path = os.path.join(out_dir, runledger.LEDGER_FILENAME)
    baseline = os.path.join(root, "slo_baseline.json")
    rc = regression.main(["--run", ledger_path, "--baseline", baseline,
                          "--write-baseline"])
    assert rc == 0, "SLO baseline write failed"
    rc = regression.main(["--run", ledger_path, "--baseline", baseline])
    assert rc == 0, f"real ledger must gate clean against itself, got {rc}"
    degraded = dict(ledger)
    degraded["goodput_e2e"] = round(ledger["goodput_e2e"] * 0.5, 6)
    degraded["badput_frac"] = dict(ledger["badput_frac"])
    degraded["badput_frac"]["idle"] = round(
        ledger["badput_frac"]["idle"] + ledger["goodput_e2e"] * 0.5, 6)
    degraded_path = os.path.join(root, "degraded_ledger.json")
    with open(degraded_path, "w") as f:
        json.dump(degraded, f)
    rc = regression.main(["--run", degraded_path, "--baseline", baseline])
    assert rc == 1, f"gate must trip on a halved goodput_e2e, got {rc}"
    print(f"[supervisor_smoke]     ledger valid: goodput_e2e="
          f"{ledger['goodput_e2e']:.3f}, wasted_steps="
          f"{ledger['wasted_steps']}, recovery classes {sorted(classes)}, "
          f"gate 0 -> 1 on degradation")


def phase_torn(root: str) -> None:
    print(f"[supervisor_smoke] torn: SIGKILL mid-async-save of step "
          f"{SAVE_KILL_STEP} ...")
    cfg = _write_cfg(root, "torn", ckpt=True, chaos=True, async_save=True,
                     resilience=_TORN_SAVE)
    out_dir = os.path.join(root, "torn", "out")
    rc = _supervise(cfg, out_dir, max_restarts=2)
    assert rc == 0, f"torn-save run exited {rc}"

    report = _report(out_dir)
    assert report["status"] == "completed", report["status"]
    assert report["restarts"] == 1, f"restarts={report['restarts']}"
    assert report["episodes"][0].get("taxonomy") in ("crash", "unknown")

    # the restart must resume from step 4, not the torn step-8 bytes: the
    # first logged step after the sequence rewinds is CKPT_EVERY + 1
    steps = [r["step"] for r in _loss_rows(out_dir)]
    rewinds = [steps[i] for i in range(1, len(steps))
               if steps[i] <= steps[i - 1]]
    assert rewinds == [CKPT_EVERY + 1], (
        f"expected one rewind to step {CKPT_EVERY + 1} (walk-back past the "
        f"torn step_{SAVE_KILL_STEP}), got {rewinds} in {steps}")
    assert steps[-1] == MAX_STEPS, steps[-2:]

    # the re-saved step-8 checkpoint must now verify (marker removed,
    # manifest committed)
    from automodel_tpu.checkpoint.checkpointing import SAVING_MARKER
    from automodel_tpu.checkpoint.manifest import has_manifest, verify_manifest
    step8 = os.path.join(root, "torn", "ckpt", f"step_{SAVE_KILL_STEP}")
    assert not os.path.exists(os.path.join(step8, SAVING_MARKER))
    assert has_manifest(step8), f"step_{SAVE_KILL_STEP} lacks a manifest"
    problems = verify_manifest(step8)
    assert not problems, (
        f"re-saved step_{SAVE_KILL_STEP} fails verification: {problems}")
    print(f"[supervisor_smoke]     rewound to step {CKPT_EVERY + 1}, "
          f"finished at {steps[-1]}, step_{SAVE_KILL_STEP} re-verified")


PHASES = {"supervise": phase_supervise, "torn": phase_torn}


def main(workdir: str | None = None, phase: str = "all") -> int:
    owns_workdir = workdir is None
    root = workdir or tempfile.mkdtemp(prefix="supervisor_smoke_")
    try:
        print(f"[supervisor_smoke] workdir {root}")
        for name, fn in PHASES.items():
            if phase in ("all", name):
                fn(root)
        print("[supervisor_smoke] PASS")
        return 0
    finally:
        if owns_workdir:
            shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--workdir", default=None,
                        help="keep artifacts here instead of a temp dir")
    parser.add_argument("--phase", default="all",
                        choices=["all", *PHASES])
    args = parser.parse_args()
    sys.exit(main(args.workdir, args.phase))
