"""Run-lifetime goodput ledger (observability/runledger.py): wasted-step math,
interval accounting that sums exactly to wall time, recovery per failure
class, the episode stamp, the restore bucket, and the regression-gate hookup
(docs/observability.md "Run-level goodput & SLOs")."""

import json
import os

import pytest

from automodel_tpu.observability import goodput as goodput_mod
from automodel_tpu.observability import regression, runledger


def _goodput_row(step, ts, wall, fracs, episode=None, loss=0.5):
    row = {"step": step, "ts": ts, "loss": loss, "goodput_wall_s": wall}
    row.update({f"goodput/{k}": v for k, v in fracs.items()})
    row["goodput"] = fracs.get("device_step", 0.0)
    if episode is not None:
        row["episode"] = episode
    return row


def _loss_rows(steps, ts0, episode=None, dt=1.0):
    return [{"step": s, "ts": ts0 + i * dt, "loss": 1.0,
             **({"episode": episode} if episode is not None else {})}
            for i, s in enumerate(steps)]


def _sum_seconds(ledger):
    return ledger["goodput_s"] + sum(ledger["badput"].values())


def _frac_sum(ledger):
    return ledger["goodput_e2e"] + sum(ledger["badput_frac"].values())


class TestSegments:
    def test_groups_by_episode_stamp(self):
        rows = _loss_rows([1, 2], 1000.0, episode=0) + \
            _loss_rows([2, 3], 1010.0, episode=1)
        segs = runledger.segments_from_rows(rows)
        assert sorted(segs) == [0, 1]
        assert segs[0].steps == [1, 2] and segs[1].steps == [2, 3]

    def test_falls_back_to_header_split(self):
        rows = [{"run_header": True, "ts": 1000.0}] + _loss_rows([1, 2], 1001.0) \
            + [{"run_header": True, "ts": 1010.0}] + _loss_rows([2, 3], 1011.0)
        segs = runledger.segments_from_rows(rows)
        assert sorted(segs) == [0, 1]
        assert segs[1].steps == [2, 3]

    def test_tracker_state_is_last_snapshot(self):
        rows = [_goodput_row(1, 1001.0, 2.0, {"device_step": 0.5}),
                _goodput_row(2, 1002.0, 3.0, {"device_step": 0.5})]
        seg = runledger.segments_from_rows(rows)[0]
        assert seg.tracker_wall_s == 3.0
        assert seg.tracker_end_ts == 1002.0
        assert seg.tracker_start_ts == pytest.approx(999.0)
        assert seg.bucket_s["device_step"] == pytest.approx(1.5)


class TestWastedSteps:
    def test_no_overlap_no_waste(self):
        segs = runledger.segments_from_rows(
            _loss_rows([1, 2, 3], 1000.0, episode=0)
            + _loss_rows([4, 5], 1010.0, episode=1))
        total, per = runledger.wasted_step_counts(segs)
        assert total == 0 and per == {0: 0, 1: 0}

    def test_crash_restart_overlap(self):
        # episode 0 trained through step 5; episode 1 resumed from the step-3
        # checkpoint and re-ran 4 and 5 before making new progress
        segs = runledger.segments_from_rows(
            _loss_rows([1, 2, 3, 4, 5], 1000.0, episode=0)
            + _loss_rows([4, 5, 6, 7], 1010.0, episode=1))
        total, per = runledger.wasted_step_counts(segs)
        assert total == 2 and per == {0: 0, 1: 2}

    def test_rollback_walkback_counts_discarded_steps(self):
        # in-process rollback: the step counter stays monotone (data
        # fast-forward), so the waste is only visible in the event walk-back
        rows = _loss_rows([1, 2, 3, 4, 5, 6], 1000.0, episode=0)
        rows.insert(5, {"step": 5, "ts": 1004.5, "episode": 0,
                        "resilience/event": "rollback_done",
                        "resilience/from_step": 5, "resilience/to_step": 3})
        segs = runledger.segments_from_rows(rows)
        total, _ = runledger.wasted_step_counts(segs)
        assert total == 2

    def test_elastic_resume_overlap_is_topology_invariant(self):
        # the shrunk pod resumes from step 5 with a different batch size; the
        # optimizer-step numbering is what overlap is measured in, so the
        # re-run of 5 and 6 counts regardless of the topology change
        segs = runledger.segments_from_rows(
            _loss_rows([1, 2, 3, 4, 5, 6], 1000.0, episode=0)
            + _loss_rows([5, 6, 7], 1020.0, episode=1))
        total, per = runledger.wasted_step_counts(segs)
        assert total == 2 and per[1] == 2

    def test_multi_episode_overlap_uses_global_max(self):
        # episode 2 resumes behind BOTH prior segments: overlap counts
        # against the global high-water mark, not just the previous episode
        segs = runledger.segments_from_rows(
            _loss_rows([1, 2, 3, 4], 1000.0, episode=0)
            + _loss_rows([3, 4], 1010.0, episode=1)
            + _loss_rows([3, 4, 5], 1020.0, episode=2))
        total, per = runledger.wasted_step_counts(segs)
        assert per == {0: 0, 1: 2, 2: 2} and total == 4


class TestLedgerAccounting:
    def test_single_episode_sums_to_wall(self):
        rows = [{"run_header": True, "ts": 1000.0}]
        rows += _loss_rows([1, 2, 3], 1001.0)
        rows += [_goodput_row(4, 1004.0, 8.0,
                              {"device_step": 0.5, "compile": 0.25,
                               "data_wait": 0.125, "idle": 0.125})]
        ledger = runledger.build_ledger(rows)
        assert ledger["wall_s"] == pytest.approx(8.0)
        assert ledger["goodput_e2e"] == pytest.approx(0.5)
        assert ledger["badput"]["recompile"] == pytest.approx(2.0)
        assert ledger["badput"]["data_stall"] == pytest.approx(1.0)
        assert ledger["wasted_steps"] == 0
        assert _sum_seconds(ledger) == pytest.approx(ledger["wall_s"], abs=1e-6)
        assert _frac_sum(ledger) == pytest.approx(1.0, abs=1e-3)
        assert runledger.validate_ledger(ledger) == []

    def test_supervised_run_accounts_backoff_reinit_and_waste(self):
        report = {
            "run_id": "r1", "status": "completed", "restarts": 1,
            "episodes": [
                {"index": 0, "started": 999.0, "duration_s": 7.0,
                 "taxonomy": "crash", "hang": False, "returncode": -9},
                {"index": 1, "started": 1008.0, "duration_s": 8.0,
                 "returncode": 0, "hang": False},
            ],
        }
        rows = _loss_rows([1, 2, 3, 4], 1001.0, episode=0)
        rows += [_goodput_row(5, 1005.0, 6.0,
                              {"device_step": 0.5, "idle": 0.5}, episode=0)]
        rows += _loss_rows([4, 5, 6, 7, 8, 9], 1009.0, episode=1)
        rows += [_goodput_row(10, 1015.0, 7.0, {"device_step": 1.0}, episode=1)]
        ledger = runledger.build_ledger(rows, report=report)
        # the 2s supervisor backoff gap between episode windows is badput
        assert ledger["badput"]["restart_backoff"] == pytest.approx(2.0)
        # steps 4 and 5 were re-trained after resume-from-checkpoint
        assert ledger["wasted_steps"] == 2
        assert ledger["episodes"][1]["wasted_steps"] == 2
        # episode 1's 7s of device time splits 2/7 wasted, 5/7 goodput
        assert ledger["badput"]["wasted_steps"] == pytest.approx(2.0)
        assert ledger["goodput_s"] == pytest.approx(3.0 + 5.0)
        assert _sum_seconds(ledger) == pytest.approx(ledger["wall_s"], abs=1e-6)
        assert _frac_sum(ledger) == pytest.approx(1.0, abs=1e-3)
        # recovery: crash at 1006, first step past the old high-water (5) is
        # step 6 at ts 1011
        assert ledger["recovery"]["crash"]["count"] == 1
        assert ledger["recovery"]["crash"]["mean_s"] == pytest.approx(5.0)
        assert ledger["episodes"][0]["recovery_s"] == pytest.approx(5.0)
        assert ledger["run_id"] == "r1"
        assert runledger.validate_ledger(ledger) == []

    def test_episode_without_rows_is_all_reinit(self):
        report = {"status": "aborted", "restarts": 1, "episodes": [
            {"index": 0, "started": 1000.0, "duration_s": 4.0,
             "taxonomy": "backend-init", "returncode": 1},
            {"index": 1, "started": 1005.0, "duration_s": 3.0,
             "taxonomy": "backend-init", "returncode": 1},
        ]}
        ledger = runledger.build_ledger([], report=report)
        assert ledger["goodput_e2e"] == 0.0
        assert ledger["badput"]["reinit"] == pytest.approx(7.0)
        assert ledger["badput"]["restart_backoff"] == pytest.approx(1.0)
        # nothing productive ever ran -> no finite recovery, but the schema
        # still validates (recovery stays empty rather than inventing a value)
        assert ledger["recovery"] == {}
        assert ledger["episodes"][0]["recovery_s"] is None
        assert _frac_sum(ledger) == pytest.approx(1.0, abs=1e-3)
        assert runledger.validate_ledger(ledger) == []

    def test_empty_inputs_yield_no_ledger(self):
        assert runledger.build_ledger([]) is None


class TestLedgerFile:
    def _write_artifacts(self, tmp_path):
        rows = _loss_rows([1, 2], 1001.0, episode=0) + \
            [_goodput_row(3, 1003.0, 4.0, {"device_step": 0.75}, episode=0)]
        with open(tmp_path / "training.jsonl", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
            f.write("{torn json\n")  # a torn tail line must not sink the ledger
        report = {"run_id": "rX", "status": "completed", "restarts": 0,
                  "episodes": [{"index": 0, "started": 999.0,
                                "duration_s": 4.5, "returncode": 0}]}
        with open(tmp_path / "supervisor_report.json", "w") as f:
            json.dump(report, f)

    def test_update_writes_atomic_valid_ledger(self, tmp_path):
        self._write_artifacts(tmp_path)
        ledger = runledger.update_run_ledger(str(tmp_path))
        path = tmp_path / runledger.LEDGER_FILENAME
        assert path.exists()
        assert runledger.validate_ledger(ledger) == []
        assert runledger.load_ledger(str(tmp_path)) == ledger
        # no stray tmp files from the atomic write
        assert not [p for p in os.listdir(tmp_path) if p.startswith(".run_ledger")]

    def test_goodput_report_cli(self, tmp_path, capsys):
        self._write_artifacts(tmp_path)
        runledger.update_run_ledger(str(tmp_path))
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "tools"))
        import goodput_report
        assert goodput_report.main([str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "goodput_e2e" in out and "episode 0" in out
        assert goodput_report.main([str(tmp_path), "--json"]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert doc["version"] == runledger.RUN_LEDGER_VERSION

    def test_validate_flags_broken_documents(self):
        assert runledger.validate_ledger("nope") != []
        good = {"version": runledger.RUN_LEDGER_VERSION, "wall_s": 10.0,
                "goodput_e2e": 0.5, "wasted_steps": 0,
                "badput": {c: 0.0 for c in runledger.BADPUT_CLASSES},
                "badput_frac": {c: 0.0 for c in runledger.BADPUT_CLASSES},
                "recovery": {},
                "episodes": [{"index": 0, "seconds": {"goodput": 5.0}}]}
        good["badput_frac"]["idle"] = 0.5
        assert runledger.validate_ledger(good) == []
        bad = dict(good, badput_frac=dict(good["badput_frac"], idle=0.9))
        assert any("!= 1" in p for p in runledger.validate_ledger(bad))
        bad = dict(good, badput={"idle": 1.0})
        assert any("taxonomy" in p for p in runledger.validate_ledger(bad))


class TestGateIntegration:
    def _ledger(self, tmp_path, goodput_e2e=0.6, idle=0.3):
        doc = {"version": runledger.RUN_LEDGER_VERSION, "wall_s": 100.0,
               "goodput_e2e": goodput_e2e, "wasted_steps": 2,
               "badput": {c: 0.0 for c in runledger.BADPUT_CLASSES},
               "badput_frac": {c: 0.0 for c in runledger.BADPUT_CLASSES},
               "recovery": {"crash": {"count": 1, "mean_s": 4.0, "max_s": 4.0}},
               "episodes": [{"index": 0, "seconds": {"goodput": 60.0}}]}
        doc["badput_frac"]["idle"] = idle
        doc["badput_frac"]["wasted_steps"] = round(1 - goodput_e2e - idle, 6)
        path = tmp_path / "run_ledger.json"
        path.write_text(json.dumps(doc))
        return str(path)

    def test_load_run_metrics_lifts_ledger_keys(self, tmp_path):
        run = regression.load_run_metrics(self._ledger(tmp_path))
        assert run["goodput_e2e"] == pytest.approx(0.6)
        assert run["wasted_steps"] == 2.0
        assert run["badput/idle"] == pytest.approx(0.3)
        assert run["recovery_s/crash"] == pytest.approx(4.0)

    def test_directions_gate_the_right_way(self, tmp_path):
        base = regression.load_run_metrics(self._ledger(tmp_path))
        # goodput_e2e regresses by DROPPING; badput/recovery/wasted by RISING
        worse = dict(base, **{"goodput_e2e": 0.3, "badput/idle": 0.6,
                              "recovery_s/crash": 8.0, "wasted_steps": 6.0})
        failed = {c.metric for c in regression.compare(worse, base) if not c.ok}
        assert {"goodput_e2e", "badput/idle",
                "recovery_s/crash", "wasted_steps"} <= failed
        better = dict(base, **{"goodput_e2e": 0.9, "badput/idle": 0.05,
                               "recovery_s/crash": 1.0, "wasted_steps": 0.0})
        assert all(c.ok for c in regression.compare(better, base))

    def test_bench_gate_cli_on_ledger(self, tmp_path):
        run = self._ledger(tmp_path)
        baseline = str(tmp_path / "baseline.json")
        assert regression.main(["--run", run, "--baseline", baseline,
                                "--write-baseline"]) == 0
        assert regression.main(["--run", run, "--baseline", baseline]) == 0
        os.makedirs(tmp_path / "deg", exist_ok=True)
        degraded = self._ledger(tmp_path / "deg", goodput_e2e=0.3, idle=0.6)
        assert regression.main(["--run", degraded, "--baseline", baseline]) == 1

    def test_ledger_metric_rows_use_contract_keys(self, tmp_path):
        doc = runledger.load_ledger(self._ledger(tmp_path))
        row = runledger.ledger_metric_rows(doc)
        assert row["ledger/goodput_e2e"] == pytest.approx(0.6)
        assert row["ledger/wasted_steps"] == 2
        assert row["ledger/episodes"] == 1
        assert row["ledger/recovery_s/crash"] == pytest.approx(4.0)
        assert row["badput/idle"] == pytest.approx(0.3)


class TestEpisodeStamp:
    def test_metric_logger_stamps_rows_and_header(self, tmp_path, monkeypatch):
        monkeypatch.setenv("AUTOMODEL_EPISODE",
                           json.dumps({"index": 2, "run_id": "abc"}))
        from automodel_tpu.loggers.metric_logger import MetricLogger
        path = tmp_path / "training.jsonl"
        with MetricLogger(path) as ml:
            ml.log_header(model_id="m")
            ml.log(7, loss=1.25)
        rows = [json.loads(x) for x in path.read_text().splitlines()]
        assert rows[0]["episode"] == 2 and rows[0]["run_id"] == "abc"
        assert rows[1]["episode"] == 2 and rows[1]["step"] == 7

    def test_no_env_no_stamp(self, tmp_path, monkeypatch):
        monkeypatch.delenv("AUTOMODEL_EPISODE", raising=False)
        from automodel_tpu.loggers.metric_logger import MetricLogger
        path = tmp_path / "training.jsonl"
        with MetricLogger(path) as ml:
            ml.log(1, loss=1.0)
        row = json.loads(path.read_text())
        assert "episode" not in row

    def test_garbage_env_is_ignored(self, tmp_path, monkeypatch):
        monkeypatch.setenv("AUTOMODEL_EPISODE", "{not json")
        from automodel_tpu.loggers.metric_logger import MetricLogger
        path = tmp_path / "training.jsonl"
        with MetricLogger(path) as ml:
            ml.log(1, loss=1.0)
        assert "episode" not in json.loads(path.read_text())


class TestRestoreBucket:
    def test_restore_in_buckets(self):
        assert "restore" in goodput_mod.BUCKETS

    def test_a_restore_ahead_of_the_wall_keeps_fractions_summing(self):
        """The tracker is built first thing in set-up; the resume's `restore` span
        bills it like any span, and the wall that opens at set-up's end starts less
        what the buckets hold: model and data building are not idle, the restore is
        not lost."""
        t = [100.0]
        tracker = goodput_mod.GoodputTracker(clock=lambda: t[0])
        t[0] += 3.0  # mesh, model, data: re-init, nobody's bucket
        with tracker.track("restore"):
            t[0] += 5.0
        t[0] += 1.0  # loggers, the step function
        tracker.open_wall()
        t[0] += 5.0
        tracker.add("device_step", 5.0)
        assert tracker.wall_s == pytest.approx(10.0)
        totals = tracker.totals()
        assert totals["restore"] == pytest.approx(5.0)
        assert totals["idle"] == pytest.approx(0.0)
        snap = tracker.snapshot()
        assert snap["goodput/restore"] == pytest.approx(0.5)
        assert snap["goodput_wall_s"] == pytest.approx(10.0)
        fracs = [v for k, v in snap.items() if k.startswith("goodput/")]
        assert sum(fracs) == pytest.approx(1.0, abs=1e-3)
