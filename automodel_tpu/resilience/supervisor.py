"""Process-level run supervision: heartbeat, hang detection, failure taxonomy,
and bounded restart-from-checkpoint (docs/resilience.md "Supervised runs").

Everything below the process boundary — rollback, verified restore, elastic
resume — already survives inside a live interpreter. What nothing survived
until now is the interpreter itself dying: SIGKILL from the OOM killer, a
wedged runtime that stops making progress without exiting, a crash loop that
burns the restart budget in seconds. :class:`Supervisor` wraps any entrypoint
in a monitored subprocess and closes that gap:

- **Heartbeat contract**: the child writes ``{"step", "time", "pid"}`` to the
  file named by the ``AUTOMODEL_HEARTBEAT_FILE`` env var (atomic tmp+rename;
  :class:`HeartbeatWriter` is wired into ``Observability.heartbeat`` so every
  recipe emits it for free). Hang detection arms only after the FIRST beat —
  an uninstrumented child is never killed for silence it never promised to
  break.
- **Hang detector**: no beat for ``hang_timeout_s`` -> SIGABRT (the in-process
  stall watchdog has already dumped all-thread stacks to ``stall_*.txt`` by
  then — the report links the newest one), grace, SIGKILL, restart.
- **Failure taxonomy** (:func:`classify_failure`): exit status + stderr tail +
  forensics artifacts (``oom_report.json``, ``spike_report.json``) reduce to
  one label — ``backend-init`` / ``oom`` / ``numerics`` / ``preemption`` /
  ``data`` / ``watchdog`` / ``crash`` / ``unknown`` — with a transient flag
  that the report records (the supervisor restarts every failure class within
  budget; restart is cheap, a lost run is not).
- **Crash-loop protection**: restarts are bounded (``max_restarts``) with the
  ``utils/retry.py`` backoff curve between attempts — per-host deterministic
  jitter, so a pod's workers do not thundering-herd the TPU runtime when they
  all die together. Budget exhausted -> structured abort in the report.
- **Restart-from-checkpoint**: a restart re-invokes the same argv; the
  recipe's resume path restores the newest *verifiable* checkpoint and the
  elastic restore (PR 14) lets a restart on a degraded topology proceed
  instead of aborting. The supervisor adds nothing to that path — which is
  the point: one recovery implementation, exercised from both sides of the
  process boundary.

Every episode is a span on an ``events.py`` timeline plus a ``supervisor/*``
metric row, and the whole run is summarized in an atomic
``supervisor_report.json`` (tools/supervise.py is the CLI).
"""

from __future__ import annotations

import dataclasses
import glob
import json
import logging
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from typing import Any, Callable

from automodel_tpu.utils.retry import RetryConfig

logger = logging.getLogger(__name__)

__all__ = [
    "HEARTBEAT_ENV",
    "EPISODE_ENV",
    "SUPERVISOR_REPORT_VERSION",
    "HeartbeatWriter",
    "read_heartbeat",
    "classify_error_text",
    "classify_failure",
    "SupervisorConfig",
    "Supervisor",
]

HEARTBEAT_ENV = "AUTOMODEL_HEARTBEAT_FILE"
# JSON {"index": episode, "run_id": ...} exported to every child so the
# MetricLogger can stamp episode identity into the shared training.jsonl
# (loggers/metric_logger.py duplicates the literal to stay import-light)
EPISODE_ENV = "AUTOMODEL_EPISODE"
# v2: run-level run_id/started, per-episode started timestamps (the run
# ledger stitches episode wall windows from them)
SUPERVISOR_REPORT_VERSION = 2

# -------------------------------------------------------------- heartbeat file


class HeartbeatWriter:
    """Atomic step-stamped heartbeat file, written from the train loop's step
    callback (``Observability.heartbeat``). Time-throttled so a fast step loop
    does not turn the beat into fsync noise; a step change always writes."""

    def __init__(self, path: str, min_interval_s: float = 1.0):
        self.path = str(path)
        self.min_interval_s = float(min_interval_s)
        self._last_wall = 0.0
        self._last_step: int | None = None

    @classmethod
    def from_env(cls, env: Any = None) -> "HeartbeatWriter | None":
        path = (env or os.environ).get(HEARTBEAT_ENV)
        return cls(path) if path else None

    def beat(self, step: int | None = None) -> None:
        now = time.time()
        if (step == self._last_step
                and now - self._last_wall < self.min_interval_s):
            return
        self._last_wall = now
        self._last_step = step
        doc = {"step": step, "time": now, "pid": os.getpid()}
        try:
            d = os.path.dirname(os.path.abspath(self.path)) or "."
            fd, tmp = tempfile.mkstemp(prefix=".heartbeat.", dir=d)
            with os.fdopen(fd, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, self.path)
        except OSError:
            # a beat must never take the run down; the supervisor treats a
            # silent child as hung, which is the honest signal anyway
            logger.debug("heartbeat write to %s failed", self.path, exc_info=True)


def read_heartbeat(path: str) -> dict[str, Any] | None:
    """The last beat, or None when the file is absent/unreadable."""
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError, ValueError):
        return None


# ---------------------------------------------------------------- taxonomy

# Markers of a backend attach that can genuinely succeed on retry: another
# holder releasing the chips, a runtime restarting, a transient RPC timeout.
# The runtime-layer names (libtpu/PJRT/"TPU platform") and bare "UNAVAILABLE"
# are here too — they identify infrastructure faults, BUT only after the
# non-transient overrides below have had their look (BENCH_r05: a lowering
# error whose message contains "UNAVAILABLE" is still a compile failure).
TRANSIENT_INIT_MARKERS = (
    "Unable to initialize backend",
    "No visible",
    "failed to connect",
    "DEADLINE_EXCEEDED",
    "Device or resource busy",
    "already in use",
    "halted",
    "hardware failure",
    "libtpu",
    "PJRT",
    "TPU platform",
    "UNAVAILABLE",
)
# Markers that override ANY init-looking text: the error came out of lowering/
# compilation or mid-dispatch, where "UNAVAILABLE" wraps a deterministic
# failure (BENCH_r05: a convert_element_type lowering error whose message
# *contains* "Unable to initialize backend ... UNAVAILABLE" retried on CPU as
# if the chip were absent). Retrying these wastes the budget and mislabels a
# code/compiler bug as infrastructure.
NON_TRANSIENT_MARKERS = (
    "setup/compile error",
    "convert_element_type",
    "INVALID_ARGUMENT",
    "Mosaic failed",
    "lowering",
    "INTERNAL: during context",
)
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory",
                "OOM when allocating", "MemoryError")
_NUMERICS_MARKERS = ("non-finite", "nonfinite", "NaN", "overflow encountered",
                     "loss=nan")
_PREEMPT_MARKERS = ("SIGTERM received", "preemption", "preempted")
_DATA_MARKERS = ("DataLoader", "dataset", "tokenizer", "vocab size mismatch",
                 "collate")


def classify_error_text(text: str) -> tuple[str, bool]:
    """Reduce an error message / traceback tail to ``(taxonomy, transient)``.

    Order matters: OOM and numerics first (their tracebacks routinely thread
    through backend frames), then the non-transient lowering/compile markers
    (which override init-looking text — the r05 misclassification), then the
    genuinely transient init markers, then preemption/data.
    """
    t = text or ""
    if any(m in t for m in _OOM_MARKERS):
        return "oom", False
    if any(m in t for m in _NUMERICS_MARKERS):
        return "numerics", False
    if any(m in t for m in NON_TRANSIENT_MARKERS):
        return "compile", False
    if any(m in t for m in TRANSIENT_INIT_MARKERS):
        return "backend-init", True
    if any(m in t for m in _PREEMPT_MARKERS):
        return "preemption", True
    if any(m in t for m in _DATA_MARKERS):
        return "data", False
    return "unknown", False


def _fresh(path: str, since: float | None) -> bool:
    try:
        return os.path.exists(path) and (
            since is None or os.path.getmtime(path) >= since)
    except OSError:
        return False


def classify_failure(
    returncode: int | None = None,
    stderr_tail: str = "",
    out_dir: str | None = None,
    hang: bool = False,
    since: float | None = None,
) -> dict[str, Any]:
    """One failed episode -> ``{"taxonomy", "transient", "evidence"}``.

    Evidence precedence: a supervisor-detected hang beats everything (the
    child may have been SIGKILLed into an arbitrary exit status); then the
    forensics artifacts the observability layer wrote *this episode*
    (``oom_report.json`` / ``spike_report.json`` under ``out_dir``, mtime
    gated by ``since``); then the stderr tail text; then the bare exit
    status — SIGTERM reads as preemption, any other signal death as
    ``crash``, a nonzero exit with no markers as ``unknown``.
    """
    if hang:
        return {"taxonomy": "watchdog", "transient": True,
                "evidence": "heartbeat went stale; supervisor killed the run"}
    if out_dir:
        oom = os.path.join(out_dir, "oom_report.json")
        if _fresh(oom, since):
            return {"taxonomy": "oom", "transient": False, "evidence": oom}
        spike = os.path.join(out_dir, "spike_report.json")
        if _fresh(spike, since):
            return {"taxonomy": "numerics", "transient": False, "evidence": spike}
    taxonomy, transient = classify_error_text(stderr_tail)
    if taxonomy != "unknown":
        return {"taxonomy": taxonomy, "transient": transient,
                "evidence": "stderr tail marker"}
    if returncode is not None and returncode < 0:
        sig = -returncode
        if sig == signal.SIGTERM:
            return {"taxonomy": "preemption", "transient": True,
                    "evidence": "killed by SIGTERM"}
        name = signal.Signals(sig).name if sig in signal.Signals._value2member_map_ \
            else str(sig)
        return {"taxonomy": "crash", "transient": True,
                "evidence": f"killed by {name}"}
    return {"taxonomy": "unknown", "transient": False,
            "evidence": f"exit status {returncode}"}


# ---------------------------------------------------------------- supervisor


@dataclasses.dataclass(frozen=True)
class SupervisorConfig:
    """Restart budget, hang threshold, and the backoff curve between attempts.

    ``hang_timeout_s`` should sit ABOVE the child's stall-watchdog threshold
    (observability config ``watchdog.threshold_s``) so the in-process stack
    dump lands before the SIGABRT — the report then links it as forensics.
    """

    max_restarts: int = 3
    hang_timeout_s: float = 900.0
    poll_interval_s: float = 0.5
    grace_s: float = 10.0
    stderr_tail_lines: int = 40
    backoff: RetryConfig = dataclasses.field(default_factory=lambda: RetryConfig(
        max_attempts=1, base_delay_s=2.0, max_delay_s=60.0))

    @classmethod
    def from_dict(cls, raw: Any) -> "SupervisorConfig":
        if raw is None:
            return cls()
        if hasattr(raw, "to_dict"):
            raw = raw.to_dict()
        d = dict(raw)
        backoff = RetryConfig.from_dict(d.pop("backoff", None))
        known = {f.name for f in dataclasses.fields(cls)} - {"backoff"}
        return cls(backoff=backoff,
                   **{k: v for k, v in d.items() if k in known})


def _atomic_write_json(path: str, doc: dict[str, Any]) -> None:
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    fd, tmp = tempfile.mkstemp(prefix=".supervisor_report.", dir=d)
    try:
        with os.fdopen(fd, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


class _StderrTee(threading.Thread):
    """Drain the child's stderr to ours while keeping a bounded tail for the
    classifier — the pipe must drain regardless or the child blocks on it."""

    def __init__(self, stream, tail_lines: int, echo=None):
        super().__init__(daemon=True)
        self.stream = stream
        self.tail: deque[str] = deque(maxlen=tail_lines)
        self.echo = echo if echo is not None else sys.stderr

    def run(self) -> None:
        try:
            for line in self.stream:
                self.tail.append(line)
                try:
                    self.echo.write(line)
                    self.echo.flush()
                except (OSError, ValueError):
                    pass
        except (OSError, ValueError):
            pass

    def text(self) -> str:
        return "".join(self.tail)


class Supervisor:
    """Run ``argv`` under supervision; see the module docstring for the model.

    ``out_dir`` is where the child writes its artifacts (heartbeat file,
    stall dumps, forensics reports) and where ``supervisor_report.json`` +
    ``supervisor_timeline.json`` land. ``metric_sink(row)`` receives one flat
    ``supervisor/*`` row per episode; by default rows append to
    ``out_dir/supervisor.jsonl``.
    """

    def __init__(
        self,
        argv: list[str],
        out_dir: str,
        config: SupervisorConfig | None = None,
        env: dict[str, str] | None = None,
        metric_sink: Callable[[dict[str, Any]], None] | None = None,
        popen: Callable[..., Any] = subprocess.Popen,
        sleep: Callable[[float], None] = time.sleep,
    ):
        self.argv = list(argv)
        self.out_dir = str(out_dir)
        self.config = config or SupervisorConfig()
        self.env = dict(os.environ if env is None else env)
        self.report_path = os.path.join(self.out_dir, "supervisor_report.json")
        self.heartbeat_path = os.path.join(self.out_dir, "heartbeat.json")
        # the child's first heartbeat write must not race directory creation
        os.makedirs(self.out_dir, exist_ok=True)
        self._popen = popen
        self._sleep = sleep
        self._metric_sink = metric_sink
        self.run_id = f"{int(time.time()):x}-{os.getpid():x}"
        self._episode_t0s: list[float] = []
        self.report: dict[str, Any] = {
            "version": SUPERVISOR_REPORT_VERSION,
            "argv": self.argv,
            "run_id": self.run_id,
            "started": round(time.time(), 3),
            "status": "running",
            "restarts": 0,
            "max_restarts": int(self.config.max_restarts),
            "episodes": [],
        }
        from automodel_tpu.observability.events import TraceTimeline

        self.timeline = TraceTimeline(
            os.path.join(self.out_dir, "supervisor_timeline.json"))

    # -- episode ------------------------------------------------------------
    def _run_episode(self, index: int) -> dict[str, Any]:
        cfg = self.config
        try:
            os.unlink(self.heartbeat_path)
        except OSError:
            pass
        env = dict(self.env)
        env[HEARTBEAT_ENV] = self.heartbeat_path
        env[EPISODE_ENV] = json.dumps({"index": index, "run_id": self.run_id})
        started = time.time()
        t0 = self.timeline.now()
        self._episode_t0s.append(t0)
        child = self._popen(self.argv, env=env, stderr=subprocess.PIPE,
                            text=True)
        tee = _StderrTee(child.stderr, cfg.stderr_tail_lines)
        tee.start()
        hang = False
        last_beat: dict[str, Any] | None = None
        last_progress = time.time()
        while True:
            rc = child.poll()
            if rc is not None:
                break
            beat = read_heartbeat(self.heartbeat_path)
            if beat is not None and beat != last_beat:
                last_beat = beat
                last_progress = time.time()
            # hang detection arms only once the child has beaten at least once:
            # silence from a process that never promised heartbeats is not a hang
            if last_beat is not None and \
                    time.time() - last_progress > cfg.hang_timeout_s:
                hang = True
                logger.warning(
                    "supervisor: no heartbeat for %.0fs (last step %s); "
                    "SIGABRT -> SIGKILL", time.time() - last_progress,
                    last_beat.get("step"))
                self._kill(child)
                rc = child.returncode
                break
            self._sleep(cfg.poll_interval_s)
        tee.join(timeout=5.0)
        duration = time.time() - started
        episode: dict[str, Any] = {
            "index": index,
            "returncode": rc,
            "started": round(started, 3),
            "duration_s": round(duration, 3),
            "hang": hang,
            "heartbeat_step": (last_beat or {}).get("step"),
            "stderr_tail": tee.text()[-8000:],
        }
        if rc != 0 or hang:
            verdict = classify_failure(
                returncode=rc, stderr_tail=episode["stderr_tail"],
                out_dir=self.out_dir, hang=hang, since=started)
            episode.update(verdict)
            dump = self._newest_stall_dump(started)
            if dump:
                episode["stall_dump"] = dump
        self.timeline.complete(
            f"supervisor/episode_{index}", "supervisor", t0,
            self.timeline.now() - t0, returncode=rc,
            taxonomy=episode.get("taxonomy"), hang=hang,
            heartbeat_step=episode["heartbeat_step"])
        return episode

    def _kill(self, child: Any) -> None:
        """SIGABRT (forensics), grace, SIGKILL — then reap."""
        for sig, wait_s in ((signal.SIGABRT, self.config.grace_s),
                            (signal.SIGKILL, 30.0)):
            try:
                child.send_signal(sig)
            except (ProcessLookupError, OSError):
                pass
            try:
                child.wait(timeout=wait_s)
                return
            except subprocess.TimeoutExpired:
                continue

    def _newest_stall_dump(self, since: float) -> str | None:
        """The stall watchdog's stack dump from THIS episode, if it fired."""
        dumps = [p for p in glob.glob(os.path.join(self.out_dir, "stall_*.txt"))
                 if _fresh(p, since)]
        return max(dumps, key=os.path.getmtime) if dumps else None

    def _emit(self, row: dict[str, Any]) -> None:
        if self._metric_sink is not None:
            self._metric_sink(row)
            return
        try:
            os.makedirs(self.out_dir, exist_ok=True)
            with open(os.path.join(self.out_dir, "supervisor.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
        except OSError:
            logger.debug("supervisor metric row write failed", exc_info=True)

    def _write_report(self) -> None:
        _atomic_write_json(self.report_path, self.report)

    def _update_ledger(self, final: bool = False) -> None:
        """Rebuild ``run_ledger.json`` from the artifacts on disk (after every
        episode and at terminal states) and emit its flat ``ledger/*`` row.
        Badput timeline spans land only once, at the terminal update, so the
        trace carries one consolidated lane. Ledger failure never takes the
        supervisor down — accounting is forensics, not control flow."""
        try:
            from automodel_tpu.observability import runledger

            ledger = runledger.update_run_ledger(self.out_dir,
                                                 report=self.report)
            if ledger is None:
                return
            self._emit(runledger.ledger_metric_rows(ledger))
            if final:
                runledger.emit_timeline_spans(ledger, self.timeline,
                                              self._episode_t0s)
        except Exception:
            logger.debug("run ledger update failed", exc_info=True)

    # -- run loop -----------------------------------------------------------
    def run(self) -> int:
        """Supervise until the child exits 0, or the restart budget is spent.

        Returns the final child exit status (0 on success; the last failing
        status — or 1 — on structured abort)."""
        cfg = self.config
        restarts = 0
        while True:
            episode = self._run_episode(len(self.report["episodes"]))
            self.report["episodes"].append(episode)
            self.report["restarts"] = restarts
            row = {
                "supervisor/episode": episode["index"],
                "supervisor/returncode": episode["returncode"],
                "supervisor/restarts": restarts,
            }
            if episode.get("taxonomy"):
                row["supervisor/taxonomy"] = episode["taxonomy"]
            if episode["returncode"] == 0 and not episode["hang"]:
                self.report["status"] = "completed"
                self._write_report()
                # ledger row first: the episode row stays the stream's last
                # line, which is what log tails (and tests) key off
                self._update_ledger(final=True)
                self._emit(row)
                self.timeline.close()
                return 0
            if restarts >= cfg.max_restarts:
                # structured abort: budget spent, the report says why each
                # attempt died — the caller gets a status, not a stacktrace
                self.report["status"] = "aborted"
                self.report["abort_reason"] = (
                    f"restart budget exhausted after {restarts} restarts; "
                    f"last failure: {episode.get('taxonomy', 'unknown')}")
                self._write_report()
                self._update_ledger(final=True)
                self._emit(row)
                self.timeline.close()
                logger.error("supervisor: %s", self.report["abort_reason"])
                return episode["returncode"] or 1
            restarts += 1
            delay = cfg.backoff.delay(restarts - 1)
            row["supervisor/restart_delay_s"] = round(delay, 3)
            self._emit(row)
            self.report["status"] = "restarting"
            self._write_report()
            self._update_ledger()
            self.timeline.instant(
                f"supervisor/restart_{restarts}", "supervisor",
                taxonomy=episode.get("taxonomy"), delay_s=round(delay, 3))
            logger.warning(
                "supervisor: episode %d failed (%s); restart %d/%d in %.1fs",
                episode["index"], episode.get("taxonomy", "unknown"),
                restarts, cfg.max_restarts, delay)
            self._sleep(delay)
