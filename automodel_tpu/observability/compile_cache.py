"""Persistent XLA compile-cache accounting, and one record per compile request.

A 1024-chip restart that recompiles every step shape burns minutes of fleet
time the persistent compilation cache exists to save — but jax only reports
cache traffic through its internal monitoring events, so nothing in the run
artifacts says whether the cache is working. This module registers the
process's ONE set of ``jax.monitoring`` listeners (installed at observability
package import, before the recipe's model-init compiles; the tap that reads
cache keys off JAX's logger comes with :func:`configure`, a run's own call):

- the event listener counts ``/jax/compilation_cache/cache_hits`` /
  ``cache_misses`` for the MetricLogger ``run_header`` row, as it always did;
- the duration and time-span listeners keep one record per compile request
  (:func:`requests`): what JAX reports for every function it lowers and hands
  to the backend — ``trace_s`` (``jaxpr_trace_duration``), ``lower_s``
  (``jaxpr_to_mlir_module_duration``), ``backend_s``
  (``backend_compile_duration``: the cache lookup and the executable's load,
  or XLA), whether the persistent cache ``hit``, ``miss``ed or was
  ``not_asked``, ``retrieval_s`` on a hit, the wall-clock ``start``/``end`` and,
  where the ``jax._src.compiler`` logger names it, the cache ``key``.

A request is what reaches the lowering: a jit nested in another traces inside
its caller's trace and is compiled as part of it, so its trace time lies
inside the caller's ``trace_s`` as JAX reports it (nothing is added or taken
away) and it gets no record of its own.

The counts keep accumulating after the header is written; the run-total view
lands in the ``compile_summary`` event row at teardown and the start's view in
the ``setup_summary`` row
(:meth:`automodel_tpu.observability.manager.Observability.compile_summary`).

Everything degrades to zeros / "not reported" when the jax-internal monitoring
API moves — reporting must never take the run down.
"""

from __future__ import annotations

import logging
import os
import threading
import time

logger = logging.getLogger(__name__)

__all__ = ["configure", "default_dir", "install", "counts", "requests", "seconds", "totals",
           "reset", "snapshot", "NOT_REPORTED"]

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
    # pre-0.4.30 spelling of a miss
    "/jax/compilation_cache/cache_misses_because_no_entry": "misses",
}
# a request that went to the persistent cache at all (no key: the cache is off)
_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
# JAX's three phases of one compile request -> the record's field
_PHASES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "backend_s",
}
NOT_REPORTED = "not reported"  # a listener call that came without ``fun_name``
_MAX_REQUESTS = 4096  # a run compiles tens; the bound is against a recompile loop

_counts = {"hits": 0, "misses": 0}
_requests: list[dict[str, object]] = []
_lock = threading.Lock()
_installed = False
_spans_reported = False  # the time-span listener is registered: it has start and end


class _Pending(threading.local):
    """Per thread (a compile runs on the thread that asked for it): the traces no
    lowering has claimed yet, and the cache traffic of the backend compile under way."""

    def __init__(self):
        self.traces: dict[str, tuple[float, float]] = {}  # fun_name -> (start, seconds)
        self.cache: dict[str, object] = {}


_pending = _Pending()


def _listener(event: str, **_kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _counts[key] += 1
        if key == "hits":
            _pending.cache["hit"] = True
    elif event == _ASKED:
        _pending.cache["asked"] = True


def _inner_name(fun_name: str) -> str:
    """``jit(train_step)`` -> ``train_step``: the lowering and the backend name
    the module, the trace names the function."""
    if fun_name.endswith(")") and "(" in fun_name:
        return fun_name[fun_name.index("(") + 1:-1]
    return fun_name


def _new_record(name: str, start: float) -> dict[str, object]:
    return {"fun_name": name, "trace_s": None, "lower_s": None, "backend_s": None,
            "cache": "not_asked", "retrieval_s": None, "key": None,
            "start": start, "end": start}


def _on_phase(event: str, start: float, seconds: float, fun_name: object) -> None:
    field = _PHASES[event]
    name = _inner_name(fun_name) if isinstance(fun_name, str) and fun_name else NOT_REPORTED
    if field == "trace_s":
        # not a request yet: nested jits report here too, inside their caller,
        # and a lowering traces helpers of its own after the function's trace
        _pending.traces[name] = (start, seconds)
        return
    with _lock:
        record = None
        if field == "backend_s":
            # the newest request of that name still waiting for its backend
            # (``.lower()`` and ``.compile()`` of an AOT step come apart)
            record = next((r for r in reversed(_requests)
                           if r["fun_name"] == name and r["backend_s"] is None), None)
        if record is None:
            record = _new_record(name, start)
            if len(_requests) < _MAX_REQUESTS:
                _requests.append(record)
        record[field], record["end"] = seconds, start + seconds
        if field == "lower_s":
            traces, _pending.traces = _pending.traces, {}
            if name in traces:  # the function's own trace, under its own name
                record["start"], record["trace_s"] = traces[name]
            return
        cache, _pending.cache = _pending.cache, {}
        if cache.get("asked"):
            record["cache"] = "hit" if cache.get("hit") else "miss"
        record["retrieval_s"] = cache.get("retrieval_s")
        record["key"] = cache.get("key")


def _duration_listener(event: str, seconds: float, **kwargs) -> None:
    try:
        if event == _RETRIEVAL:
            _pending.cache["retrieval_s"] = float(seconds)
        elif event in _PHASES and not _spans_reported:
            _on_phase(event, time.time() - float(seconds), float(seconds),
                      kwargs.get("fun_name"))
    except Exception:
        logger.debug("compile record dropped", exc_info=True)


def _time_span_listener(event: str, start: float, end: float, **kwargs) -> None:
    try:
        if event in _PHASES:
            _on_phase(event, float(start), float(end) - float(start), kwargs.get("fun_name"))
    except Exception:
        logger.debug("compile record dropped", exc_info=True)


class _KeyTap(logging.Filter):
    """The cache key of a request, without patching JAX: ``jax._src.compiler``
    logs ``... cache hit for '%s' with key %r`` / ``... CACHE MISS for '%s' with
    key %r`` at DEBUG. The filter reads the key off the record's arguments and
    lets through what the logger would have let through had it not been opened
    up for this: what its parent's level admits. Once somebody else gives the
    logger a level or a handler of its own (``jax_debug_log_modules`` does
    both) theirs rules, and every record that reaches the filter passes."""

    def __init__(self, log: logging.Logger):
        super().__init__()
        self._log = log

    def filter(self, record: logging.LogRecord) -> bool:
        try:
            args = record.args
            if isinstance(args, tuple) and len(args) == 2 and "with key" in str(record.msg):
                _pending.cache["key"] = str(args[1])
        except Exception:
            pass
        if self._log.level != logging.DEBUG or self._log.handlers:
            return True
        parent = self._log.parent
        return parent is None or record.levelno >= parent.getEffectiveLevel()


def _tap_keys() -> None:
    """Open ``jax._src.compiler``'s DEBUG lines up to :class:`_KeyTap`: from
    :func:`configure`, so for a run that places the persistent cache and not
    for every importer. A logger somebody gave a level of its own is left
    alone (no keys then)."""
    compiler_log = logging.getLogger("jax._src.compiler")
    if compiler_log.level != logging.NOTSET or any(
            isinstance(f, _KeyTap) for f in compiler_log.filters):
        return
    compiler_log.addFilter(_KeyTap(compiler_log))
    compiler_log.setLevel(logging.DEBUG)


def install() -> bool:
    """Register the monitoring listener once per process; True if active."""
    global _installed
    if _installed:
        return True
    try:
        from jax._src import monitoring

        monitoring.register_event_listener(_listener)
        _installed = True
    except Exception:
        logger.debug("jax monitoring API unavailable; compile-cache counts "
                     "stay at zero", exc_info=True)
        return _installed
    global _spans_reported
    try:  # each on its own: an older JAX has the first and not the second
        monitoring.register_event_duration_secs_listener(_duration_listener)
        monitoring.register_event_time_span_listener(_time_span_listener)
        _spans_reported = True
    except Exception:
        logger.debug("jax reports no compile durations or time spans", exc_info=True)
    return _installed


_THRESHOLDS = (
    ("min_entry_size_bytes", "jax_persistent_cache_min_entry_size_bytes"),
    ("min_compile_time_secs", "jax_persistent_cache_min_compile_time_secs"),
)


def default_dir() -> str:
    """``<checkout>/.jax_cache``: a fixed path, because the path is part of
    the cache key — a directory named after a pid, a time or a temp name
    never hits."""
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(os.path.dirname(os.path.dirname(here)), ".jax_cache")


def configure(raw: object = None) -> dict[str, object]:
    """Place the persistent compilation cache; the one function every entry
    point (recipes, ``chip_smoke.py``) goes through.

    Must run before the first compile of the process (the recipe calls it at
    the very top of ``setup()``, ahead of jit model init), because entries are
    only written for compiles that happen while the cache is configured.

    Where the cache lives, first match wins:

    1. ``JAX_COMPILATION_CACHE_DIR`` is set: JAX reads it itself; nothing is
       set in code and ``compile_cache.dir`` is ignored — the machine decides.
    2. ``compile_cache.dir`` from the YAML section ``raw``;
    3. :func:`default_dir`.

    .. code-block:: yaml

        compile_cache:
          dir: /data/xla_cache     # optional; see the order above
          min_entry_size_bytes: 0  # thresholds only when the YAML names
          min_compile_time_secs: 0 # them: a default-on cache keeps JAX's own
                                   # floors, so toy compiles are not written

    Returns what was applied; never raises — a run must not die because
    caching could not be set up.
    """
    if hasattr(raw, "to_dict"):
        raw = raw.to_dict()
    d = dict(raw or {})  # type: ignore[arg-type]
    applied: dict[str, object] = {}
    try:
        import jax

        env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if env_dir:
            applied["dir"], applied["dir_from"] = env_dir, "env"
        else:
            cache_dir = str(d.get("dir") or default_dir())
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            applied["dir"] = cache_dir
            applied["dir_from"] = "config" if d.get("dir") else "default"
        for key, opt in _THRESHOLDS:
            if key not in d:
                continue
            # coerce to the flag's current type (int vs float) — read via
            # attribute: config.read() raises for context-managed flags
            current = getattr(jax.config, opt)
            jax.config.update(opt, type(current)(d[key]))
            applied[key] = d[key]
    except Exception:
        logger.warning("persistent compilation cache could not be configured; "
                       "restarts will recompile from scratch", exc_info=True)
        return applied
    install()
    try:
        _tap_keys()
    except Exception:
        logger.debug("cache keys stay unreported", exc_info=True)
    logger.info("persistent compilation cache at %s (%s)",
                applied["dir"], applied["dir_from"])
    return applied


def counts() -> dict[str, int]:
    """Hit/miss tallies since install (or zeros if never installed)."""
    with _lock:
        return dict(_counts)


def requests() -> list[dict[str, object]]:
    """One record per compile request since install, in order of arrival
    (copies; the module docstring says what a record holds)."""
    with _lock:
        return [dict(r) for r in _requests]


def seconds(record: dict[str, object]) -> float:
    """What JAX reports of one request: trace + lowering + backend."""
    return sum(float(record.get(k) or 0.0) for k in ("trace_s", "lower_s", "backend_s"))


def totals() -> dict[str, object]:
    """The records summed: the ``compile_summary`` row's half of this module.
    ``missed`` names the requests the cache was asked for and did not have (at
    most 20)."""
    records = requests()
    compiled = [r for r in records if r["backend_s"] is not None]

    def total(field: str) -> float:
        return round(sum(float(r[field] or 0.0) for r in records), 3)

    return {
        "compile_requests": len(compiled),
        "compile_trace_s": total("trace_s"),
        "compile_lower_s": total("lower_s"),
        "compile_backend_s": total("backend_s"),
        "cache_retrieval_s": total("retrieval_s"),
        "missed": [str(r["fun_name"]) for r in compiled if r["cache"] == "miss"][:20],
    }


def reset() -> None:
    """Zero the tallies and forget the records (tests only — the listeners
    stay registered)."""
    with _lock:
        for k in _counts:
            _counts[k] = 0
        del _requests[:]
    _pending.__init__()


def snapshot() -> dict[str, object]:
    """run_header-ready view: cache config + traffic seen so far.

    Written at setup time, so the counts cover model-init / eval-shape
    compiles only; the run totals come from ``compile_summary`` at teardown.
    """
    out: dict[str, object] = {"listener": _installed, **counts()}
    try:
        from jax._src import compilation_cache

        out["persistent_enabled"] = bool(
            compilation_cache.is_persistent_cache_enabled())
    except Exception:
        out["persistent_enabled"] = False
    try:
        import jax

        cache_dir = jax.config.jax_compilation_cache_dir
        if cache_dir:
            out["dir"] = str(cache_dir)
    except Exception:
        logger.debug("compilation cache dir unreadable", exc_info=True)
    return out
