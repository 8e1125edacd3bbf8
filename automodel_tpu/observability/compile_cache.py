"""Persistent XLA compile-cache hit/miss accounting for the run_header.

A 1024-chip restart that recompiles every step shape burns minutes of fleet
time the persistent compilation cache exists to save — but jax only reports
cache traffic through its internal monitoring events, so nothing in the run
artifacts says whether the cache is working. This module registers one
process-wide listener for ``/jax/compilation_cache/cache_hits`` /
``cache_misses`` (installed at observability package import, before the
recipe's model-init compiles) and exposes the tallies plus the
persistent-cache configuration for the MetricLogger ``run_header`` row.

The counts keep accumulating after the header is written; the run-total view
lands in the ``compile_summary`` event row at teardown
(:meth:`automodel_tpu.observability.manager.Observability.compile_summary`).

Everything degrades to zeros/False when the jax-internal monitoring API moves
— reporting must never take the run down.
"""

from __future__ import annotations

import logging
import os
import threading

logger = logging.getLogger(__name__)

__all__ = ["configure", "default_dir", "install", "counts", "reset", "snapshot"]

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
    # pre-0.4.30 spelling of a miss
    "/jax/compilation_cache/cache_misses_because_no_entry": "misses",
}
_counts = {"hits": 0, "misses": 0}
_lock = threading.Lock()
_installed = False


def _listener(event: str, **_kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        with _lock:
            _counts[key] += 1


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
    logger.info("persistent compilation cache at %s (%s)",
                applied["dir"], applied["dir_from"])
    return applied


def counts() -> dict[str, int]:
    """Hit/miss tallies since install (or zeros if never installed)."""
    with _lock:
        return dict(_counts)


def reset() -> None:
    """Zero the tallies (tests only — the listener stays registered)."""
    with _lock:
        for k in _counts:
            _counts[k] = 0


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
