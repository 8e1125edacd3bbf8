"""One record per compile request (observability/compile_cache.py): what JAX's
monitoring reports of every function it lowers and hands to the backend, kept by name
in order of arrival, with the persistent cache's verdict."""

import logging
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from automodel_tpu.observability import compile_cache


@pytest.fixture
def cache_dir(tmp_path):
    """The persistent cache switched on in a directory of the test's own, both floors
    0 so that toy compiles are written (tests/conftest.py keeps it off otherwise)."""
    from jax._src import compilation_cache as jax_cache

    opts = {"jax_enable_compilation_cache": True,
            "jax_compilation_cache_dir": str(tmp_path / "xla_cache"),
            "jax_persistent_cache_min_entry_size_bytes": 0,
            "jax_persistent_cache_min_compile_time_secs": 0}
    old = {o: getattr(jax.config, o) for o in opts}
    for o, v in opts.items():
        jax.config.update(o, v)
    jax_cache.reset_cache()  # the directory is read once, at the first use
    compile_cache.install()
    compile_cache._tap_keys()  # as `configure` does for a run
    compile_cache.reset()
    yield tmp_path / "xla_cache"
    for o, v in old.items():
        jax.config.update(o, v)
    jax_cache.reset_cache()
    compile_cache.reset()
    _untap()


def _untap():
    """Leave ``jax._src.compiler`` as an importer of the package finds it."""
    compiler_log = logging.getLogger("jax._src.compiler")
    for f in [f for f in compiler_log.filters if isinstance(f, compile_cache._KeyTap)]:
        compiler_log.removeFilter(f)
        compiler_log.setLevel(logging.NOTSET)


def _records(name):
    return [r for r in compile_cache.requests() if r["fun_name"] == name]


def test_a_fresh_function_misses_and_its_twin_hits(cache_dir):
    def fresh_fn_of_this_test(x):
        return jnp.tanh(x) * 3 + 1

    jax.jit(fresh_fn_of_this_test)(jnp.arange(8.0))
    (first,) = _records("fresh_fn_of_this_test")
    assert first["cache"] == "miss" and first["retrieval_s"] is None
    for field in ("trace_s", "lower_s", "backend_s"):
        assert first[field] > 0
    assert first["start"] < first["end"]
    assert any(cache_dir.iterdir())  # the miss was written

    jax.clear_caches()  # the in-memory caches go, the directory stays: a second process
    jax.jit(fresh_fn_of_this_test)(jnp.arange(8.0))
    first, twin = _records("fresh_fn_of_this_test")
    assert twin["cache"] == "hit" and twin["retrieval_s"] > 0
    assert twin["start"] >= first["end"]  # in order of arrival
    # the key the cache was asked for, off the jax._src.compiler logger: the same twice
    assert twin["key"] == first["key"] and "fresh_fn_of_this_test" in twin["key"]

    totals = compile_cache.totals()
    assert totals["missed"].count("fresh_fn_of_this_test") == 1  # the first alone
    assert totals["compile_requests"] == len(
        [r for r in compile_cache.requests() if r["backend_s"] is not None])
    assert totals["cache_retrieval_s"] > 0
    counts = compile_cache.counts()
    assert counts["hits"] >= 1 and counts["misses"] >= 1


def test_a_nested_jit_traces_inside_its_caller_and_makes_no_request(cache_dir):
    @jax.jit
    def nested_inner_of_this_test(x):
        return x * 2

    def outer_of_this_test(x):
        return nested_inner_of_this_test(x) + 1

    jax.jit(outer_of_this_test)(jnp.arange(4.0))
    (outer,) = _records("outer_of_this_test")
    assert outer["trace_s"] > 0 and outer["cache"] == "miss"
    assert not _records("nested_inner_of_this_test")  # compiled as part of its caller


def test_lower_and_compile_apart_make_one_record(cache_dir):
    def aot_fn_of_this_test(x):
        return x - 3

    lowered = jax.jit(aot_fn_of_this_test).lower(jnp.arange(4.0))
    (record,) = _records("aot_fn_of_this_test")
    assert record["lower_s"] > 0 and record["backend_s"] is None
    assert record["cache"] == "not_asked"  # nothing asked yet
    three = jnp.arange(3.0)
    before = compile_cache.totals()["compile_requests"]
    jax.jit(lambda x: x + 7)(three)  # another request in between
    lowered.compile()
    (record,) = _records("aot_fn_of_this_test")
    assert record["backend_s"] > 0 and record["cache"] == "miss"
    assert compile_cache.totals()["compile_requests"] == before + 2


def test_with_the_cache_off_a_request_is_not_asked():
    compile_cache.install()
    compile_cache.reset()

    def uncached_fn_of_this_test(x):
        return x / 5

    jax.jit(uncached_fn_of_this_test)(jnp.arange(4.0))
    (record,) = _records("uncached_fn_of_this_test")
    assert record["cache"] == "not_asked" and record["key"] is None
    assert compile_cache.totals()["missed"] == []
    compile_cache.reset()


@pytest.mark.parametrize("event", sorted(compile_cache._PHASES))
def test_a_call_without_fun_name_is_kept_as_not_reported(event):
    compile_cache.reset()
    compile_cache._time_span_listener(event, 10.0, 10.5)  # as an older JAX calls it
    compile_cache._duration_listener(event, 0.5)
    compile_cache._listener("/jax/compilation_cache/cache_hits")
    compile_cache._duration_listener("/some/event/nobody/knows", 1.0, odd_keyword=object())
    records = compile_cache.requests()
    if compile_cache._PHASES[event] == "trace_s":
        assert records == []  # a trace alone is no request
    else:
        (record,) = records
        assert record["fun_name"] == compile_cache.NOT_REPORTED
        assert record[compile_cache._PHASES[event]] == pytest.approx(0.5)
    compile_cache.reset()


def test_importing_the_package_leaves_jaxs_logger_alone():
    """The tap comes with `configure`, a run's own call: an importer (a test, a tool)
    finds ``jax._src.compiler`` at no level of its own and with no filter on it. In a
    process of its own: an earlier test of this one may have started a recipe."""
    code = ("import logging; from automodel_tpu.observability import compile_cache; "
            "log = logging.getLogger('jax._src.compiler'); "
            "assert compile_cache.install() is True; "
            "assert log.level == logging.NOTSET and not log.filters, (log.level, log.filters)")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"}, timeout=120)
    assert done.returncode == 0, done.stderr[-2000:]


def test_the_key_tap_passes_what_the_logger_passed_before_and_yields_to_a_later_owner():
    compiler_log = logging.getLogger("jax._src.compiler")
    compile_cache._tap_keys()
    (tap,) = [f for f in compiler_log.filters if isinstance(f, compile_cache._KeyTap)]
    make = lambda level: logging.LogRecord(  # noqa: E731
        "jax._src.compiler", level, __file__, 1,
        "Persistent compilation cache hit for '%s' with key %r", ("jit_f", "jit_f-abc"), None)
    try:
        floor = compiler_log.parent.getEffectiveLevel()
        assert tap.filter(make(logging.CRITICAL)) is True
        assert tap.filter(make(logging.DEBUG)) is (logging.DEBUG >= floor)
        assert compile_cache._pending.cache.pop("key") == "jit_f-abc"
        # somebody opens the logger up for themselves afterwards, as
        # `jax_debug_log_modules` does (a handler and DEBUG): their lines pass
        handler = logging.NullHandler()
        compiler_log.addHandler(handler)
        assert tap.filter(make(logging.DEBUG)) is True
        compiler_log.removeHandler(handler)
        compiler_log.setLevel(logging.INFO)  # or a level of their own
        assert tap.filter(make(logging.INFO)) is True
        assert compile_cache._pending.cache.pop("key") == "jit_f-abc"  # the key is still read
    finally:
        compiler_log.setLevel(logging.DEBUG)
        _untap()
        assert compiler_log.level == logging.NOTSET and not compiler_log.filters
