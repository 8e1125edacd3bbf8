"""The schedule's host form (``optim/scheduler.build_lr_schedule``): a Python or numpy
integer step gets the traced form's float32 formula from numpy and a host number back,
so the log row's ``lr`` costs no device program; a traced step is served as ever."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from automodel_tpu.optim.scheduler import OptimizerParamScheduler, build_lr_schedule

_WARM, _DECAY = 10, 100
# 0, 1, warm-up - 1, warm-up, mid-decay, the last step and beyond
_STEPS = (0, 1, _WARM - 1, _WARM, (_WARM + _DECAY) // 2, _DECAY - 1, _DECAY, _DECAY + 1, 3 * _DECAY)
_SHAPES = {
    "plain": dict(),
    "warmup": dict(lr_warmup_steps=_WARM),
    "warmup-min_lr": dict(lr_warmup_steps=_WARM, min_lr=1e-5),
    "warmup-min_lr-init_lr": dict(lr_warmup_steps=_WARM, min_lr=1e-5, init_lr=3e-6),
}


def _ulp(x) -> np.float32:
    return np.spacing(np.abs(np.float32(x)))


@pytest.mark.parametrize("shape", list(_SHAPES))
@pytest.mark.parametrize("style", ["cosine", "linear", "constant"])
def test_host_value_equals_the_traced_forms_to_an_ulp(style, shape):
    schedule = build_lr_schedule(3e-4, lr_decay_steps=_DECAY, lr_decay_style=style,
                                 **_SHAPES[shape])
    for step in _STEPS:
        for host_step in (step, np.int64(step), np.int32(step)):
            host = schedule(host_step)
            assert isinstance(host, np.float32) and not isinstance(host, jax.Array)
            device = np.float32(schedule(jnp.int32(step)))
            assert abs(host - device) <= _ulp(device), (step, host, device)


@pytest.mark.parametrize("style", ["cosine", "linear", "constant"])
def test_every_step_of_a_run_is_within_an_ulp_of_the_peak(style):
    """Between the points above the two cosines may differ in their last bit, which
    `0.5 * (1 + cos)` carries into the coefficient whole: an ulp of the peak rate, and
    more than an ulp of a rate that has decayed far below it."""
    schedule = build_lr_schedule(1e-2, min_lr=1e-4, init_lr=1e-6, lr_warmup_steps=7,
                                 lr_decay_steps=137, lr_decay_style=style)
    traced = np.asarray(jax.jit(jax.vmap(schedule))(jnp.arange(200, dtype=jnp.int32)))
    host = np.asarray([schedule(step) for step in range(200)])
    assert host.dtype == np.float32
    assert np.max(np.abs(host - traced)) <= _ulp(1e-2)


def test_no_decay_horizon_holds_the_peak_after_warmup():
    schedule = build_lr_schedule(1e-3, lr_warmup_steps=4)
    assert [float(schedule(s)) for s in (0, 2, 4, 1000)] == [
        float(schedule(jnp.int32(s))) for s in (0, 2, 4, 1000)]
    assert schedule(1000) == np.float32(1e-3)


def test_the_stateful_wrapper_reads_the_host_form():
    sched = OptimizerParamScheduler(1e-3, lr_warmup_steps=5, lr_decay_steps=50)
    sched.step_to(20)
    assert isinstance(sched.schedule(sched.step), np.float32)
    assert sched.lr == pytest.approx(float(sched.schedule(jnp.int32(20))), rel=1e-6)


def test_a_traced_step_still_compiles_into_the_optimizer():
    schedule = build_lr_schedule(1e-2, min_lr=1e-4, lr_warmup_steps=2, lr_decay_steps=10)
    opt = optax.adamw(schedule)
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = opt.init(params)

    @jax.jit
    def step(params, state):
        updates, state = opt.update({"w": jnp.ones((4,), jnp.float32)}, state, params)
        return optax.apply_updates(params, updates), state

    moved = []
    for _ in range(4):
        before = params["w"]
        params, state = step(params, state)
        moved.append(float(jnp.abs(params["w"] - before).max()))
    # the optimizer's count is a traced scalar: warm-up from 0 shows in the updates
    assert moved[0] == 0.0 and moved[1] < moved[2]
    assert isinstance(schedule(jnp.int32(3)), jax.Array)
    lowered = jax.jit(schedule).lower(jnp.int32(3)).as_text()
    assert "cosine" in lowered
