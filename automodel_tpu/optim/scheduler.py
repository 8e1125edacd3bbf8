"""Megatron-style LR + weight-decay scheduling (reference optim/scheduler.py:14).

``OptimizerParamScheduler`` reproduces the reference semantics — linear warmup from
``init_lr`` to ``max_lr`` over ``lr_warmup_steps``, then cosine/linear/constant decay
to ``min_lr`` over ``lr_decay_steps``, plus an optional weight-decay ramp — but as a
pure function of the step, exposed both as an optax schedule (for inside-jit use) and
as a stateful object with state_dict/load_state_dict (for recipe checkpointing).
"""

from __future__ import annotations

import math
from typing import Callable

import numpy as np

__all__ = ["build_lr_schedule", "OptimizerParamScheduler"]


def build_lr_schedule(
    max_lr: float,
    min_lr: float = 0.0,
    init_lr: float = 0.0,
    lr_warmup_steps: int = 0,
    lr_decay_steps: int | None = None,
    lr_decay_style: str = "cosine",
) -> Callable[[int], float]:
    """Pure step->lr function. A traced or device scalar (the optimizer's count,
    inside jit) is served with ``jax.numpy``, as ever; a Python or numpy integer (the
    log row's step) gets the same float32 formula from numpy and a host ``np.float32``
    back: no device program, no round trip."""
    if lr_decay_style not in ("cosine", "linear", "constant"):
        raise ValueError(f"unknown lr_decay_style {lr_decay_style!r}")

    def host_schedule(step: int) -> np.float32:
        # the lines of `schedule` below, operation for operation, in numpy's float32
        f32 = np.float32
        step = f32(step)
        warm = f32(max(lr_warmup_steps, 1))
        if step < lr_warmup_steps:
            return f32(init_lr) + f32(max_lr - init_lr) * np.minimum(step, warm) / warm
        if lr_decay_style == "constant" or lr_decay_steps is None:
            return f32(max_lr)
        total = f32(max(lr_decay_steps - lr_warmup_steps, 1))
        frac = np.clip((step - f32(lr_warmup_steps)) / total, f32(0.0), f32(1.0))
        if lr_decay_style == "cosine":
            coeff = f32(0.5) * (f32(1.0) + np.cos(f32(math.pi) * frac))
        else:  # linear
            coeff = f32(1.0) - frac
        return f32(min_lr) + f32(max_lr - min_lr) * coeff

    def schedule(step):
        if isinstance(step, (int, np.integer)):
            return host_schedule(step)
        import jax.numpy as jnp

        step = jnp.asarray(step, jnp.float32)
        warm = jnp.float32(max(lr_warmup_steps, 1))
        warmup_lr = init_lr + (max_lr - init_lr) * jnp.minimum(step, warm) / warm
        if lr_decay_style == "constant" or lr_decay_steps is None:
            decayed = jnp.float32(max_lr)
        else:
            total = jnp.float32(max(lr_decay_steps - lr_warmup_steps, 1))
            frac = jnp.clip((step - lr_warmup_steps) / total, 0.0, 1.0)
            if lr_decay_style == "cosine":
                coeff = 0.5 * (1.0 + jnp.cos(math.pi * frac))
            else:  # linear
                coeff = 1.0 - frac
            decayed = min_lr + (max_lr - min_lr) * coeff
        return jnp.where(step < lr_warmup_steps, warmup_lr, decayed)

    return schedule


class OptimizerParamScheduler:
    """Stateful wrapper tracking the current step, lr, and weight decay."""

    def __init__(
        self,
        max_lr: float,
        min_lr: float = 0.0,
        init_lr: float = 0.0,
        lr_warmup_steps: int = 0,
        lr_decay_steps: int | None = None,
        lr_decay_style: str = "cosine",
        start_wd: float | None = None,
        end_wd: float | None = None,
        wd_incr_steps: int | None = None,
        wd_incr_style: str = "constant",
    ):
        self.schedule = build_lr_schedule(
            max_lr, min_lr, init_lr, lr_warmup_steps, lr_decay_steps, lr_decay_style
        )
        self.max_lr, self.min_lr = max_lr, min_lr
        self.start_wd, self.end_wd = start_wd, end_wd
        self.wd_incr_steps, self.wd_incr_style = wd_incr_steps, wd_incr_style
        self.step = 0

    def step_to(self, step: int) -> None:
        self.step = int(step)

    def advance(self) -> None:
        self.step += 1

    @property
    def lr(self) -> float:
        return float(self.schedule(self.step))

    @property
    def wd(self) -> float | None:
        if self.start_wd is None:
            return None
        if self.end_wd is None or not self.wd_incr_steps or self.wd_incr_style == "constant":
            return self.start_wd
        frac = min(max(self.step / self.wd_incr_steps, 0.0), 1.0)
        if self.wd_incr_style == "cosine":
            coeff = 0.5 * (1.0 - math.cos(math.pi * frac))
        else:  # linear
            coeff = frac
        return self.start_wd + (self.end_wd - self.start_wd) * coeff

    def state_dict(self) -> dict:
        return {"step": self.step}

    def load_state_dict(self, state: dict) -> None:
        self.step = int(state["step"])
