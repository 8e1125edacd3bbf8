"""Per-compile HBM attribution + an analytic fit-before-run memory plan.

The compute/comms pillar (:mod:`hlo_costs`) explains every second of step
time; this module is its memory twin — it explains every byte of HBM, twice:

1. **Analytically, before any compile.** Params and optimizer state exist as
   sharded arrays the moment setup finishes, so their exact per-shard bytes
   are known; the batch stack's bytes follow from the config, and the live
   activation working set is estimated from the model dims (one microbatch is
   live at a time under the scan-based grad accumulation). The resulting
   :class:`MemoryPlan` carries a ``hbm_headroom_gib`` / ``fits`` verdict
   usable *before execution* — the fit-before-run primitive that deciding
   whether a resharded checkpoint fits a new mesh shape needs (ROADMAP #3).
   The plan's flat ``mem_plan/*`` keys ride the run_header.

2. **Exactly, at the first compile.** ``Compiled.memory_analysis()`` reports
   XLA's own argument/output/temp/generated-code byte totals for the
   per-device program. :func:`compiled_memory_attribution` flattens those
   into ``mem/*`` keys for the ``compile_costs`` event row, and
   :func:`reconcile` checks the analytic argument total against XLA's within
   a documented tolerance (:data:`RECON_TOLERANCE`) — if the analytic model
   drifts from what the compiler actually allocates, the reconciliation row
   says so before an OOM does.

Reconciliation contract: the *argument* bytes are compared (params +
optimizer state + batch stack — all concrete, exactly sharded inputs). The
activation estimate is deliberately NOT gated against ``temp_size``:
temporaries also hold fusion workspace and collective buffers, so the plan
reports the ratio (``mem_plan/act_vs_temp``) as a diagnostic instead of
pretending the coarse model is exact. Arguments reconcile within
``RECON_TOLERANCE`` (10%) on real programs; padding and replicated small
leaves account for the slack.

Per-chip HBM capacity resolves in priority order: explicit override
(``observability.memory.hbm_limit_gib`` — also how CPU tests exercise the
verdict) > the runtime's ``memory_stats()['bytes_limit']`` > the
:class:`~automodel_tpu.observability.hlo_costs.DeviceSpec` capacity table >
unknown (``None``: headroom/fits keys stay absent rather than guessing).
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any

logger = logging.getLogger(__name__)

__all__ = [
    "ACTIVATION_BYTES_PER_TOKEN_LAYER",
    "RECON_TOLERANCE",
    "MemoryPlan",
    "tree_shard_bytes",
    "resolve_hbm_limit_bytes",
    "build_memory_plan",
    "compiled_memory_attribution",
    "reconcile",
]

# Live fp32 activation tensors per (token, layer, hidden-unit) during the
# backward of one pre-norm transformer block: attn in/q/k/v/attn-out/post,
# mlp in/gate/up/act/down plus the residual stream — ~14 hidden-sized
# tensors. Remat ladders shrink this; the estimate is a ceiling for the
# default no-remat path and is labeled an estimate everywhere it appears.
ACTIVATION_BYTES_PER_TOKEN_LAYER = 14

# documented reconciliation tolerance: analytic argument bytes vs XLA's
# argument_size_in_bytes (padding + replicated small leaves + host-side
# scalar args account for the slack)
RECON_TOLERANCE = 0.10

_GIB = float(2**30)


def _gib(nbytes: float | int | None) -> float | None:
    # 6 decimals = ~1 KiB resolution: test-sized programs (a few KiB of
    # arguments) must not round to an indistinguishable 0.0
    return None if nbytes is None else round(float(nbytes) / _GIB, 6)


def _leaf_shard_bytes(leaf: Any) -> int:
    """Per-device bytes of one array(-like): the shard shape when sharded,
    the full shape otherwise. Works for concrete jax.Arrays and abstract
    ShapeDtypeStructs alike — only shape/dtype/sharding are touched."""
    import numpy as np

    shape = getattr(leaf, "shape", None)
    if shape is None:
        return 0
    try:
        itemsize = np.dtype(leaf.dtype).itemsize
    except Exception:
        return 0
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shape = sharding.shard_shape(tuple(shape))
        except Exception:
            pass  # unsupported sharding kind: count the full (replicated) size
    return int(math.prod(shape)) * itemsize


def tree_shard_bytes(tree: Any) -> int:
    """Sum of per-device bytes over every array leaf of a pytree."""
    import jax

    return sum(_leaf_shard_bytes(leaf) for leaf in jax.tree.leaves(tree))


@dataclasses.dataclass
class MemoryPlan:
    """The analytic per-device HBM budget, in bytes (GiB only at the edges)."""

    params_bytes: int
    opt_bytes: int
    batch_bytes: int
    act_est_bytes: int
    hbm_limit_bytes: int | None = None
    # filled in at the first compile from memory_analysis(); None until then
    measured_peak_bytes: int | None = None

    @property
    def total_bytes(self) -> int:
        return self.params_bytes + self.opt_bytes + self.batch_bytes + self.act_est_bytes

    @property
    def headroom_bytes(self) -> int | None:
        if self.hbm_limit_bytes is None:
            return None
        # once XLA has spoken, its peak beats the analytic estimate
        used = self.measured_peak_bytes if self.measured_peak_bytes is not None else self.total_bytes
        return self.hbm_limit_bytes - used

    @property
    def fits(self) -> bool | None:
        head = self.headroom_bytes
        return None if head is None else head >= 0

    def header_row(self) -> dict[str, Any]:
        """Flat ``mem_plan/*`` keys for the run_header (and the OOM report)."""
        out: dict[str, Any] = {
            "mem_plan/params_gib": _gib(self.params_bytes),
            "mem_plan/opt_gib": _gib(self.opt_bytes),
            "mem_plan/batch_gib": _gib(self.batch_bytes),
            "mem_plan/act_est_gib": _gib(self.act_est_bytes),
            "mem_plan/total_gib": _gib(self.total_bytes),
        }
        if self.hbm_limit_bytes is not None:
            out["mem_plan/hbm_limit_gib"] = _gib(self.hbm_limit_bytes)
            out["mem_plan/hbm_headroom_gib"] = _gib(self.headroom_bytes)
            out["mem_plan/fits"] = self.fits
        return out


def resolve_hbm_limit_bytes(override_gib: float | None = None,
                            devices: Any = None) -> int | None:
    """Per-chip HBM capacity; None when genuinely unknown (CPU, no override)."""
    if override_gib is not None:
        return int(float(override_gib) * _GIB)
    import jax

    devs = list(devices) if devices is not None else jax.local_devices()
    limits: list[int] = []
    for d in devs:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats and stats.get("bytes_limit"):
            limits.append(int(stats["bytes_limit"]))
    if limits:
        return min(limits)  # the tightest chip is the one that OOMs first
    if devs and getattr(devs[0], "platform", None) == "tpu":
        from automodel_tpu.observability.hlo_costs import device_specs

        spec = device_specs(devs[0].device_kind)
        if spec is not None and spec.hbm_gib:
            return int(spec.hbm_gib * _GIB)
    return None


def _text_config(model_config: Any) -> Any:
    """The text-stack dims (VLM configs nest them under ``.text``)."""
    if model_config is None:
        return None
    return getattr(model_config, "text", model_config)


def build_memory_plan(
    params: Any,
    opt_state: Any,
    *,
    micro_batch_size: int,
    seq_len: int,
    grad_acc_steps: int = 1,
    dp_degree: int = 1,
    batch_streams: int = 4,
    model_config: Any = None,
    activation_itemsize: int = 4,
    hbm_limit_override_gib: float | None = None,
    devices: Any = None,
) -> MemoryPlan:
    """Analytic per-device plan from the concrete sharded state + config dims.

    ``batch_streams``: int32 token streams per stack entry (input_ids, labels,
    positions, segment_ids). ``dp_degree`` divides the batch dimension —
    the stack shards over every data axis (dp_replicate, dp_shard, ep).
    Activations assume ONE live microbatch (scan-based grad accumulation
    keeps exactly one in flight); the batch stack itself holds all
    ``grad_acc_steps`` microbatches on device.
    """
    params_bytes = tree_shard_bytes(params)
    opt_bytes = tree_shard_bytes(opt_state)
    shard_batch = max(int(micro_batch_size) // max(int(dp_degree), 1), 1)
    batch_bytes = int(grad_acc_steps) * shard_batch * int(seq_len) * 4 * int(batch_streams)

    act_bytes = 0
    tcfg = _text_config(model_config)
    hidden = getattr(tcfg, "hidden_size", None) if tcfg is not None else None
    layers = getattr(tcfg, "num_hidden_layers", None) if tcfg is not None else None
    if isinstance(tcfg, dict):
        hidden = tcfg.get("hidden_size")
        layers = tcfg.get("num_hidden_layers")
    if hidden and layers:
        tokens_per_shard = shard_batch * int(seq_len)
        act_bytes = (tokens_per_shard * int(hidden) * int(layers)
                     * ACTIVATION_BYTES_PER_TOKEN_LAYER * int(activation_itemsize))

    return MemoryPlan(
        params_bytes=params_bytes,
        opt_bytes=opt_bytes,
        batch_bytes=batch_bytes,
        act_est_bytes=act_bytes,
        hbm_limit_bytes=resolve_hbm_limit_bytes(hbm_limit_override_gib, devices),
    )


def compiled_memory_attribution(compiled: Any) -> dict[str, int] | None:
    """Raw byte totals from ``Compiled.memory_analysis()``, or None.

    ``peak_est`` is the classic XLA accounting identity: arguments + outputs
    + temporaries + generated code − aliased (donated inputs alias outputs,
    so their bytes must not be double-counted).
    """
    try:
        ma = compiled.memory_analysis()
    except Exception:
        logger.debug("memory_analysis unavailable on this backend", exc_info=True)
        return None
    if ma is None:
        return None
    try:
        out = {
            "args": int(ma.argument_size_in_bytes),
            "out": int(ma.output_size_in_bytes),
            "temp": int(ma.temp_size_in_bytes),
            "code": int(ma.generated_code_size_in_bytes),
            "alias": int(ma.alias_size_in_bytes),
        }
    except AttributeError:
        logger.debug("memory_analysis missing expected fields", exc_info=True)
        return None
    out["peak_est"] = out["args"] + out["out"] + out["temp"] + out["code"] - out["alias"]
    return out


def reconcile(plan: MemoryPlan, attribution: dict[str, int]) -> dict[str, Any]:
    """Compare the analytic plan against XLA's measured attribution.

    Returns flat log-row keys: ``mem/*_gib`` (the measured side),
    ``mem_plan/recon_rel_err`` (analytic vs measured *argument* bytes — the
    gated comparison, tolerance :data:`RECON_TOLERANCE`) and
    ``mem_plan/act_vs_temp`` (activation estimate / temp bytes, a diagnostic
    ratio, never gated). Also refines the plan's headroom in place with the
    measured peak.
    """
    row: dict[str, Any] = {
        f"mem/{k}_gib": _gib(v) for k, v in attribution.items()
    }
    analytic_args = plan.params_bytes + plan.opt_bytes + plan.batch_bytes
    measured_args = attribution.get("args", 0)
    if measured_args > 0:
        rel = abs(analytic_args - measured_args) / measured_args
        row["mem_plan/recon_rel_err"] = round(rel, 4)
        if rel > RECON_TOLERANCE:
            logger.warning(
                "memory plan reconciliation off by %.1f%% (analytic args %.3f GiB "
                "vs compiled %.3f GiB) — the analytic model may be stale for "
                "this config", rel * 100, analytic_args / _GIB, measured_args / _GIB)
    temp = attribution.get("temp", 0)
    if temp > 0 and plan.act_est_bytes:
        row["mem_plan/act_vs_temp"] = round(plan.act_est_bytes / temp, 3)
    plan.measured_peak_bytes = attribution.get("peak_est")
    if plan.hbm_limit_bytes is not None:
        row["mem_plan/hbm_headroom_gib"] = _gib(plan.headroom_bytes)
        row["mem_plan/fits"] = plan.fits
    return row
