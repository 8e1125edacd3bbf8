"""Device mesh construction and logical-axis sharding rules.

TPU-native translation of the reference mesh layer
(nemo_automodel/components/distributed/mesh.py:48,121,247 and mesh_utils.py:46,190-228):
one ``jax.sharding.Mesh`` replaces DeviceMesh + all flattened axes — "flattening" is just
``PartitionSpec`` tuples. The reference's moe mesh ``(pp, ep_shard, ep)`` collapses into
the same mesh: the ``ep`` axis is first-class, carved out of the data dims
(world = pp * dp_replicate * dp_shard * ep * cp * tp; data parallel degree is
dp_replicate * dp_shard * ep, matching the reference constraint ``dp*cp % ep == 0``
at mesh_utils.py:181).

Parallelism is expressed through *logical axis names* on every array dimension
(t5x/maxtext-style): a :class:`ShardingRules` table maps logical names to mesh axes, and
models annotate params/activations with logical names only. Changing the parallel layout
means changing the rules table, never the model — the same contract as the reference's
"parallelism is configuration".
"""

from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

logger = logging.getLogger(__name__)

__all__ = [
    "MeshAxis",
    "MeshContext",
    "ShardingRules",
    "create_device_mesh",
    "default_sharding_rules",
]


class MeshAxis:
    """Canonical mesh axis names (reference MeshAxisName, distributed/mesh.py:55)."""

    PP = "pp"
    DP_REPLICATE = "dp_replicate"
    DP_SHARD = "dp_shard"
    EP = "ep"
    CP = "cp"
    TP = "tp"

    ALL = (PP, DP_REPLICATE, DP_SHARD, EP, CP, TP)
    # Data-parallel axes: batch shards over all of these (reference "dp" flatten).
    DATA = (DP_REPLICATE, DP_SHARD, EP)
    # Axes FSDP shards dense params over (reference "dp_shard_cp" flatten).
    FSDP = (DP_SHARD, EP, CP)
    # Axes loss/metrics reduce over (reference "dp_cp" flatten).
    DP_CP = (DP_REPLICATE, DP_SHARD, EP, CP)


@dataclasses.dataclass
class MeshContext:
    """Validated parallelism sizes; builds the single global Mesh.

    ``dp_shard = -1`` infers the remaining world size (reference mesh.py:121).
    """

    pp: int = 1
    dp_replicate: int = 1
    dp_shard: int = -1
    ep: int = 1
    cp: int = 1
    tp: int = 1
    world_size: int | None = None  # default: jax.device_count()
    # set by create_device_mesh: "topology" | "enumeration"
    device_order: str | None = dataclasses.field(default=None, compare=False)

    def __post_init__(self):
        if self.world_size is None:
            self.world_size = jax.device_count()
        for name in ("pp", "dp_replicate", "ep", "cp", "tp"):
            v = getattr(self, name)
            if not isinstance(v, int) or v < 1:
                raise ValueError(f"{name} must be a positive int, got {v!r}")
        fixed = self.pp * self.dp_replicate * self.ep * self.cp * self.tp
        if self.dp_shard == -1:
            if self.world_size % fixed != 0:
                raise ValueError(
                    f"world_size {self.world_size} not divisible by pp*dp_replicate*ep*cp*tp = {fixed}"
                )
            self.dp_shard = self.world_size // fixed
        if self.dp_shard < 1:
            raise ValueError(f"dp_shard must be >= 1, got {self.dp_shard}")
        total = fixed * self.dp_shard
        if total != self.world_size:
            raise ValueError(
                f"mesh sizes pp={self.pp} x dp_replicate={self.dp_replicate} x "
                f"dp_shard={self.dp_shard} x ep={self.ep} x cp={self.cp} x tp={self.tp} "
                f"= {total} != world_size {self.world_size}"
            )

    @property
    def shape(self) -> dict[str, int]:
        return {
            MeshAxis.PP: self.pp,
            MeshAxis.DP_REPLICATE: self.dp_replicate,
            MeshAxis.DP_SHARD: self.dp_shard,
            MeshAxis.EP: self.ep,
            MeshAxis.CP: self.cp,
            MeshAxis.TP: self.tp,
        }

    @property
    def dp_size(self) -> int:
        """Global batch shards over this many ways (reference "dp" flatten)."""
        return self.dp_replicate * self.dp_shard * self.ep

    @property
    def fsdp_size(self) -> int:
        return self.dp_shard * self.ep * self.cp

    @property
    def active_axes(self) -> tuple[str, ...]:
        return tuple(a for a, s in self.shape.items() if s > 1)

    def build_mesh(self, devices: Sequence[Any] | None = None) -> Mesh:
        return create_device_mesh(self, devices)


def create_device_mesh(ctx: MeshContext, devices: Sequence[Any] | None = None) -> Mesh:
    """Build the global ``jax.sharding.Mesh`` (reference mesh_utils.py:46).

    Axis order is outermost (slowest-varying, crosses DCN first) to innermost
    (fastest-varying, stays on ICI): pp, dp_replicate, dp_shard, ep, cp, tp.
    TP innermost keeps its all-reduces on the shortest ICI hops; PP outermost
    tolerates DCN latency (point-to-point, overlappable).
    """
    if devices is None:
        devices = jax.devices()
    devices = list(devices)
    shape = tuple(ctx.shape.values())
    if len(devices) != math.prod(shape):
        raise ValueError(f"got {len(devices)} devices for mesh shape {shape}")
    # ICI/DCN-topology-aware assignment (keeps tp on the shortest torus hops);
    # where no topology info exists (CPU test platform) enumeration order is
    # taken instead — said out loud and kept on the context, because on real
    # chips that order can put a tp pair on the long way round the torus
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
        ctx.device_order = "topology"
    except (ValueError, NotImplementedError, AssertionError) as e:
        dev_array = np.asarray(devices).reshape(shape)
        ctx.device_order = "enumeration"
        logger.warning("mesh %s: no topology-aware device order (%s); using "
                       "enumeration order", shape, e)
    else:
        logger.info("mesh %s: topology-aware device order", shape)
    return Mesh(dev_array, axis_names=tuple(ctx.shape.keys()))


class ShardingRules:
    """Maps logical axis names -> mesh axes; produces PartitionSpecs/NamedShardings.

    The TPU-native replacement for the reference's per-module TP plans
    (distributed/optimized_tp_plans.py:406) and FSDP wrapping policy
    (distributed/parallelizer.py:1003): declarative data instead of module wrappers.
    """

    def __init__(self, rules: dict[str, str | tuple[str, ...] | None], mesh: Mesh | None = None):
        self.rules = dict(rules)
        self.mesh = mesh
        # Validate: no mesh axis may be used by two logical axes in one spec; that is
        # checked per-spec in __call__ since conflicts only matter within one array.
        if mesh is not None:
            for k, v in self.rules.items():
                for ax in _as_tuple(v):
                    if ax not in mesh.axis_names:
                        raise ValueError(f"rule {k!r} -> {v!r}: {ax!r} not a mesh axis {mesh.axis_names}")

    def with_mesh(self, mesh: Mesh) -> "ShardingRules":
        return ShardingRules(self.rules, mesh)

    def updated(self, **overrides: str | tuple[str, ...] | None) -> "ShardingRules":
        rules = dict(self.rules)
        rules.update(overrides)
        return ShardingRules(rules, self.mesh)

    def spec(self, logical_axes: Sequence[str | None] | None) -> PartitionSpec:
        """Translate a tuple of logical axis names to a PartitionSpec."""
        if logical_axes is None:
            return PartitionSpec()
        out: list[Any] = []
        used: set[str] = set()
        for name in logical_axes:
            if name is None:
                out.append(None)
                continue
            mapped = self.rules.get(name)
            axes = tuple(a for a in _as_tuple(mapped) if a not in used)
            used.update(axes)
            if not axes:
                out.append(None)
            elif len(axes) == 1:
                out.append(axes[0])
            else:
                out.append(axes)
        while out and out[-1] is None:
            out.pop()
        return PartitionSpec(*out)

    def sharding(self, logical_axes: Sequence[str | None] | None) -> NamedSharding:
        if self.mesh is None:
            raise ValueError("ShardingRules has no mesh bound; call with_mesh(mesh) first")
        return NamedSharding(self.mesh, self.spec(logical_axes))

    def tree_spec(self, logical_tree: Any) -> Any:
        """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
        return jax.tree.map(
            self.spec, logical_tree, is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))
        )

    def tree_sharding(self, logical_tree: Any) -> Any:
        return jax.tree.map(
            self.sharding, logical_tree, is_leaf=lambda x: x is None or (isinstance(x, tuple) and all(isinstance(e, (str, type(None))) for e in x))
        )


def _as_tuple(v: str | tuple[str, ...] | None) -> tuple[str, ...]:
    if v is None:
        return ()
    if isinstance(v, str):
        return (v,)
    return tuple(v)


def default_sharding_rules(
    *,
    sequence_parallel: bool = True,
    fsdp_over_cp: bool = True,
) -> ShardingRules:
    """Default logical->mesh mapping implementing FSDP(+HSDP) x TP(+SP) x CP x EP.

    Logical axes used by all models in automodel_tpu.models:

    activations:
      ``batch``        per-example dim             -> all data axes
      ``act_seq``      residual-stream sequence dim -> (cp, tp) under SP, else cp
                       (SP = shard LayerNorm/residual activations along seq over tp,
                       reference optimized_tp_plans.py:48-64; XLA inserts the
                       all-gather/reduce-scatter pair that DTensor styles do by hand)
      ``act_attn_seq`` sequence dim inside attention -> cp only
      ``act_embed``    hidden dim of activations   -> None
      ``act_heads``    attention heads             -> tp
    params:
      ``embed``        hidden dim                  -> fsdp axes (ZeRO-3 shard)
      ``vocab``        vocabulary                  -> tp (vocab-parallel embed/head)
      ``mlp``          FFN intermediate            -> tp (colwise/rowwise pair)
      ``heads``        q heads dim                 -> tp
      ``kv_heads``     kv heads dim                -> tp
      ``expert``       expert dim of MoE params    -> ep
      ``expert_mlp``   FFN dim inside experts      -> tp
      ``norm``         rmsnorm scale               -> None (replicated)
    """
    fsdp_axes: tuple[str, ...] = (MeshAxis.DP_SHARD, MeshAxis.EP) + (
        (MeshAxis.CP,) if fsdp_over_cp else ()
    )
    rules: dict[str, str | tuple[str, ...] | None] = {
        # stacked layer dim -> pp: stage slicing is just a sharding (parallel/pipeline.py)
        "layers": MeshAxis.PP,
        # MoE dense-prefix stack: replicated over pp (runs on every stage rank)
        "dense_layers": None,
        "batch": MeshAxis.DATA,
        "act_seq": (MeshAxis.CP, MeshAxis.TP) if sequence_parallel else (MeshAxis.CP,),
        "act_attn_seq": MeshAxis.CP,
        "act_embed": None,
        "act_heads": MeshAxis.TP,
        "act_mlp": MeshAxis.TP,
        "act_vocab": MeshAxis.TP,
        "embed": fsdp_axes,
        "vocab": MeshAxis.TP,
        "mlp": MeshAxis.TP,
        "heads": MeshAxis.TP,
        "kv_heads": MeshAxis.TP,
        "head_dim": None,
        "expert": MeshAxis.EP,
        "expert_embed": fsdp_axes[:1],
        "expert_mlp": MeshAxis.TP,
        "norm": None,
    }
    return ShardingRules(rules)
