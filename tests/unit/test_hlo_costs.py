"""HLO cost/roofline accounting (observability/hlo_costs.py): analytic
flop/byte extraction from a compiled executable, the collective-byte parser
(single source of truth — the dryrun's MULTICHIP tables import it), device
peak specs, and the roofline + bound diagnosis math."""

import importlib.util
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from automodel_tpu.observability.hlo_costs import (
    collective_bytes,
    compiled_cost_metrics,
    device_specs,
    diagnose_bound,
    roofline_metrics,
)


def test_graft_entry_uses_this_parser():
    """The dedup contract: __graft_entry__'s _collective_bytes must BE this
    function (not a copy), so MULTICHIP output stays byte-identical."""
    if "__graft_entry__" in sys.modules:
        g = sys.modules["__graft_entry__"]
    else:
        spec = importlib.util.spec_from_file_location("__graft_entry__", "__graft_entry__.py")
        g = importlib.util.module_from_spec(spec)
        sys.modules["__graft_entry__"] = g
        spec.loader.exec_module(g)
    assert g._collective_bytes is collective_bytes


def test_collective_bytes_per_kind():
    hlo = """
  %ag = f32[16,64]{1,0} all-gather(f32[4,64]{1,0} %p0), dimensions={0}
  %ar = bf16[8,128]{1,0} all-reduce(bf16[8,128]{1,0} %x), to_apply=%sum
  %d = f32[128,128]{1,0} dot(f32[128,64] %a, f32[64,128] %b)
"""
    got = collective_bytes(hlo)
    assert got == {"all-gather": 16 * 64 * 4, "all-reduce": 8 * 128 * 2}


class TestCompiledCostMetrics:
    def test_toy_sharded_model_flops_and_comm(self, cpu_devices):
        mesh = Mesh(np.array(cpu_devices).reshape(8), ("dp",))
        x = jax.device_put(jnp.ones((8, 128), jnp.float32),
                           NamedSharding(mesh, P("dp", None)))
        w = jax.device_put(jnp.ones((128, 128), jnp.float32),
                           NamedSharding(mesh, P()))

        @jax.jit
        def f(x, w):
            y = x @ w
            return jax.lax.with_sharding_constraint(y, NamedSharding(mesh, P())).sum()

        compiled = f.lower(x, w).compile()
        costs = compiled_cost_metrics(compiled)
        # 8x128 @ 128x128 = 2*8*128*128 flops; XLA reports per-device or whole-
        # program depending on backend — just pin positivity and presence
        assert costs["hlo_flops"] > 0
        assert costs["hlo_bytes_accessed"] > 0
        # resharding dp->replicated must emit an all-gather
        assert costs["comm_bytes_all_gather"] > 0
        assert costs["comm_bytes_total"] >= costs["comm_bytes_all_gather"]

    def test_unsupported_object_degrades_to_empty(self):
        assert compiled_cost_metrics(object()) == {}


class TestDeviceSpecs:
    def test_known_kinds(self):
        assert device_specs("TPU v5 lite").name == "v5e"
        assert device_specs("TPU v5p").name == "v5p"
        assert device_specs("TPU v4").name == "v4"
        assert device_specs("TPU v6e").name == "v6e"

    def test_unknown_kind_is_an_error_and_cpu_has_no_peak(self):
        """One peak table, no default: a CPU gets no spec (so no roofline and
        no MFU on its rows) and any other unknown kind raises."""
        from automodel_tpu.observability.hlo_costs import UnknownDeviceError

        assert device_specs("cpu") is None
        with pytest.raises(UnknownDeviceError, match="TPU v9 mega"):
            device_specs("TPU v9 mega")


class TestRoofline:
    def _spec(self):
        return device_specs("TPU v5 lite")  # 197 TF, 819 GB/s HBM, 200 GB/s ICI

    def test_compute_bound(self):
        r = roofline_metrics({"hlo_flops": 1e12, "hlo_bytes_accessed": 1e9,
                              "comm_bytes_total": 1e8}, self._spec())
        assert r["roofline_bound"] == "compute"
        assert r["roofline_step_time_s"] == pytest.approx(r["roofline_t_compute_s"])
        assert r["roofline_t_compute_s"] == pytest.approx(1e12 / (197e12))

    def test_memory_bound(self):
        r = roofline_metrics({"hlo_flops": 1e9, "hlo_bytes_accessed": 1e12,
                              "comm_bytes_total": 0}, self._spec())
        assert r["roofline_bound"] == "memory"
        assert r["roofline_t_memory_s"] == pytest.approx(1e12 / 819e9)

    def test_comms_bound(self):
        r = roofline_metrics({"hlo_flops": 0, "hlo_bytes_accessed": 0,
                              "comm_bytes_total": 1e12}, self._spec())
        assert r["roofline_bound"] == "comms"
        assert r["roofline_t_comm_s"] == pytest.approx(1e12 / 200e9)

    def test_empty_costs_no_roofline(self):
        assert roofline_metrics({}, self._spec()) == {}

    def test_diagnose_bound_branches(self):
        r = roofline_metrics({"hlo_flops": 1e12, "hlo_bytes_accessed": 1e9,
                              "comm_bytes_total": 0}, self._spec())
        assert diagnose_bound(0.01, r) == "compute"
        # heavy input wait overrides the HLO-side diagnosis
        assert diagnose_bound(0.01, r, data_wait_frac=0.5) == "input"
        assert diagnose_bound(0.01, r, data_wait_frac=0.5, input_bound_frac=0.6) == "compute"
        assert diagnose_bound(None, r) is None
        assert diagnose_bound(0.01, {}) is None
        assert diagnose_bound(0.01, None) is None
