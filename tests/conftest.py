"""Test env: force a virtual 8-device CPU platform BEFORE any JAX backend initializes.

This is the TPU-native analogue of the reference's mocked torch.distributed unit tests
(tests/unit_tests/distributed/README.md:44-52) — real SPMD semantics, no hardware.
Backend initialization is lazy, so setting the platform here (before any test touches
a device) lands every test on the 8-device CPU platform.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the persistent compilation cache is on by default for runs (observability/
# compile_cache.py); thousands of toy compiles from several workers must not
# write to it. Tests of the cache turn it on in their own tmp_path.
jax.config.update("jax_enable_compilation_cache", False)
# parity tests compare fp32 logits against torch; XLA:CPU's default (oneDNN) matmul
# path accumulates at reduced precision, which flips near-tied MoE routing decisions
jax.config.update("jax_default_matmul_precision", "float32")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session")
def mesh8(cpu_devices):
    """A (dp_shard=2, cp=2, tp=2) 8-device mesh shared across tests."""
    from automodel_tpu.parallel.mesh import MeshContext

    ctx = MeshContext(dp_shard=2, cp=2, tp=2, world_size=8)
    return ctx.build_mesh(cpu_devices)


@pytest.fixture(scope="module")
def assume_v5e_peaks():
    """A CPU has no peak, so a CPU run's rows carry no roofline, bound or MoE
    span. The tests of that plumbing say out loud which chip they assume —
    here, in the test, not as a default of the program."""
    from automodel_tpu.observability import hlo_costs, manager

    mp = pytest.MonkeyPatch()
    mp.setattr(manager, "device_specs",
               lambda kind: hlo_costs.device_specs("TPU v5 lite"))
    yield
    mp.undo()
