"""Test bootstrap: simulate an 8-device mesh on CPU.

The reference simulates a cluster by forking processes over loopback
(SURVEY.md §4.2, train_dist.py:138-147).  Our analog is
``--xla_force_host_platform_device_count=8``: eight XLA CPU devices in one
process, meshed exactly like TPU chips.  The flag must land before JAX
initializes its backends, hence this top-of-conftest env mutation.

Tests force the CPU: the platform is pinned here before any backend
initializes, and exported so every subprocess a test spawns (demos,
benchmarks, `tpu_dist.run` children) inherits the same request.  The chip
is reached through ``chip_smoke.py``, never through pytest.
"""

import os

os.environ.setdefault("TPU_DIST_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = "cpu"

from tpu_dist.utils.platform import pin_cpu  # noqa: E402

pin_cpu(8)

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected >=8 simulated CPU devices, got {len(devs)}"
    return devs


def spmd_run(fn, *args, world=8):
    """Shared helper: run rank-style fn on the simulated CPU mesh."""
    from tpu_dist import comm

    return comm.spmd(fn, *args, world=world, platform="cpu")


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """The Pallas interpreter in `ops.kernel_for_platform`'s place: what
    a program lowered for the TPU computes where the selection picks the
    kernel, run here.  (The selection itself never interprets: off the
    TPU it takes the caller's plain form.)"""
    from tpu_dist import ops
    from tpu_dist.nn import attention

    monkeypatch.setattr(
        ops, "kernel_for_platform",
        lambda kernel, plain, *operands: kernel(*operands, interpret=True),
    )
    # what was traced under the other rule is not this test's, nor the next's
    attention._per_device_attention.cache_clear()
    yield
    attention._per_device_attention.cache_clear()
