"""Test environment: CPU jax with 8 virtual devices, chosen explicitly.

Mirrors the reference's custom_cpu fake-device CI trick (SURVEY.md §4): the
full framework runs against host-CPU XLA with a virtual 8-device mesh so
every parallelism axis (dp/mp/pp/sharding/sep/ep) is exercised without TPU
hardware. ``chip_smoke.py`` is the standing proof of the real-chip path.

``JAX_PLATFORMS=cpu`` is exported into ``os.environ`` (not only set through
``jax.config``) so that every child a test spawns — fleet replicas, launch
workers, smoke tools — inherits the choice: no library code defaults a
platform, and ``paddle_tpu.core.device`` treats the CPU as a stand-in for
the chip only in a process that was put there on purpose.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# x64 available: the OpTest harness needs float64 for finite-difference
# gradient checks (production default dtype is still float32 via creation ops).
os.environ.setdefault("JAX_ENABLE_X64", "1")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)


import pytest  # noqa: E402


@pytest.fixture
def force_tpu(monkeypatch):
    """Make flash-attention selection see a fake TPU backend with an
    importable pallas kernel (the selection tests run on CPU; the real
    kernels are exercised on the chip)."""
    import paddle_tpu.kernels.flash_attention as fa

    class _FakeTpu:
        platform = "tpu"

    from paddle_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(fa.jax, "devices", lambda: [_FakeTpu()])
    monkeypatch.setattr(fa, "_pallas_fa", lambda: object())
    # a mesh an earlier test of this worker left installed would make
    # the selection refuse every compiled kernel (GSPMD cannot partition
    # one); these tests are about the one-chip policy
    monkeypatch.setitem(mesh_mod._STATE, "mesh", None)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow')",
    )
