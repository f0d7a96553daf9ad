"""Test configuration: force an 8-virtual-device CPU JAX backend.

Mirrors the reference's tier-1/tier-2 test strategy (SURVEY.md §4): unit
tests never need real TPU hardware; multi-chip sharding is exercised on a
virtual CPU mesh via --xla_force_host_platform_device_count.

``JAX_PLATFORMS=cpu`` (what the driver's command sets) selects the CPU;
the ``jax.config.update`` below holds a bare ``pytest tests/`` to it too.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("DLROVER_TPU_SOCKET_DIR", "/tmp/dlrover_tpu_test/sockets")
os.environ["DLROVER_TPU_PLATFORM"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
