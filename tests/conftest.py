"""Test configuration: run everything on a virtual 8-device CPU platform.

Mirrors the reference's testing approach of exercising distributed logic with
plain `mpirun -n N` on one machine (reference: tests/run_mpi_tests.cpp) — here
via XLA's forced host-platform device count, so `shard_map` sharding logic is
tested without TPU pod hardware (SURVEY.md §4 "TPU-build translation").

Double precision (the reference's default and its 1e-6 oracle tolerance,
tests/test_util/test_check_values.hpp:46-50) requires jax x64, which is
CPU-only — another reason tests pin JAX_PLATFORMS=cpu.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

from spfft_tpu.utils.platform import force_virtual_cpu_devices  # noqa: E402

# JAX_PLATFORMS above pins the CPU for this process and the subprocesses
# the tests spawn; the device count goes through the live config too.
force_virtual_cpu_devices(8)
jax.config.update("jax_enable_x64", True)
