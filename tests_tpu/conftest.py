"""On-TPU CI lane configuration (make ci-tpu).

Unlike tests/ (which pins JAX_PLATFORMS=cpu and a virtual 8-device mesh
so distributed logic runs anywhere), this lane runs on the REAL chip:
Mosaic codegen, T(8,128) layout behavior, pair-IO boundaries and the
wide-kernel DMA path have a documented history of silent corruption
(the round-2 rank-3 irfft bug, the round-4 wide-kernel compile crash)
that CPU-pinned tests structurally cannot see — round-4 verdict item 3.
"""

import jax
import pytest


def pytest_runtest_setup(item):
    if jax.default_backend() != "tpu":
        pytest.skip("ci-tpu lane requires the real TPU backend "
                    "(run tests/ for the CPU suite)")


@pytest.fixture(scope="session")
def tpu_device():
    return jax.devices()[0]
