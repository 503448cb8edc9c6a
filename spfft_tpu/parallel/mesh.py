"""Device-mesh helpers.

The reference scales over MPI ranks with a duplicated communicator per Grid
(reference: src/mpi_util/mpi_communicator_handle.hpp:47-56). The TPU-native
equivalent of the communicator is a 1-D ``jax.sharding.Mesh`` over the shard
axis; collectives ride ICI within a pod slice and DCN across slices, chosen by
XLA from the device order — there is no NCCL/MPI analogue to manage.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

from ..errors import InvalidParameterError

SHARD_AXIS = "shards"


def make_mesh(num_shards: Optional[int] = None,
              devices: Optional[Sequence[jax.Device]] = None,
              axis_name: str = SHARD_AXIS) -> Mesh:
    """Create a 1-D mesh over ``num_shards`` devices (default: all)."""
    if devices is None:
        devices = jax.devices()
    if num_shards is None:
        num_shards = len(devices)
    if num_shards > len(devices):
        raise InvalidParameterError(
            f"requested {num_shards} shards but only {len(devices)} devices "
            "are available")
    return Mesh(np.asarray(devices[:num_shards]), (axis_name,))
