"""Where each value and each z-plane of a stick set lives over the chips
of one host: the benchmark's own copy of the program's
``utils.workloads.round_robin_stick_partition`` (a test holds them
equal), vectorised, so that no change to the program moves the
benchmark's inputs. The system over the distributed plan
(``systems/dist_plan.py``), the sharded driver and the sharded control
(``references/sparse_fft_slabs.py``) all read it.

Sharded arrays are ``(shards, max_values, 2)`` values and ``(shards,
max_planes, dim_y, dim_x, 2)`` slabs, shard s holding its values first
and its slab in its first rows, zeros after.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np


def unique_inverse(keys: np.ndarray) -> tuple:
    """``np.unique(keys, return_inverse=True)``, in one pass where the
    keys are already in order (as SpFFT's stick sets come)."""
    keys = np.asarray(keys)
    if keys.size and bool(np.all(keys[1:] >= keys[:-1])):
        step = np.empty(keys.size, bool)
        step[0] = True
        np.not_equal(keys[1:], keys[:-1], out=step[1:])
        return keys[step], np.cumsum(step) - 1
    uniq, inv = np.unique(keys, return_inverse=True)
    return uniq, inv.reshape(-1)


def stick_owners(ix, iy, ny: int, shards: int) -> np.ndarray:
    """Shard of each value: whole z-sticks (x, y) go round-robin to the
    shards in the order of their keys ``x * ny + y`` (storage order,
    0 <= x, y < dim)."""
    keys = np.asarray(ix, np.int64) * int(ny) + np.asarray(iy, np.int64)
    _, rank = unique_inverse(keys)
    return (rank % int(shards)).astype(np.int32)


class Layout:
    """The sharded layout of one stick set: ``rows[s]`` are the global
    value rows shard s holds, in their global order; shard s holds the
    z-planes ``[offsets[s], offsets[s] + planes[s])``."""

    def __init__(self, ix, iy, dims, shards: int, planes):
        nx, ny, nz = (int(n) for n in dims)
        self.dims = (nx, ny, nz)
        self.shards = int(shards)
        owners = stick_owners(ix, iy, ny, self.shards)
        with ThreadPoolExecutor(self.shards) as pool:
            self.rows = list(pool.map(lambda s: np.flatnonzero(owners == s),
                                      range(self.shards)))
        self.counts = np.array([len(r) for r in self.rows], np.int64)
        self.num_values = int(owners.size)
        self.planes = [int(p) for p in planes]
        if len(self.planes) != self.shards or sum(self.planes) != nz:
            raise ValueError(f"planes {self.planes} do not split "
                             f"{nz} z-planes over {self.shards} shards")
        self.offsets = [int(o) for o in
                        np.concatenate([[0], np.cumsum(self.planes)[:-1]])]

    @property
    def max_values(self) -> int:
        return int(self.counts.max())

    @property
    def max_planes(self) -> int:
        return max(self.planes)

    @property
    def order(self) -> np.ndarray:
        """The global row of every value in shard order: shard 0's values,
        then shard 1's, ..."""
        return np.concatenate(self.rows)

    def host_values(self, values) -> np.ndarray:
        """Host copy of the sharded values ``(shards, max_values, 2)`` in
        shard order, each shard's own values: ``(num_values, 2)``."""
        blocks = dict(host_blocks(values))
        return np.concatenate([blocks[s][:self.counts[s]]
                               for s in range(self.shards)])

    def host_slabs(self, space) -> dict:
        """Shard -> host copy of its own planes of the sharded slabs
        ``(shards, max_planes, ny, nx[, 2])``."""
        return {s: block[:self.planes[s]]
                for s, block in host_blocks(space)}


def host_blocks(array):
    """(shard, host copy of its block) of an array sharded over its
    leading axis, the shard's, each block read on its own."""
    for piece in array.addressable_shards:
        yield piece.index[0].start or 0, np.asarray(piece.data)[0]


def layout_of(config: dict, triplets: np.ndarray) -> Layout:
    """The layout a configuration states for its stick set."""
    if config.get("distribution", "round_robin") != "round_robin":
        raise ValueError(f"unknown stick distribution "
                         f"{config['distribution']!r}")
    nx, ny, nz = (int(n) for n in config["grid"])
    t = np.asarray(triplets)
    return Layout(t[:, 0] % nx, t[:, 1] % ny, config["grid"],
                  int(config["shards"]), config["planes"])
