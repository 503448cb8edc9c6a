"""Transpose/exchange-layer unit tests, below the FFT pipeline.

Mirrors reference tests/mpi_tests/test_transpose.cpp: drive the pack →
exchange → unpack mechanism directly against the plan's distribution
tables, checking (a) the freq→space→freq round trip restores every true
stick, (b) stick segments land at the correct (z, y, x) grid positions —
for both the fused all_to_all and the ppermute-ring mechanisms."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spfft_tpu import TransformType
from spfft_tpu.parallel import make_distributed_plan, make_mesh
from spfft_tpu.parallel.exchange import (all_to_all_blocks,
                                         pack_freq_to_blocks,
                                         pack_space_to_blocks,
                                         ring_exchange_blocks,
                                         unpack_blocks_to_grid,
                                         unpack_blocks_to_sticks)

from test_util import random_sparse_triplets
from test_distributed import split_by_sticks, split_planes

DIMS = (11, 12, 13)


def _make_plan(exchange_weights=([2, 1, 0, 1], [1, 3, 1, 2])):
    rng = np.random.default_rng(31)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, exchange_weights[0])
    planes = split_planes(DIMS[2], exchange_weights[1])
    plan = make_distributed_plan(TransformType.C2C, *DIMS, parts, planes,
                                 mesh=make_mesh(4), precision="double")
    return plan


@pytest.mark.parametrize("mechanism", [all_to_all_blocks,
                                       ring_exchange_blocks])
def test_exchange_round_trip_restores_sticks(mechanism):
    plan = _make_plan()
    dp = plan.dist_plan
    rng = np.random.default_rng(32)
    S, ms, dz = dp.num_shards, dp.max_sticks, dp.dim_z
    sticks = np.zeros((S, ms, dz), np.complex128)
    for r in range(S):
        n = dp.shard_plans[r].num_sticks
        sticks[r, :n] = (rng.standard_normal((n, dz))
                         + 1j * rng.standard_normal((n, dz)))

    def body(sticks, zmap, col_inv, cols_flat, z_src):
        blocks = pack_freq_to_blocks(sticks[0], zmap)
        blocks = mechanism(blocks, plan.axis_name, None)
        grid = unpack_blocks_to_grid(blocks, col_inv, dp.dim_y,
                                     dp.dim_x_freq)
        blocks2 = pack_space_to_blocks(grid, cols_flat, S, ms)
        blocks2 = mechanism(blocks2, plan.axis_name, None)
        return unpack_blocks_to_sticks(blocks2, z_src)[None]

    shmap = jax.shard_map(
        body, mesh=plan.mesh,
        in_specs=(P(plan.axis_name), P(), P(), P(), P()),
        out_specs=P(plan.axis_name))
    got = np.asarray(jax.jit(shmap)(
        jax.device_put(sticks, NamedSharding(plan.mesh, P(plan.axis_name))),
        plan._zmap, plan._col_inv, plan._cols_flat, plan._z_src))
    for r in range(S):
        n = dp.shard_plans[r].num_sticks
        np.testing.assert_allclose(got[r, :n], sticks[r, :n], atol=0,
                                   rtol=0)


def test_exchange_grid_placement():
    """After the backward exchange, each shard's grid must hold stick
    (x, y) of shard r at [z_local, y, x] for each of its true planes —
    checked against a dense oracle built from the plan metadata."""
    plan = _make_plan()
    dp = plan.dist_plan
    rng = np.random.default_rng(33)
    S, ms, dz = dp.num_shards, dp.max_sticks, dp.dim_z
    sticks = np.zeros((S, ms, dz), np.complex128)
    for r in range(S):
        n = dp.shard_plans[r].num_sticks
        sticks[r, :n] = (rng.standard_normal((n, dz))
                         + 1j * rng.standard_normal((n, dz)))

    def body(sticks, zmap, col_inv):
        blocks = pack_freq_to_blocks(sticks[0], zmap)
        blocks = all_to_all_blocks(blocks, plan.axis_name, None)
        return unpack_blocks_to_grid(blocks, col_inv, dp.dim_y,
                                     dp.dim_x_freq)[None]

    shmap = jax.shard_map(
        body, mesh=plan.mesh, in_specs=(P(plan.axis_name), P(), P()),
        out_specs=P(plan.axis_name))
    grids = np.asarray(jax.jit(shmap)(
        jax.device_put(sticks, NamedSharding(plan.mesh, P(plan.axis_name))),
        plan._zmap, plan._col_inv))

    # oracle: dense (dim_z, dim_y, dim_x_freq) built from stick tables
    dense = np.zeros((dz, dp.dim_y, dp.dim_x_freq), np.complex128)
    for r in range(S):
        sp = dp.shard_plans[r]
        for i in range(sp.num_sticks):
            key = int(sp.stick_keys[i])
            x, y = key // dp.dim_y, key % dp.dim_y
            dense[:, y, x] = sticks[r, i]
    for r in range(S):
        off, n_pl = dp.plane_offsets[r], dp.num_planes[r]
        np.testing.assert_allclose(grids[r, :n_pl],
                                   dense[off:off + n_pl], atol=0, rtol=0)
