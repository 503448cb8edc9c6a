"""Grid/Transform public API tests (reference: grid.hpp, transform.hpp,
multi_transform.hpp; multi-transform behavior mirrors
tests/mpi_tests/test_multi_transform.cpp)."""

import numpy as np
import pytest

from spfft_tpu import (Grid, InvalidParameterError, ProcessingUnit, Scaling,
                       TransformType, make_mesh, multi_transform_backward,
                       multi_transform_forward)
from spfft_tpu.utils import as_complex_np

from test_util import (dense_backward, dense_cube_from_values,
                       random_sparse_triplets, random_values, sample_cube)


def test_local_grid_example_flow():
    """The reference examples/example.cpp flow: dense 2x2x2 C2C."""
    dims = (2, 2, 2)
    triplets = np.array([(x, y, z) for x in range(2) for y in range(2)
                         for z in range(2)], np.int32)
    values = np.arange(8) * (1.0 - 1.0j)

    grid = Grid(2, 2, 2, 4, ProcessingUnit.HOST, precision="double")
    t = grid.create_transform(ProcessingUnit.HOST, TransformType.C2C,
                              2, 2, 2, 2, 8, indices=triplets)
    assert t.local_slice_size() == 8
    assert t.global_size == 8
    assert t.num_local_elements() == 8

    space = t.backward(values)
    assert t.space_domain_data() is space
    cube = dense_cube_from_values(triplets, values, dims)
    np.testing.assert_allclose(as_complex_np(np.asarray(space)),
                               dense_backward(cube), atol=1e-12)

    # forward consumes the stored space-domain data (example.cpp:79-81)
    out = as_complex_np(np.asarray(t.forward()))
    np.testing.assert_allclose(out, values * 8, atol=1e-12)


def test_flat_interleaved_indices():
    """C-API style flat x1,y1,z1,x2,y2,z2 index array (grid.h)."""
    grid = Grid(4, 4, 4, 16, precision="double")
    flat = np.array([0, 0, 0, 1, 2, 3])
    t = grid.create_transform(ProcessingUnit.HOST, TransformType.C2C,
                              4, 4, 4, indices=flat)
    assert t.num_local_elements() == 2


def test_grid_limits_enforced():
    # reference: transform_internal.cpp:52-83
    grid = Grid(4, 4, 4, 1, precision="double")
    with pytest.raises(InvalidParameterError):
        grid.create_transform(ProcessingUnit.HOST, TransformType.C2C,
                              8, 4, 4, indices=np.array([[0, 0, 0]]))
    with pytest.raises(InvalidParameterError):
        # two sticks > max_num_local_z_sticks == 1
        grid.create_transform(ProcessingUnit.HOST, TransformType.C2C,
                              4, 4, 4, indices=np.array([[0, 0, 0],
                                                         [1, 1, 0]]))


def test_forward_without_space_data_raises():
    grid = Grid(4, 4, 4, 16, precision="double")
    t = grid.create_transform(ProcessingUnit.HOST, TransformType.C2C,
                              4, 4, 4, indices=np.array([[0, 0, 0]]))
    with pytest.raises(InvalidParameterError):
        t.forward()


def test_distributed_grid():
    dims = (8, 8, 8)
    rng = np.random.default_rng(2)
    triplets = random_sparse_triplets(rng, dims)
    values = random_values(rng, len(triplets))
    cube = dense_cube_from_values(triplets, values, dims)

    # round-robin sticks over 2 shards
    keys = triplets[:, 0].astype(np.int64) * 8 + triplets[:, 1]
    uk = np.unique(keys)
    own = {k: i % 2 for i, k in enumerate(uk.tolist())}
    parts = [triplets[np.array([own[k] == r for k in keys])] for r in range(2)]

    grid = Grid(8, 8, 8, 64, mesh=make_mesh(2), precision="double")
    t = grid.create_transform(ProcessingUnit.DEVICE, TransformType.C2C,
                              8, 8, 8, triplets_per_shard=parts,
                              planes_per_shard=[4, 4])
    assert t.distributed
    assert t.local_z_offset(1) == 4
    vparts = [sample_cube(cube, p, dims) for p in parts]
    space = t.backward(vparts)
    got = np.concatenate(t.plan.unshard_space(space), axis=0)
    np.testing.assert_allclose(got, dense_backward(cube), atol=1e-10)


def test_multi_transform():
    """Three cloned transforms, constant values each, batched backward +
    forward, exact check (reference: test_multi_transform.cpp)."""
    dims = (6, 6, 6)
    triplets = np.asarray([(x, y, z) for x in range(6) for y in range(6)
                           for z in range(6)], np.int32)
    grid = Grid(6, 6, 6, 36, precision="double")
    base = grid.create_transform(ProcessingUnit.HOST, TransformType.C2C,
                                 6, 6, 6, indices=triplets)
    transforms = [base.clone() for _ in range(3)]
    batches = [np.full(len(triplets), complex(k + 1, -(k + 1)))
               for k in range(3)]

    spaces = multi_transform_backward(transforms, batches)
    outs = multi_transform_forward(transforms,
                                   scalings=[Scaling.FULL] * 3)
    for k in range(3):
        got = as_complex_np(np.asarray(outs[k]))
        np.testing.assert_allclose(got, batches[k], atol=1e-12)
        assert transforms[k].space_domain_data() is spaces[k]

    with pytest.raises(InvalidParameterError):
        multi_transform_backward(transforms, batches[:2])


def test_python_examples_run():
    """The shipped Python examples execute end-to-end (on the test CPU
    platform; the C example is exercised by test_capi.py)."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("example.py", "example_distributed.py", "example_scf.py",
                 "example_multihost.py",
                 "example_poisson.py"):
        out = subprocess.run(
            [sys.executable, os.path.join(repo, "examples", name)],
            env=env, capture_output=True, text=True, timeout=300)
        assert out.returncode == 0, f"{name}: {out.stderr[-2000:]}"


def test_transform_property_getters():
    """The reference transform.hpp:91-171 getter surface on both plan
    kinds."""
    import numpy as np
    from spfft_tpu import (ExchangeType, ProcessingUnit, TransformType,
                           make_local_plan)
    from spfft_tpu.grid import Transform
    from spfft_tpu.parallel import make_distributed_plan, make_mesh

    trip = np.array([[0, 0, 0], [1, 2, 3]])
    local = Transform(make_local_plan(TransformType.C2C, 4, 4, 4, trip,
                                      precision="double"))
    assert local.processing_unit == ProcessingUnit.DEVICE
    assert local.precision == "double"
    assert local.exchange_type == ExchangeType.DEFAULT
    assert local.num_shards == 1

    parts = [trip[:1], trip[1:], trip[:0], trip[:0]]
    dist = Transform(make_distributed_plan(
        TransformType.C2C, 4, 4, 4, parts, [1, 1, 1, 1],
        mesh=make_mesh(4), precision="double",
        exchange=ExchangeType.UNBUFFERED))
    assert dist.processing_unit == ProcessingUnit.DEVICE
    assert dist.precision == "double"
    assert dist.exchange_type == ExchangeType.UNBUFFERED
    assert dist.num_shards == 4
    assert isinstance(dist.device_id, int)
    assert dist.num_threads == 4
    assert local.num_threads == 1


def test_space_domain_data_location():
    import numpy as np
    from spfft_tpu import ProcessingUnit, TransformType, make_local_plan
    from spfft_tpu.grid import Transform

    trip = np.array([[0, 0, 0], [1, 2, 3]])
    t = Transform(make_local_plan(TransformType.C2C, 4, 4, 4, trip,
                                  precision="double"))
    assert t.space_domain_data() is None
    t.backward(np.array([1 + 1j, 2 - 1j]))
    host = t.space_domain_data(ProcessingUnit.HOST)
    assert isinstance(host, np.ndarray)
    np.testing.assert_array_equal(
        host, np.asarray(t.space_domain_data(ProcessingUnit.DEVICE)))


def test_space_domain_host_snapshot_is_readonly():
    """Ported reference code that writes into space_domain_data(HOST) must
    fail loudly, not silently no-op (the reference buffer is writable;
    VERDICT r2 missing item 5)."""
    n = 4
    trip = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)], np.int32)
    grid = Grid(n, n, n, n * n)
    t = grid.create_transform(ProcessingUnit.DEVICE, TransformType.C2C,
                              n, n, n, indices=trip)
    vals = np.ones(len(trip), np.complex64)
    t.backward(vals)
    snap = t.space_domain_data(ProcessingUnit.HOST)
    with pytest.raises(ValueError):
        snap[0, 0, 0, 0] = 7.0
    # the documented mutation route still works
    writable = snap.copy()
    writable[0, 0, 0, 0] = 7.0
    t.set_space_domain_data(writable)


def test_space_domain_host_snapshot_does_not_alias_numpy_store():
    """A numpy array passed to set_space_domain_data must not share memory
    with the HOST snapshot (the snapshot promise; review r3)."""
    n = 4
    trip = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)], np.int32)
    grid = Grid(n, n, n, n * n)
    t = grid.create_transform(ProcessingUnit.DEVICE, TransformType.C2C,
                              n, n, n, indices=trip)
    a = np.zeros((n, n, n, 2), np.float32)
    t.set_space_domain_data(a)
    snap = t.space_domain_data(ProcessingUnit.HOST)
    a[0, 0, 0, 0] = 7.0
    assert snap[0, 0, 0, 0] == 0.0  # true snapshot, no aliasing
    assert a.flags.writeable  # the caller's array is untouched


def test_grid_copy_is_independent():
    """Grid deep-copy parity (reference grid_internal.cpp:232-262): the
    copy carries the same limits, works through copy.copy/deepcopy, and
    transforms made from original and copy are fully isolated."""
    import copy as copy_mod

    n = 6
    trip = np.array([[x, y, z] for x in range(2) for y in range(2)
                     for z in range(n)], np.int32)
    grid = Grid(n, n, n, 4)
    for dup in (grid.copy(), copy_mod.copy(grid),
                copy_mod.deepcopy(grid)):
        assert dup is not grid
        assert dup.max_dim_x == grid.max_dim_x
        assert dup.max_dim_y == grid.max_dim_y
        assert dup.max_dim_z == grid.max_dim_z
        assert dup.max_num_local_z_columns == grid.max_num_local_z_columns
        assert dup.processing_unit == grid.processing_unit
        assert dup.distributed == grid.distributed
    dup = grid.copy()
    ta = grid.create_transform(ProcessingUnit.DEVICE, TransformType.C2C,
                               n, n, n, indices=trip)
    tb = dup.create_transform(ProcessingUnit.DEVICE, TransformType.C2C,
                              n, n, n, indices=trip)
    vals = np.arange(len(trip)).astype(np.complex64)
    np.testing.assert_allclose(np.asarray(ta.backward(vals)),
                               np.asarray(tb.backward(vals)),
                               atol=0, rtol=0)
