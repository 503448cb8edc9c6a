"""Distributed Pallas compression path (interpret mode on the CPU mesh).

The distributed plan can route its compression stages through the same
monotone-gather kernel the local plan uses, with per-shard tables padded to
uniform shapes (gather_kernel.pad_tables_to). On CPU, ``use_pallas=True``
runs the kernel in interpret mode inside shard_map — validating the padded
multi-shard tables exactly; the compiled kernel itself is exercised on the
real chip by scripts/verify_drive.py step 6. Measured on TPU v5e
(128³ sphere, 1-shard mesh, same session): 18.6 ms (XLA gathers) ->
4.6 ms (Pallas) per fused pair."""

import numpy as np
import pytest

from spfft_tpu import ExchangeType, Scaling, TransformType
from spfft_tpu.parallel import make_distributed_plan, make_mesh

from spfft_tpu.utils.workloads import sort_triplets_stick_major

from test_util import (dense_backward, dense_cube_from_values, dense_forward,
                       hermitian_triplets, random_sparse_triplets,
                       random_values, sample_cube, tolerance_for)
from test_distributed import split_by_sticks, split_planes

DIMS = (12, 11, 13)


def _plans(transform_type, parts, planes, exchange=ExchangeType.DEFAULT):
    mk = lambda up: make_distributed_plan(  # noqa: E731
        transform_type, *DIMS, parts, planes, mesh=make_mesh(4),
        precision="single", exchange=exchange, use_pallas=up)
    ref, pal = mk(False), mk(True)
    assert pal._pallas_dist is not None, "pallas tables must build"
    assert pal._pallas_interpret, "CPU backend must use interpret mode"
    return ref, pal


def test_pallas_matches_xla_c2c():
    rng = np.random.default_rng(51)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, [2, 1, 0, 1])  # empty shard
    planes = split_planes(DIMS[2], [1, 3, 1, 2])
    ref, pal = _plans(TransformType.C2C, parts, planes)
    vals = [random_values(rng, len(p)).astype(np.complex64) for p in parts]
    np.testing.assert_array_equal(np.asarray(pal.backward(vals)),
                                  np.asarray(ref.backward(vals)))
    got = pal.unshard_values(pal.apply_pointwise(vals,
                                                 scaling=Scaling.FULL))
    for g, v in zip(got, vals):
        np.testing.assert_allclose(g, v, atol=1e-4, rtol=0)


def test_pallas_matches_xla_r2c():
    rng = np.random.default_rng(52)
    space = rng.uniform(-1, 1, (DIMS[2], DIMS[1], DIMS[0]))
    freq = dense_forward(space.astype(np.complex128))
    triplets = hermitian_triplets(rng, DIMS)
    parts = [sort_triplets_stick_major(p, DIMS)
             for p in split_by_sticks(triplets, DIMS, [1, 2, 1, 1])]
    planes = split_planes(DIMS[2], [2, 1, 1, 1])
    ref, pal = _plans(TransformType.R2C, parts, planes)
    vals = [sample_cube(freq, p, DIMS).astype(np.complex64) for p in parts]
    a = np.asarray(ref.backward(vals))
    b = np.asarray(pal.backward(vals))
    np.testing.assert_allclose(b, a, atol=1e-5, rtol=0)
    oracle = space * space.size
    got = np.concatenate(pal.unshard_space(pal.backward(vals)), axis=0)
    np.testing.assert_allclose(got, oracle, atol=1e-2, rtol=0)


def test_pallas_apply_pointwise_with_fn_args():
    """fn/fn_args on the Pallas path: the *rest split in _pair_body must
    hand the 8 ptables to the bodies and the trailing args to fn."""
    import jax
    rng = np.random.default_rng(55)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, [1, 2, 0, 1])
    planes = split_planes(DIMS[2], [1, 1, 1, 1])
    ref, pal = _plans(TransformType.C2C, parts, planes)
    vals = [random_values(rng, len(p)).astype(np.complex64) for p in parts]

    def scale_field(space, field):
        return space * field[..., None]

    dp = pal.dist_plan
    field = np.full((dp.num_shards, dp.max_planes, DIMS[1], DIMS[0]), 2.0,
                    np.float32)
    field_ref = jax.device_put(field, ref._sharded)
    field_pal = jax.device_put(field, pal._sharded)
    a = np.asarray(ref.apply_pointwise(vals, scale_field, field_ref,
                                       scaling=Scaling.FULL))
    b = np.asarray(pal.apply_pointwise(vals, scale_field, field_pal,
                                       scaling=Scaling.FULL))
    np.testing.assert_allclose(b, a, atol=1e-5, rtol=0)
    got = pal.unshard_values(b)
    for g, v in zip(got, vals):
        np.testing.assert_allclose(g, 2.0 * v, atol=1e-4, rtol=0)


def test_pallas_with_ring_exchange():
    rng = np.random.default_rng(53)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, [1, 1, 1, 1])
    planes = split_planes(DIMS[2], [1, 1, 1, 1])
    ref, pal = _plans(TransformType.C2C, parts, planes,
                      exchange=ExchangeType.UNBUFFERED)
    vals = [random_values(rng, len(p)).astype(np.complex64) for p in parts]
    np.testing.assert_array_equal(np.asarray(pal.backward(vals)),
                                  np.asarray(ref.backward(vals)))


def test_pallas_auto_off_on_cpu_and_double_guard():
    rng = np.random.default_rng(54)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, [1, 1, 1, 1])
    planes = split_planes(DIMS[2], [1, 1, 1, 1])
    # auto (None) on CPU: stays on the XLA path
    plan = make_distributed_plan(TransformType.C2C, *DIMS, parts, planes,
                                 mesh=make_mesh(4), precision="single")
    assert plan._pallas_dist is None
    # forcing the kernel on a double plan is an error, like the local plan
    from spfft_tpu.errors import InvalidParameterError
    with pytest.raises(InvalidParameterError):
        make_distributed_plan(TransformType.C2C, *DIMS, parts, planes,
                              mesh=make_mesh(4), precision="double",
                              use_pallas=True)


@pytest.mark.parametrize("seed", range(3))
def test_pallas_random_config_property(seed):
    """Random dims/distributions through the padded-table construction:
    the kernel path must agree with the XLA path bit-for-bit."""
    rng = np.random.default_rng(3000 + seed)
    dims = tuple(int(d) for d in rng.integers(4, 18, 3))
    shards = int(rng.integers(2, 5))
    triplets = random_sparse_triplets(rng, dims)
    if len(triplets) == 0:
        pytest.skip("degenerate empty set")
    parts = [sort_triplets_stick_major(p, dims) for p in
             split_by_sticks(triplets, dims, rng.integers(0, 3, shards) + 1)]
    planes = split_planes(dims[2], rng.integers(0, 3, shards) + 1)

    def mk(up):
        return make_distributed_plan(
            TransformType.C2C, *dims, parts, planes,
            mesh=make_mesh(shards), precision="single", use_pallas=up)
    ref, pal = mk(False), mk(True)
    if pal._pallas_dist is None:
        pytest.skip("tables not buildable for this config")
    vals = [random_values(rng, len(p)).astype(np.complex64) for p in parts]
    np.testing.assert_array_equal(np.asarray(pal.backward(vals)),
                                  np.asarray(ref.backward(vals)))
    np.testing.assert_array_equal(
        np.asarray(pal.forward(pal.backward(vals))),
        np.asarray(ref.forward(ref.backward(vals))))


def test_pallas_batched_matches_xla_batched():
    """The batched-grid kernel inside the batched SPMD body (interpret
    mode): fused distributed batch through Pallas == XLA batch == singles."""
    rng = np.random.default_rng(57)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, [2, 1, 0, 1])
    planes = split_planes(DIMS[2], [1, 3, 1, 2])
    ref, pal = _plans(TransformType.C2C, parts, planes)
    vals = [[random_values(rng, len(p)).astype(np.complex64) for p in parts]
            for _ in range(3)]
    got = np.asarray(pal.backward_batched(vals))
    want = np.asarray(ref.backward_batched(vals))
    np.testing.assert_array_equal(got, want)
    for i, v in enumerate(vals):
        np.testing.assert_array_equal(got[:, i],
                                      np.asarray(pal.backward(v)))
    # forward direction too
    spaces = [pal.backward(v) for v in vals]
    fgot = np.asarray(pal.forward_batched(spaces, Scaling.FULL))
    fwant = np.asarray(ref.forward_batched(spaces, Scaling.FULL))
    np.testing.assert_allclose(fgot, fwant, atol=1e-6, rtol=0)


def test_pallas_compact_float_split_r2c_combo():
    """The riskiest interaction surface in one plan: COMPACT_BUFFERED_FLOAT
    (exact-count schedule + reduced wire precision) x the split-x window
    x R2C symmetry x the Pallas kernel, on a skewed 4-shard distribution
    with an empty shard — against the XLA-path plan and the dense oracle."""
    rng = np.random.default_rng(77)
    dims = (24, 10, 12)  # narrow occupied x of the half spectrum -> split
    triplets = hermitian_triplets(rng, dims)
    triplets = triplets[triplets[:, 0] <= 4]  # force a narrow x window
    triplets = sort_triplets_stick_major(triplets, dims)
    parts = split_by_sticks(triplets, dims, [3, 1, 0, 2])
    planes = split_planes(dims[2], [0, 5, 4, 3])
    mk = lambda up: make_distributed_plan(  # noqa: E731
        TransformType.R2C, *dims, parts, planes, mesh=make_mesh(4),
        precision="single", exchange=ExchangeType.COMPACT_BUFFERED_FLOAT,
        use_pallas=up)
    ref, pal = mk(False), mk(True)
    assert pal._pallas_dist is not None and pal._pallas_interpret
    assert pal._split_x is not None, "split-x must engage for this set"
    # hermitian-CONSISTENT values (sampled from a real field's spectrum):
    # arbitrary values at x=0-plane mirror points are projected by the
    # real transform and would fail an exact round trip
    field = rng.uniform(-1, 1, (dims[2], dims[1], dims[0]))
    freq = dense_forward(field.astype(np.complex128))
    vals = [sample_cube(freq, p, dims).astype(np.complex64) for p in parts]
    got_p = np.asarray(pal.backward(vals))
    got_r = np.asarray(ref.backward(vals))
    np.testing.assert_allclose(got_p, got_r, atol=1e-2)  # bf16 wire
    # dense oracle: the provided values plus their hermitian mirrors
    nx, ny, nz = dims
    cube = dense_cube_from_values(np.concatenate(parts),
                                  np.concatenate(vals), dims)
    st = np.concatenate(parts) % np.array([nx, ny, nz])
    mz, my, mx = (-st[:, 2]) % nz, (-st[:, 1]) % ny, (-st[:, 0]) % nx
    selfc = (st[:, 2] == mz) & (st[:, 1] == my) & (st[:, 0] == mx)
    cube[mz[~selfc], my[~selfc], mx[~selfc]] = \
        np.conj(np.concatenate(vals)[~selfc])
    cube[st[selfc, 2], st[selfc, 1], st[selfc, 0]] = \
        np.concatenate(vals)[selfc].real
    want = dense_backward(cube).real
    space = np.concatenate(pal.unshard_space(got_p), axis=0)
    # bf16 wire carries ~8 mantissa bits: bound the error relative to the
    # field magnitude, not absolutely
    np.testing.assert_allclose(space, want,
                               atol=0.02 * np.abs(want).max())
    # round trip through the fused pair
    out = pal.unshard_values(pal.apply_pointwise(vals,
                                                 scaling=Scaling.FULL))
    vmax = max(np.abs(np.concatenate(vals)).max(), 1.0)
    for g, v in zip(out, vals):
        np.testing.assert_allclose(g, v, atol=0.01 * vmax, rtol=0)


# -- gather geometry: the wide cover in one launch a shard, else narrow ------

GEOM_DIMS = (24, 24, 64)  # 144 sticks a shard: 9,216 slots, 2 super-tiles


def _geometry_parts(scatter_first=False):
    """Four stick-major shards of one random set; with ``scatter_first``
    the first shard's sticks come in a random order (z still ascending
    in each), so its wide cover needs more chunks than super-tiles."""
    rng = np.random.default_rng(56)
    triplets = random_sparse_triplets(rng, GEOM_DIMS, stick_fraction=1.0,
                                      fill_fraction=0.9)
    parts = [sort_triplets_stick_major(p, GEOM_DIMS)
             for p in split_by_sticks(triplets, GEOM_DIMS, [1, 1, 1, 1])]
    if scatter_first:
        p0 = parts[0]
        key = p0[:, 0] * GEOM_DIMS[1] + p0[:, 1]
        sticks, inv = np.unique(key, return_inverse=True)
        rank = np.random.default_rng(3).permutation(len(sticks))[inv]
        parts[0] = p0[np.lexsort((p0[:, 2], rank))]
    return parts, triplets


def _geometry_plan(monkeypatch, chunks, scatter_first=False):
    """A 4-shard C2C plan built with the wide launch's SMEM budget shrunk
    to ``chunks`` chunks — the stand-in for a shard whose super-tile count
    passes (or fits) the real budget, which only a 768^3 grid reaches."""
    from spfft_tpu import obs
    from spfft_tpu.ops import gather_kernel as gk
    parts, triplets = _geometry_parts(scatter_first)
    planes = split_planes(GEOM_DIMS[2], [1, 1, 1, 1])

    def count(direction, geometry):
        return obs.GLOBAL_COUNTERS.get("spfft_plan_gather_geometry_total",
                                       plan="distributed",
                                       direction=direction,
                                       geometry=geometry)

    dirs = [(d, g) for d in ("decompress", "compress")
            for g in ("wide", "narrow")]
    before = {k: count(*k) for k in dirs}
    with monkeypatch.context() as m:
        m.setattr(gk, "SMEM_TABLE_WORDS", chunks * (3 + gk.WIDE_P // 4))
        plan = make_distributed_plan(
            TransformType.C2C, *GEOM_DIMS, parts, planes, mesh=make_mesh(4),
            precision="single", use_pallas=True)
    counted = {k: count(*k) - before[k] for k in dirs}
    return plan, parts, triplets, counted


def test_wide_geometry_one_launch_a_shard(monkeypatch):
    """Per-shard tables that fit one launch take the wide cover in both
    directions (the counter and ``gather_geometry`` say so); where the
    shard's super-tiles pass the limit, the narrow cover. The gather only
    selects, so both give the same bits, and both match the dense
    oracle."""
    wide, parts, triplets, counted = _geometry_plan(monkeypatch, 2)
    assert wide.gather_geometry == {"decompress": "wide",
                                    "compress": "wide"}
    assert counted == {("decompress", "wide"): 1, ("compress", "wide"): 1,
                       ("decompress", "narrow"): 0,
                       ("compress", "narrow"): 0}
    for t in (wide._pallas_dist["dec"], wide._pallas_dist["cmp"]):
        assert t["super_p1"] == 3  # 2 super-tiles a shard and the dummy
        row0, sub = t["stacked"][:2]
        assert sub.shape == (4, row0.shape[1] * t["p_tiles"] // 4)
    narrow, _, _, counted = _geometry_plan(monkeypatch, 1)
    assert narrow.gather_geometry == {"decompress": "narrow",
                                      "compress": "narrow"}
    assert counted == {("decompress", "narrow"): 1,
                       ("compress", "narrow"): 1,
                       ("decompress", "wide"): 0, ("compress", "wide"): 0}

    rng = np.random.default_rng(57)
    vals = [random_values(rng, len(p)).astype(np.complex64) for p in parts]
    space_w = np.asarray(wide.backward(vals))
    np.testing.assert_array_equal(space_w, np.asarray(narrow.backward(vals)))
    slabs = wide.unshard_space(space_w)
    cube = dense_cube_from_values(np.concatenate(parts),
                                  np.concatenate(vals), GEOM_DIMS)
    oracle = dense_backward(cube)
    np.testing.assert_allclose(np.concatenate(slabs, axis=0), oracle,
                               atol=tolerance_for("single", oracle), rtol=0)

    fwd_w = np.asarray(wide.forward(slabs, Scaling.NONE))
    np.testing.assert_array_equal(
        fwd_w, np.asarray(narrow.forward(slabs, Scaling.NONE)))
    freq = dense_forward(np.concatenate(slabs, axis=0).astype(np.complex128))
    for got, p in zip(wide.unshard_values(fwd_w), parts):
        want = sample_cube(freq, p, GEOM_DIMS)
        np.testing.assert_allclose(got, want,
                                   atol=tolerance_for("single", want),
                                   rtol=0)


def test_wide_cover_past_limit_falls_back_narrow(monkeypatch):
    """A shard whose sticks are scattered covers its 2 super-tiles in more
    than 4 wide chunks: under a 4-chunk budget the shard-uniform tables
    pass the limit though the super-tile count fits, and both directions
    fall back to narrow; under 8 they take one wide launch a shard.
    Either cover selects the same values, bit for bit."""
    narrow, parts, _, counted = _geometry_plan(monkeypatch, 4,
                                               scatter_first=True)
    assert narrow.gather_geometry == {"decompress": "narrow",
                                      "compress": "narrow"}
    assert counted[("decompress", "narrow")] == 1
    assert counted[("compress", "narrow")] == 1
    wide, _, _, counted = _geometry_plan(monkeypatch, 8, scatter_first=True)
    assert wide.gather_geometry == {"decompress": "wide", "compress": "wide"}
    assert counted[("decompress", "wide")] == 1
    assert counted[("compress", "wide")] == 1
    for t in (wide._pallas_dist["dec"], wide._pallas_dist["cmp"]):
        assert t["super_p1"] == 3 and 4 < t["stacked"][0].shape[1] <= 8

    rng = np.random.default_rng(59)
    vals = [random_values(rng, len(p)).astype(np.complex64) for p in parts]
    space = np.asarray(wide.backward(vals))
    np.testing.assert_array_equal(space, np.asarray(narrow.backward(vals)))
    slabs = wide.unshard_space(space)
    np.testing.assert_array_equal(
        np.asarray(wide.forward(slabs, Scaling.NONE)),
        np.asarray(narrow.forward(slabs, Scaling.NONE)))


def test_gather_geometry_empty_without_kernel():
    """A plan on the XLA gather path reports no kernel geometry."""
    rng = np.random.default_rng(58)
    triplets = random_sparse_triplets(rng, DIMS)
    parts = split_by_sticks(triplets, DIMS, [1, 1, 1, 1])
    planes = split_planes(DIMS[2], [1, 1, 1, 1])
    plan = make_distributed_plan(TransformType.C2C, *DIMS, parts, planes,
                                 mesh=make_mesh(4), precision="single",
                                 use_pallas=False)
    assert plan.gather_geometry == {}
