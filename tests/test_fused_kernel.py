"""Interpret-mode tests for the fused compression+z-DFT Pallas kernels
(ops/fused_kernel.py) and their plan dispatch: bit-exact (fp32) against
the unfused decompress -> pdft_last composition across c2c/r2c, batched,
shuffled-stick orders and sentinel/zero-stick edge cases, plus the
fallback gate (every unsupported case routes to the two-kernel path
with a recorded reason) and the HLO evidence that the dense gather-tile
intermediate is gone from the fused program."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spfft_tpu import Scaling, TransformType, make_local_plan
from spfft_tpu.ops import dft
from spfft_tpu.ops import fused_kernel as fkm
from spfft_tpu.ops import gather_kernel as gk

DIM_Z = 128  # smallest fused-eligible z (dim_z % 128 == 0)


@pytest.fixture
def fused_env(monkeypatch):
    """The CPU fused lane: the mdft T pipeline forced on (the fused
    seam only exists there) and the fused kernels in interpret mode."""
    monkeypatch.setenv("SPFFT_TPU_FORCE_MATMUL_DFT", "1")
    monkeypatch.setenv("SPFFT_TPU_FUSED_INTERPRET", "1")


def _plan(triplets, nx=8, ny=6, nz=DIM_Z, ttype=TransformType.C2C,
          **kw):
    return make_local_plan(ttype, nx, ny, nz, np.asarray(triplets,
                                                         np.int32),
                           precision="single", use_pallas=True, **kw)


def _gappy_triplets(nx=8, ny=6, nz=DIM_Z, z_step=2):
    """Sparse sticks (every other z slot empty) — the sentinel/empty-
    slot edge the gather mask must zero before the DFT sees it."""
    return [(x, y, z) for x in range(nx) for y in range(ny)
            if (x + y) % 3 != 0 for z in range(0, nz, z_step)]


def _values(n, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n)
            + 1j * rng.standard_normal(n)).astype(np.complex64)


def _unfused_backward(plan, vals):
    return np.asarray(jax.jit(
        lambda v, t: plan._backward_impl(v, t, pallas=False))(
            plan._coerce_values(vals), plan._tables))


def _unfused_forward(plan, space, scaled):
    return np.asarray(jax.jit(
        lambda s, t: plan._forward_impl(s, t, scaled=scaled,
                                        pallas=False))(
            plan._coerce_space(space), plan._tables))


# -- kernel level ------------------------------------------------------------

def test_kernel_decompress_zdft_matches_composition():
    """run_decompress_zdft == windowed gather -> pdft_last, elementwise
    (fp32), on a sparse slot set."""
    rng = np.random.default_rng(0)
    s_pad, dim_z = 32, DIM_Z
    num_slots = s_pad * dim_z
    occ = rng.random(num_slots) < 0.6
    vi = np.flatnonzero(occ)
    (dec_idx, occupied), _ = gk.compression_gather_inputs(vi, num_slots)
    nt = gk.build_monotone_gather_tables(dec_idx, occupied, len(vi))
    assert nt is not None and not nt.segs
    ft = fkm.build_fused_decompress_tables(nt, dim_z, s_pad)
    assert not isinstance(ft, str)
    assert ft.r_sticks * dim_z == ft.p_tiles * gk.TILE

    vals = rng.standard_normal((len(vi), 2)).astype(np.float32)
    re, im = gk.planar_from_interleaved(jnp.asarray(vals), nt.src_rows)
    mats = dft.c2c_mats(dim_z, dft.BACKWARD)
    sr, si = fkm.run_decompress_zdft(
        re, im, fkm.decompress_device_tables(ft), fkm.commit_mats(mats),
        ft, interpret=True)

    o_re, o_im = gk.run_gather(re, im, gk.gather_device_tables(nt), nt,
                               interpret=True)
    ur = np.asarray(o_re).reshape(-1)[:num_slots].reshape(s_pad, dim_z)
    ui = np.asarray(o_im).reshape(-1)[:num_slots].reshape(s_pad, dim_z)
    wr, wi = dft.pdft_last(jnp.asarray(ur), jnp.asarray(ui), mats)
    np.testing.assert_allclose(np.asarray(sr)[:s_pad], np.asarray(wr),
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(np.asarray(si)[:s_pad], np.asarray(wi),
                               rtol=2e-6, atol=2e-6)


def test_kernel_zdft_compress_matches_composition():
    """run_zdft_compress == pdft_last -> windowed gather, with the
    scale folded into the matrices (compile-time scaling)."""
    rng = np.random.default_rng(1)
    s_pad, dim_z = 32, DIM_Z
    num_slots = s_pad * dim_z
    vi = np.flatnonzero(rng.random(num_slots) < 0.5)
    _, (cmp_idx, cmp_valid) = gk.compression_gather_inputs(vi, num_slots)
    nt = gk.build_monotone_gather_tables(cmp_idx, cmp_valid, num_slots)
    assert nt is not None and not nt.segs
    ct = fkm.build_fused_compress_tables(nt, dim_z, s_pad)
    assert not isinstance(ct, str)

    sr = rng.standard_normal((s_pad, dim_z)).astype(np.float32)
    si = rng.standard_normal((s_pad, dim_z)).astype(np.float32)
    mats = dft.c2c_mats(dim_z, dft.FORWARD, scale=1.0 / num_slots)
    psr, psi = fkm.pad_sticks_planar(jnp.asarray(sr), jnp.asarray(si),
                                     ct.src_sticks)
    fo_re, fo_im = fkm.run_zdft_compress(
        psr, psi, fkm.compress_device_tables(ct), fkm.commit_mats(mats),
        ct, interpret=True)
    got_re = np.asarray(fo_re).reshape(-1)[:ct.num_out]
    got_im = np.asarray(fo_im).reshape(-1)[:ct.num_out]

    tr, ti = dft.pdft_last(jnp.asarray(sr), jnp.asarray(si), mats)
    pad = nt.src_rows * 128 - num_slots
    fre = jnp.pad(jnp.asarray(tr).reshape(-1),
                  (0, pad)).reshape(nt.src_rows, 128)
    fim = jnp.pad(jnp.asarray(ti).reshape(-1),
                  (0, pad)).reshape(nt.src_rows, 128)
    w_re, w_im = gk.run_gather(fre, fim, gk.gather_device_tables(nt), nt,
                               interpret=True)
    np.testing.assert_allclose(
        got_re, np.asarray(w_re).reshape(-1)[:nt.num_out],
        rtol=2e-6, atol=2e-6)
    np.testing.assert_allclose(
        got_im, np.asarray(w_im).reshape(-1)[:nt.num_out],
        rtol=2e-6, atol=2e-6)


def test_super_tile_geometry_invariant():
    for dz in (128, 256, 384, 512, 640):
        r, p = fkm.super_tile_geometry(dz)
        assert r * dz == p * gk.TILE
        assert p <= fkm.MAX_P_TILES


# -- super-tile geometry vs the narrow fused kernel and a dense oracle ------

GEOM_N = 12  # grid 12 x 12 x DIM_Z: super-tiles of 64 sticks, 8 tiles


def _geometry_triplets(sticks: str, r2c: bool):
    """``cutoff``: SpFFT's benchmark set (every stick with x below half
    the x columns, full z, in stick order); ``sphere``: a ball of
    partial sticks; ``shuffled``: the cutoff sticks in a random stick
    order, so a super-tile's values lie in several source windows."""
    n, h = GEOM_N, GEOM_N // 2
    if sticks == "sphere":
        xs = range(0, h + 1) if r2c else range(-h + 1, h)
        trip = [(x % n, y % n, z % DIM_Z)
                for x in xs for y in range(-h + 1, h)
                for z in range(-DIM_Z // 2, DIM_Z // 2)
                if (x / h) ** 2 + (y / h) ** 2
                + (z / (DIM_Z / 2)) ** 2 <= 1.0]
        return np.asarray(trip, np.int32)
    xs = range(h + 1 if r2c else h)
    trip = np.asarray([(x, y, z) for x in xs for y in range(n)
                       for z in range(DIM_Z)], np.int32)
    if sticks == "shuffled":
        st = trip.reshape(-1, DIM_Z, 3)
        perm = np.random.default_rng(31).permutation(len(st))
        trip = st[perm].reshape(-1, 3)
    return trip


def _geometry_case(sticks: str, ttype: str):
    """Index plan and both geometries' fused tables for one stick set,
    built as the plan builds them."""
    from spfft_tpu.indexing import build_index_plan
    r2c = ttype == "r2c"
    p = build_index_plan(TransformType.R2C if r2c else TransformType.C2C,
                         GEOM_N, GEOM_N, DIM_Z,
                         _geometry_triplets(sticks, r2c))
    s_pad = -(-(p.num_sticks + 1) // 32) * 32
    num_slots = s_pad * DIM_Z
    (dec_idx, occ), (cmp_idx, cmp_valid) = gk.compression_gather_inputs(
        p.value_indices.astype(np.int64), num_slots)
    zid = p.zero_stick_id if r2c else None
    dec = (fkm.build_super_decompress_tables(
               dec_idx, occ, p.num_values, DIM_Z, p.num_sticks,
               zero_stick_id=zid),
           fkm.build_fused_decompress_tables(
               gk.build_monotone_gather_tables(dec_idx, occ,
                                               p.num_values),
               DIM_Z, s_pad, zero_stick_id=zid))
    cmp_ = (fkm.build_super_compress_tables(cmp_idx, cmp_valid, num_slots,
                                            DIM_Z, s_pad),
            fkm.build_fused_compress_tables(
                gk.build_monotone_gather_tables(cmp_idx, cmp_valid,
                                                num_slots),
                DIM_Z, s_pad))
    for sup, nar in (dec, cmp_):
        assert sup.geometry == "super_tile" and nar.geometry == "narrow"
    if sticks == "shuffled":
        assert dec[0].grid_steps > dec[0].num_super
        assert cmp_[0].grid_steps > cmp_[0].num_tiles // cmp_[0].p_tiles
    return p, s_pad, zid, dec, cmp_


def _rel_l2(a, b):
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


GEOMETRY_CASES = [
    pytest.param(sticks, ttype, batch, id=f"{sticks}-{ttype}-b{batch}")
    for sticks in ("cutoff", "sphere", "shuffled")
    for ttype in ("c2c", "r2c") for batch in (0, 2)]


@pytest.mark.parametrize("sticks,ttype,batch", GEOMETRY_CASES)
def test_super_tile_backward_matches_narrow(sticks, ttype, batch):
    """The super-tile backward kernel is bit-identical to the narrow
    one (same (R, dim_z) z-DFT per super-tile, each slot receiving its
    one value) and matches a dense float64 oracle, the r2c (0,0)-stick
    completion included."""
    p, _, zid, (sup, nar), _ = _geometry_case(sticks, ttype)
    lead = (batch,) if batch else ()
    vals = np.random.default_rng(32).standard_normal(
        lead + (p.num_values, 2)).astype(np.float32)
    mats = fkm.commit_mats(dft.c2c_mats(DIM_Z, dft.BACKWARD))

    def run(t):
        re, im = gk.planar_from_interleaved(jnp.asarray(vals), t.src_rows)
        sr, si = fkm.run_decompress_zdft(
            re, im, fkm.decompress_device_tables(t), mats, t,
            interpret=True)
        return (np.asarray(sr)[..., :p.num_sticks, :],
                np.asarray(si)[..., :p.num_sticks, :])

    got, want = run(sup), run(nar)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])

    x = np.zeros(lead + (p.num_sticks * DIM_Z,), np.complex128)
    x[..., p.value_indices] = vals[..., 0] + 1j * vals[..., 1]
    x = x.reshape(lead + (p.num_sticks, DIM_Z))
    if zid is not None:
        row = x[..., zid, :]
        mirror = np.conj(np.roll(row[..., ::-1], 1, axis=-1))
        x[..., zid, :] = np.where(row != 0, row, mirror)
    oracle = np.fft.ifft(x, axis=-1) * DIM_Z
    assert _rel_l2(got[0] + 1j * got[1], oracle) < 1e-6


@pytest.mark.parametrize("sticks,ttype,batch", GEOMETRY_CASES)
def test_super_tile_forward_matches_narrow(sticks, ttype, batch):
    """The super-tile forward kernel (P output tiles a step from one
    transformed stick window) agrees with the narrow one to 2e-7
    relative l2 and with a dense float64 oracle."""
    p, s_pad, _, _, (sup, nar) = _geometry_case(sticks, ttype)
    lead = (batch,) if batch else ()
    rng = np.random.default_rng(33)
    raw = np.zeros(lead + (2, s_pad, DIM_Z), np.float32)
    raw[..., :p.num_sticks, :] = rng.standard_normal(
        lead + (2, p.num_sticks, DIM_Z))
    sr, si = jnp.asarray(raw[..., 0, :, :]), jnp.asarray(raw[..., 1, :, :])
    mats = fkm.commit_mats(dft.c2c_mats(DIM_Z, dft.FORWARD))

    def run(t):
        psr, psi = fkm.pad_sticks_planar(sr, si, t.src_sticks)
        o_re, o_im = fkm.run_zdft_compress(
            psr, psi, fkm.compress_device_tables(t), mats, t,
            interpret=True)
        flat = lead + (-1,)
        return (np.asarray(o_re).reshape(flat)[..., :t.num_out]
                + 1j * np.asarray(o_im).reshape(flat)[..., :t.num_out])

    got, want = run(sup), run(nar)
    assert _rel_l2(got, want) <= 2e-7
    x = raw[..., 0, :, :].astype(np.float64) + 1j * raw[..., 1, :, :]
    y = np.fft.fft(x, axis=-1).reshape(lead + (-1,))
    assert _rel_l2(got, y[..., p.value_indices]) < 1e-6


def test_super_tile_geometry_counted(fused_env):
    """SpFFT's cutoff stick set (the benchmark cells' kind) takes the
    super-tile geometry in both directions: one grid step per 64-stick
    super-tile, recorded in ``spfft_plan_fused_geometry_total`` and on
    the plan."""
    from spfft_tpu import obs
    from spfft_tpu.benchmark import cutoff_stick_triplets

    def count(direction, geometry):
        return obs.GLOBAL_COUNTERS.get("spfft_plan_fused_geometry_total",
                                       direction=direction,
                                       geometry=geometry)

    before = {d: count(d, "super_tile") for d in ("backward", "forward")}
    trip = cutoff_stick_triplets(16, 16, DIM_Z, 0.5, False)
    plan = _plan(trip, nx=16, ny=16)
    assert plan.fused_active and plan.fused_fallback_reasons == {}
    assert plan.fused_geometry == {"backward": "super_tile",
                                   "forward": "super_tile"}
    # 128 full sticks: 2 super-tiles a direction
    assert plan.fused_grid_steps == {"backward": 2, "forward": 2}
    for d in ("backward", "forward"):
        assert count(d, "super_tile") == before[d] + 1
    vals = _values(len(trip), seed=34)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_array_equal(space, _unfused_backward(plan, vals))
    out = np.asarray(plan.forward(space, Scaling.FULL))
    np.testing.assert_allclose(out, _unfused_forward(plan, space, True),
                               rtol=2e-6, atol=2e-6)


def test_super_tile_smem_limit_keeps_narrow(fused_env, monkeypatch):
    """Tables past the scalar-prefetch budget keep the narrow fused
    kernel — never the unfused path — with the reason recorded under
    the ``fused_super_tile_*`` stages and ``narrow`` counted."""
    from spfft_tpu import obs
    monkeypatch.setattr(fkm, "SMEM_TABLE_WORDS", 8)

    def count(name, **labels):
        return obs.GLOBAL_COUNTERS.get(name, **labels)

    dirs = ("backward", "forward")
    before = {d: (count("spfft_plan_fused_geometry_total", direction=d,
                        geometry="narrow"),
                  count("spfft_plan_pallas_fallback_total",
                        stage=f"fused_super_tile_{d}", reason="smem"))
              for d in dirs}
    plan = _plan(_gappy_triplets())
    assert plan.fused_active and plan.fused_fallback_reasons == {}
    assert plan.fused_geometry == {"backward": "narrow",
                                   "forward": "narrow"}
    for d in dirs:
        assert count("spfft_plan_fused_geometry_total", direction=d,
                     geometry="narrow") == before[d][0] + 1
        assert count("spfft_plan_pallas_fallback_total",
                     stage=f"fused_super_tile_{d}",
                     reason="smem") == before[d][1] + 1
    vals = _values(plan.num_local_elements, seed=35)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)


# -- plan level --------------------------------------------------------------

def test_plan_backward_forward_fused_bit_exact(fused_env):
    """Fused c2c round trip == the unfused two-kernel composition,
    elementwise, both scalings — the gappy (sentinel-heavy) stick set."""
    plan = _plan(_gappy_triplets())
    assert plan.fused_active
    assert plan.fused_fallback_reasons == {}
    vals = _values(plan.num_local_elements, seed=2)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)
    for scaling, scaled in ((Scaling.NONE, False), (Scaling.FULL, True)):
        out = np.asarray(plan.forward(space, scaling))
        np.testing.assert_allclose(out,
                                   _unfused_forward(plan, space, scaled),
                                   rtol=2e-6, atol=2e-6)


def test_plan_fused_pair_round_trip(fused_env):
    """apply_pointwise (the benchmark's fused pair) through the fused
    kernels recovers the inputs at FULL scaling."""
    plan = _plan(_gappy_triplets())
    assert plan.fused_active
    vals = _values(plan.num_local_elements, seed=3)
    out = np.asarray(plan.apply_pointwise(vals, scaling=Scaling.FULL))
    np.testing.assert_allclose(out[:, 0] + 1j * out[:, 1], vals,
                               rtol=1e-4, atol=1e-5)


def test_plan_shuffled_stick_order_fused(fused_env):
    """Shuffled triplet order (locally-coherent but not stick-major)
    still passes the fused gate and stays bit-exact."""
    rng = np.random.default_rng(7)
    trip = np.asarray(_gappy_triplets(), np.int32)
    trip = trip[rng.permutation(len(trip))]
    plan = _plan(trip)
    assert plan.fused_active, plan.fused_fallback_reasons
    vals = _values(len(trip), seed=4)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)
    out = np.asarray(plan.forward(space))
    np.testing.assert_allclose(out, _unfused_forward(plan, space, False),
                               rtol=2e-6, atol=2e-6)


def test_plan_batched_fused(fused_env):
    """The batched boundary runs the batched fused grids and matches
    per-slab unfused execution."""
    plan = _plan(_gappy_triplets())
    assert plan.fused_active
    rng = np.random.default_rng(8)
    B, N = 3, plan.num_local_elements
    vb = rng.standard_normal((B, N, 2)).astype(np.float32)
    got = np.asarray(plan.backward_batched(vb))
    for b in range(B):
        np.testing.assert_allclose(
            got[b], _unfused_backward(plan, vb[b]), rtol=2e-6, atol=2e-6)
    out = np.asarray(plan.forward_batched(got, Scaling.FULL))
    for b in range(B):
        np.testing.assert_allclose(
            out[b], _unfused_forward(plan, got[b], True),
            rtol=2e-6, atol=2e-6)


def test_plan_r2c_fused(fused_env):
    """R2C fuses BOTH directions whether or not the (0,0) stick is
    present: its hermitian completion now rides inside the backward
    kernel (the one-hot mirror contraction of
    fused_kernel._complete_zero_stick), so ``fused_active`` holds with
    no recorded reason and both variants stay bit-exact vs the unfused
    composition."""
    nx, ny = 8, 6
    no_zero = [(x, y, z) for x in range(nx // 2 + 1) for y in range(ny)
               if (x, y) != (0, 0) for z in range(0, DIM_Z, 2)]
    plan = _plan(no_zero, ttype=TransformType.R2C)
    assert plan.fused_active and plan.fused_fallback_reasons == {}
    assert plan._fused["dec"].zinfo is None  # no (0,0) stick to complete
    vals = _values(len(no_zero), seed=5)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)
    out = np.asarray(plan.forward(space, Scaling.FULL))
    np.testing.assert_allclose(out, _unfused_forward(plan, space, True),
                               rtol=2e-6, atol=2e-6)

    with_zero = [(x, y, z) for x in range(nx // 2 + 1) for y in range(ny)
                 for z in range(0, DIM_Z, 2)]
    plan_z = _plan(with_zero, ttype=TransformType.R2C)
    assert plan_z.fused_active and plan_z.fused_fallback_reasons == {}
    assert plan_z._fused["dec"] is not None
    assert plan_z._fused["dec"].zinfo is not None
    assert plan_z._fused["cmp"] is not None
    vz = _values(len(with_zero), seed=6)
    sz = np.asarray(plan_z.backward(vz))
    np.testing.assert_array_equal(sz, _unfused_backward(plan_z, vz))
    oz = np.asarray(plan_z.forward(sz))
    np.testing.assert_allclose(oz, _unfused_forward(plan_z, sz, False),
                               rtol=2e-6, atol=2e-6)


def test_plan_r2c_fused_batched_zero_stick(fused_env):
    """The batched backward grid completes the (0,0) stick per slab,
    bit-exactly vs per-slab unfused execution."""
    nx, ny = 8, 6
    with_zero = [(x, y, z) for x in range(nx // 2 + 1) for y in range(ny)
                 for z in range(0, DIM_Z, 2)]
    plan = _plan(with_zero, ttype=TransformType.R2C)
    assert plan.fused_active, plan.fused_fallback_reasons
    rng = np.random.default_rng(21)
    B, N = 3, plan.num_local_elements
    vb = rng.standard_normal((B, N, 2)).astype(np.float32)
    got = np.asarray(plan.backward_batched(vb))
    for b in range(B):
        np.testing.assert_array_equal(got[b],
                                      _unfused_backward(plan, vb[b]))


def test_plan_empty_sticks_zeroed(fused_env):
    """Sticks whose slots carry no values at all come out as exact
    zeros of the z-DFT (the scratch zeroing + validity mask contract),
    and the round trip stays bit-exact."""
    # only 3 z-values per stick, most of each stick empty
    trip = [(x, y, z) for x in range(8) for y in range(6)
            if (x + y) % 2 == 0 for z in (0, 1, DIM_Z - 1)]
    plan = _plan(trip)
    assert plan.fused_active, plan.fused_fallback_reasons
    vals = _values(len(trip), seed=9)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)


# -- fallback gate -----------------------------------------------------------

def test_gate_dimz_not_multiple_128(fused_env):
    trip = [(x, y, z) for x in range(8) for y in range(8)
            for z in range(96)]
    plan = _plan(trip, nx=8, ny=8, nz=96)
    assert not plan.fused_active
    assert plan.fused_fallback_reasons == {
        "dec": "dimz_not_multiple_128", "cmp": "dimz_not_multiple_128"}
    vals = _values(len(trip), seed=10)
    space = np.asarray(plan.backward(vals))  # two-kernel path still runs
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)


def test_gate_oversized_z(fused_env):
    """dim_z above the fused-kernel axis cap (dft_kernel.max_dim)
    routes to the two-kernel path with the recorded reason."""
    from spfft_tpu.ops import dft_kernel as dk
    nz = 384
    assert nz % 128 == 0 and nz > dk.max_dim()
    trip = [(x, y, z) for x in range(4) for y in range(4)
            for z in range(0, nz, 2)]
    plan = _plan(trip, nx=4, ny=4, nz=nz)
    assert not plan.fused_active
    assert plan.fused_fallback_reasons == {
        "dec": "dimz_over_cap", "cmp": "dimz_over_cap"}


def test_gate_double_precision_never_fused(fused_env):
    """Double precision never reaches the fused gate (the Pallas
    compression path is single-only)."""
    trip = _gappy_triplets(nx=4, ny=4)
    plan = make_local_plan(TransformType.C2C, 4, 4, DIM_Z,
                           np.asarray(trip, np.int32),
                           precision="double")
    assert not plan.fused_active
    assert "fzd_tabs" not in plan._tables


def test_gate_env_disable(fused_env, monkeypatch):
    monkeypatch.setenv("SPFFT_TPU_FUSED_COMPRESS", "0")
    plan = _plan(_gappy_triplets())
    assert not plan.fused_active
    assert "fzd_tabs" not in plan._tables
    vals = _values(plan.num_local_elements, seed=11)
    space = np.asarray(plan.backward(vals))
    np.testing.assert_allclose(space, _unfused_backward(plan, vals),
                               rtol=2e-6, atol=2e-6)


def test_gate_recompute_blowup_model():
    """The forward cost model declines when window-overlap recompute
    exceeds RECOMPUTE_LIMIT x the unfused pass."""
    rng = np.random.default_rng(12)
    s_pad, dim_z = 32, DIM_Z
    num_slots = s_pad * dim_z
    vi = np.flatnonzero(rng.random(num_slots) < 0.5)
    _, (cmp_idx, cmp_valid) = gk.compression_gather_inputs(vi, num_slots)
    nt = gk.build_monotone_gather_tables(cmp_idx, cmp_valid, num_slots)
    rows = fkm.compress_recompute_rows(nt, dim_z)
    # a tiny stick count makes ANY recompute blow the model
    out = fkm.build_fused_compress_tables(nt, dim_z,
                                          num_sticks=max(1, int(
                                              rows / 100)))
    assert out == "recompute_blowup"


def test_fallback_counter_recorded(fused_env):
    from spfft_tpu import obs
    before = obs.GLOBAL_COUNTERS.get(
        "spfft_plan_pallas_fallback_total",
        stage="fused_decompress_zdft", reason="dimz_not_multiple_128")
    trip = [(x, y, z) for x in range(4) for y in range(4)
            for z in range(96)]
    plan = _plan(trip, nx=4, ny=4, nz=96)
    plan._finalize()
    after = obs.GLOBAL_COUNTERS.get(
        "spfft_plan_pallas_fallback_total",
        stage="fused_decompress_zdft", reason="dimz_not_multiple_128")
    assert after == before + 1


# -- the acceptance criterion: no dense gather-tile intermediate -------------

def test_fused_backward_hlo_drops_gather_intermediate(fused_env,
                                                      monkeypatch):
    """The fused backward program must not contain the unfused path's
    dense gather-output buffer (num_tiles, 8, 128) — the HBM
    intermediate this kernel exists to remove — while the forced
    UNFUSED kernel path does lower it."""
    import functools
    plan = _plan(_gappy_triplets())
    assert plan.fused_active
    dec = plan._pallas["dec"]
    vil = plan._coerce_values(_values(plan.num_local_elements, seed=13))
    shape = "%dx%dx%dxf32" % ((dec.num_super * dec.p_tiles)
                              if isinstance(dec, gk.WideGatherTables)
                              else dec.num_tiles, gk.TILE_SUB,
                              gk.TILE_LANE)
    fused_text = jax.jit(
        lambda v: plan._backward_impl(v, plan._tables_hot)).lower(
            vil).as_text()
    assert shape not in fused_text

    # contrast: the unfused kernel path (gather kernel in interpret,
    # fused dispatch off) materialises exactly that buffer
    monkeypatch.setattr(plan, "_fused_active_flag", False)
    monkeypatch.setattr(gk, "run_gather",
                        functools.partial(gk.run_gather, interpret=True))
    monkeypatch.setattr(plan, "_pallas_active", True)
    unfused_text = jax.jit(
        lambda v: plan._backward_impl(v, plan._tables)).lower(
            vil).as_text()
    assert shape in unfused_text


# -- distributed forward-twin gate rows (parallel/dist.py) -------------------

def _dist_plan(**kw):
    """A tiny 2-shard r2c plan on the virtual CPU mesh — the same
    flagship shape test_fused_dist.py fuzzes, here only to poke the
    dist gate matrix."""
    from spfft_tpu.parallel import make_distributed_plan, make_mesh
    from spfft_tpu.utils.workloads import sort_triplets_stick_major
    from test_distributed import split_by_sticks, split_planes
    from test_util import hermitian_triplets
    dims = (8, 6, DIM_Z)
    trips = hermitian_triplets(np.random.default_rng(11), dims)
    parts = [sort_triplets_stick_major(p, dims)
             for p in split_by_sticks(trips, dims, [2, 1])]
    return make_distributed_plan(
        TransformType.R2C, *dims, parts, split_planes(DIM_Z, [1, 1]),
        mesh=make_mesh(2), precision="single",
        use_pallas=kw.pop("use_pallas", True), **kw)


def test_dist_gate_no_matmul_dft(monkeypatch):
    """Without the mdft T pipeline both distributed twins decline with
    a recorded no_matmul_dft reason (the fused seam only exists on the
    matmul-DFT path)."""
    monkeypatch.delenv("SPFFT_TPU_FORCE_MATMUL_DFT", raising=False)
    monkeypatch.setenv("SPFFT_TPU_FUSED_INTERPRET", "1")
    plan = _dist_plan()
    assert not plan.fused_dist_active
    assert plan.fused_dist_fallback_reason == "no_matmul_dft"
    assert plan.fused_dist_fwd_fallback_reason == "no_matmul_dft"


def test_dist_fwd_twin_recompute_counter_recorded(fused_env, monkeypatch):
    """The forward twin's recompute_blowup decline records under the
    dist_fused_zdft_compress stage (declared in METRIC_SPECS) and the
    series surfaces through the Prometheus exposition — the runtime
    coverage for the new fallback stage label."""
    from spfft_tpu import obs
    monkeypatch.setenv("SPFFT_TPU_FUSED_RECOMPUTE_LIMIT", "1")
    before = obs.GLOBAL_COUNTERS.get(
        "spfft_plan_pallas_fallback_total",
        stage="dist_fused_zdft_compress", reason="recompute_blowup")
    # at the tightest limit this workload's stick-window recompute
    # blows the forward cost model (the backward stays active)
    plan = _dist_plan()
    assert plan.fused_dist_bwd_active
    assert plan.fused_dist_fwd_fallback_reason == "recompute_blowup"
    after = obs.GLOBAL_COUNTERS.get(
        "spfft_plan_pallas_fallback_total",
        stage="dist_fused_zdft_compress", reason="recompute_blowup")
    assert after == before + 1
    text = obs.prometheus_text()
    assert ('spfft_plan_pallas_fallback_total{reason="recompute_blowup"'
            ',stage="dist_fused_zdft_compress"}') in text


# -- runtime demotion ladder -------------------------------------------------

def test_runtime_launch_fault_demotes_one_direction(fused_env):
    """An injected kernel.launch fault during backward demotes EXACTLY
    the dec direction: the failing request itself succeeds on the
    unfused retry (bit-exact), the reason is recorded, forward stays
    fused, and the next backward runs unfused without re-failing."""
    from spfft_tpu import faults

    tr = _gappy_triplets()
    plan = _plan(tr)
    vals = _values(plan.index_plan.num_values)
    want = _unfused_backward(plan, vals)
    try:
        faults.arm(faults.FaultPlan(script="kernel.launch@1"))
        got = np.asarray(plan.backward(vals))
    finally:
        faults.disarm()
    np.testing.assert_array_equal(got, want)

    dem = plan.fused_demotions()
    assert set(dem) == {"dec"}
    assert "runtime" in dem["dec"]["reason"]
    assert "InjectedFault" in dem["dec"]["reason"]
    assert not dem["dec"]["permanent"]

    # the direction stays demoted and serving continues
    np.testing.assert_array_equal(np.asarray(plan.backward(vals)), want)
    assert plan.fused_demotions()["dec"]["unfused_ok"] >= 1


def test_runtime_demotion_reprobe_readmits(fused_env):
    """The bounded re-probe: a demoted direction banks
    FUSED_REPROBE_AFTER unfused successes, then the next dispatch runs
    the fused kernel again as a probe — success lifts the demotion."""
    from spfft_tpu import faults

    tr = _gappy_triplets()
    plan = _plan(tr)
    vals = _values(plan.index_plan.num_values)
    want = _unfused_backward(plan, vals)
    try:
        faults.arm(faults.FaultPlan(script="kernel.launch@1"))
        np.testing.assert_array_equal(
            np.asarray(plan.backward(vals)), want)
    finally:
        faults.disarm()
    assert set(plan.fused_demotions()) == {"dec"}

    for i in range(plan.FUSED_REPROBE_AFTER - 1):
        plan.backward(vals)
    rec = plan.fused_demotions()["dec"]
    assert rec["unfused_ok"] == plan.FUSED_REPROBE_AFTER - 1
    assert not rec["probing"]

    plan.backward(vals)  # banks the last unfused success
    assert plan.fused_demotions()["dec"]["probing"]

    # the probe call runs fused (no fault armed) and readmits
    got = np.asarray(plan.backward(vals))
    np.testing.assert_array_equal(got, want)
    assert plan.fused_demotions() == {}


def test_runtime_demotion_permanent_after_failed_probes(fused_env):
    """kernel.launch@* (the device really is broken): every re-probe
    fails, and after FUSED_REPROBE_MAX failed probes the demotion is
    permanent — no further probes, requests keep succeeding unfused."""
    from spfft_tpu import faults

    tr = _gappy_triplets()
    plan = _plan(tr)
    vals = _values(plan.index_plan.num_values)
    want = _unfused_backward(plan, vals)
    try:
        # @* only fires on FUSED dispatches; banked unfused calls never
        # reach the kernel.launch check, so the script stays armed
        faults.arm(faults.FaultPlan(script="kernel.launch@*"))
        np.testing.assert_array_equal(
            np.asarray(plan.backward(vals)), want)
        for probe in range(plan.FUSED_REPROBE_MAX):
            for _ in range(plan.FUSED_REPROBE_AFTER):
                plan.backward(vals)
            assert plan.fused_demotions()["dec"]["probing"]
            # the probe dispatch fails fused, re-demotes, serves unfused
            np.testing.assert_array_equal(
                np.asarray(plan.backward(vals)), want)
            rec = plan.fused_demotions()["dec"]
            assert rec["probes"] == probe + 1
    finally:
        faults.disarm()
    rec = plan.fused_demotions()["dec"]
    assert rec["permanent"]
    assert rec["probes"] == plan.FUSED_REPROBE_MAX

    # permanent: banking many successes never flips probing again
    for _ in range(plan.FUSED_REPROBE_AFTER + 1):
        plan.backward(vals)
    rec = plan.fused_demotions()["dec"]
    assert rec["permanent"] and not rec["probing"]
    np.testing.assert_array_equal(np.asarray(plan.backward(vals)), want)


def test_runtime_demotion_forward_direction_independent(fused_env):
    """Demoting cmp (forward) leaves dec (backward) fused: the ladder
    is strictly per-direction."""
    from spfft_tpu import faults

    tr = _gappy_triplets()
    plan = _plan(tr)
    vals = _values(plan.index_plan.num_values)
    space = plan.backward(vals)
    want = _unfused_forward(plan, space, scaled=False)
    try:
        faults.arm(faults.FaultPlan(script="kernel.launch@1"))
        got = np.asarray(plan.forward(space, scaling=Scaling.NONE))
    finally:
        faults.disarm()
    np.testing.assert_array_equal(got, want)
    assert set(plan.fused_demotions()) == {"cmp"}
    # backward still dispatches fused (no demotion recorded for dec)
    plan.backward(vals)
    assert set(plan.fused_demotions()) == {"cmp"}


def test_request_shaped_error_does_not_demote(fused_env):
    """A poisoned payload (request-attributed) must propagate untouched
    and never demote the kernel — demotion is for device faults only."""
    from spfft_tpu.errors import InvalidParameterError

    tr = _gappy_triplets()
    plan = _plan(tr)
    with pytest.raises(InvalidParameterError):
        plan.backward(np.zeros(3, np.complex64))
    assert plan.fused_demotions() == {}
