"""Parity tests: native C++ planner vs the NumPy reference semantics.

The NumPy path in spfft_tpu.indexing is the executable specification of the
reference index conversion (reference: src/compression/indices.hpp:120-186);
the native library must agree bit-for-bit on valid inputs and raise the same
exception types on invalid ones.
"""

import numpy as np
import pytest

from spfft_tpu import native
from spfft_tpu.errors import InvalidIndicesError
from spfft_tpu.indexing import (build_index_plan, inverse_col_map,
                                inverse_slot_map)
from spfft_tpu.types import TransformType

from test_util import center_triplets, random_sparse_triplets


def _make_triplets(rng, dims, centered, hermitian):
    """Random triplet set valid for the given mode: hermitian restricts
    storage x to [0, dim_x//2]; centered converts to negative-frequency
    indexing (x stays non-negative for hermitian)."""
    t = random_sparse_triplets(rng, dims)
    if hermitian:
        t = t[t[:, 0] <= dims[0] // 2]
        if t.shape[0] == 0:
            t = np.array([[0, 0, 0]], np.int32)
    if centered:
        c = center_triplets(t, dims)
        if hermitian:
            c[:, 0] = t[:, 0]
        t = c
    return t

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native planner unavailable")


def _numpy_reference(hermitian, dims, triplets):
    """The pure-NumPy conversion, bypassing the native fast path."""
    dim_x, dim_y, dim_z = dims
    x, y, z = (triplets[:, 0].astype(np.int64),
               triplets[:, 1].astype(np.int64),
               triplets[:, 2].astype(np.int64))
    xs = np.where(x < 0, x + dim_x, x)
    ys = np.where(y < 0, y + dim_y, y)
    zs = np.where(z < 0, z + dim_z, z)
    keys = xs * dim_y + ys
    stick_keys, stick_ids = np.unique(keys, return_inverse=True)
    value_indices = stick_ids.astype(np.int64) * dim_z + zs
    return value_indices.astype(np.int32), stick_keys.astype(np.int32)


DIMS = [(1, 1, 1), (2, 3, 4), (11, 12, 13), (13, 11, 12), (32, 32, 32),
        (100, 13, 2)]


@pytest.mark.parametrize("dims", DIMS)
@pytest.mark.parametrize("centered", [False, True])
@pytest.mark.parametrize("hermitian", [False, True])
def test_plan_indices_parity(dims, centered, hermitian):
    rng = np.random.default_rng(hash((dims, centered, hermitian)) % 2**32)
    triplets = _make_triplets(rng, dims, centered, hermitian)
    res = native.plan_indices(hermitian, *dims, triplets)
    assert res is not None
    vi, keys, got_centered = res
    ref_vi, ref_keys = _numpy_reference(hermitian, dims, triplets)
    np.testing.assert_array_equal(vi, ref_vi)
    np.testing.assert_array_equal(keys, ref_keys)
    assert got_centered == bool((triplets < 0).any())


def test_plan_indices_empty():
    res = native.plan_indices(False, 4, 4, 4,
                              np.empty((0, 3), np.int64))
    vi, keys, centered = res
    assert vi.size == 0 and keys.size == 0 and not centered


@pytest.mark.parametrize("bad", [
    np.array([[4, 0, 0]]),    # x beyond dim-1
    np.array([[0, -3, 0]]),   # centered y below floor(4/2) - 4 + 1 = -1
    np.array([[0, 0, 99]]),   # z far out of range
])
def test_plan_indices_bounds(bad):
    with pytest.raises(InvalidIndicesError):
        build_index_plan(TransformType.C2C, 4, 4, 4, bad)


def test_hermitian_negative_x_folds_onto_mirror():
    # round 15: negative-x r2c triplets are no longer rejected — they
    # fold onto the conjugate mirror stick (value_conj marks the read
    # as conjugated), so full-sphere inputs build trimmed plans
    p = build_index_plan(TransformType.R2C, 8, 8, 8,
                         np.array([[-1, 0, 0]]))
    assert p.stick_x.tolist() == [1] and p.stick_y.tolist() == [0]
    assert p.value_conj is not None and p.value_conj.tolist() == [True]
    # out-of-range x is still a bounds error after the fold
    with pytest.raises(InvalidIndicesError):
        build_index_plan(TransformType.R2C, 8, 8, 8,
                         np.array([[-5, 0, 0]]))


def test_inverse_map_parity():
    rng = np.random.default_rng(7)
    n_slots = 1000
    idx = rng.choice(n_slots, size=300, replace=False).astype(np.int32)
    got = native.inverse_map(idx, n_slots, 300)
    ref = np.full(n_slots, 300, np.int32)
    ref[idx] = np.arange(300, dtype=np.int32)
    np.testing.assert_array_equal(got, ref)


def test_inverse_map_duplicates_last_wins():
    idx = np.array([5, 5, 2, 5], np.int32)
    got = native.inverse_map(idx, 8, 4)
    assert got[5] == 3 and got[2] == 2
    assert all(got[i] == 4 for i in (0, 1, 3, 4, 6, 7))


def test_full_plan_through_native_matches_numpy(monkeypatch):
    """build_index_plan with and without the native path must agree."""
    rng = np.random.default_rng(3)
    dims = (12, 13, 11)
    triplets = random_sparse_triplets(rng, dims)
    plan_native = build_index_plan(TransformType.C2C, *dims, triplets)
    monkeypatch.setenv("SPFFT_TPU_NO_NATIVE", "1")
    plan_numpy = build_index_plan(TransformType.C2C, *dims, triplets)
    np.testing.assert_array_equal(plan_native.value_indices,
                                  plan_numpy.value_indices)
    np.testing.assert_array_equal(plan_native.stick_keys,
                                  plan_numpy.stick_keys)
    np.testing.assert_array_equal(plan_native.slot_src, plan_numpy.slot_src)
    np.testing.assert_array_equal(plan_native.col_inv, plan_numpy.col_inv)


def test_native_wide_tables_parity(monkeypatch):
    """The C++ wide-gather cover produces IDENTICAL tables to the NumPy
    builder (the executable specification) — geometry choice, chunk order,
    packed words, byte-packed sub offsets, everything."""
    _check_wide_parity(monkeypatch, p_tiles=8)


@pytest.mark.parametrize("p_tiles", [16, 64])
def test_native_wide_tables_parity_super_tile(monkeypatch, p_tiles):
    """The same parity at the fused z kernels' super-tile widths (16
    tiles at dim_z 256, 64 the most the fused geometry takes)."""
    _check_wide_parity(monkeypatch, p_tiles=p_tiles)


def _check_wide_parity(monkeypatch, p_tiles):
    from spfft_tpu import native
    from spfft_tpu.ops import gather_kernel as gk

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(44)

    def cases():
        L, num_src = 50_000, 34_000
        steps = (rng.random(L) < 0.67).astype(np.int64)
        yield ("decompress", np.minimum(np.cumsum(steps) - steps,
                                        num_src - 1),
               steps.astype(bool), num_src, {})
        idx2 = np.sort(rng.choice(120_000, 60_000, replace=False))
        yield ("compress", idx2, np.ones(60_000, bool), 120_000, {})
        n = 57_344
        idx3 = np.sort(rng.choice(99_000, n, replace=False))
        idx3 = idx3.reshape(-1, 4096)[rng.permutation(n // 4096)].reshape(-1)
        yield ("block-shuffled", idx3, np.ones(n, bool), 99_000, {})
        yield ("tiny", np.arange(100), np.ones(100, bool), 100, {})
        yield ("forced", idx2, np.ones(60_000, bool), 120_000,
               {"kp_rows": 16, "k_rows": 128})

    for name, idx, valid, num_src, kw in cases():
        kw = dict(kw, p_tiles=p_tiles)
        t_nat = gk.build_wide_gather_tables(idx, valid, num_src, **kw)
        with monkeypatch.context() as m:
            m.setattr(native, "wide_gather_tables",
                      lambda *a, **k: None)
            t_py = gk.build_wide_gather_tables(idx, valid, num_src, **kw)
        assert (t_nat is None) == (t_py is None), name
        if t_nat is None:
            continue
        for field in ("num_out", "num_super", "src_rows", "span_rows",
                      "kp_rows", "p_tiles", "segs"):
            assert getattr(t_nat, field) == getattr(t_py, field), \
                f"{name}.{field}"
        for field in ("row0", "sub", "out_tile", "first", "packed"):
            a, b = getattr(t_nat, field), getattr(t_py, field)
            assert a.dtype == b.dtype, f"{name}.{field} dtype"
            np.testing.assert_array_equal(a, b, err_msg=f"{name}.{field}")
        # the kernel's 1-D sub, as both builds commit it: chunk g's
        # byte-packed words at g * (P // 4)
        C, w = t_nat.row0.shape[0], p_tiles // 4
        assert t_nat.sub.shape == t_py.sub.shape == (C, w), name
        for dn, dp in zip(gk.gather_device_tables(t_nat),
                          gk.gather_device_tables(t_py)):
            for a, b in zip(dn, dp):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)
        flat = np.concatenate([np.asarray(d[1])
                               for d in gk.gather_device_tables(t_nat)])
        assert flat.shape == (C * w,), name
        g, j = np.arange(C)[:, None], np.arange(p_tiles)
        np.testing.assert_array_equal(
            (flat[g * w + j // 4] >> (8 * (j % 4))) & 0xFF,
            (t_py.sub[:, j // 4] >> (8 * (j % 4))) & 0xFF, err_msg=name)


def test_native_wide_tables_blowup_parity(monkeypatch):
    """Random order falls back identically (native raises the internal
    blowup signal exactly where the NumPy cover returns None)."""
    from spfft_tpu import native
    from spfft_tpu.ops import gather_kernel as gk

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(45)
    idx = rng.integers(0, 2_000_000, 60_000)
    assert gk.build_wide_gather_tables(idx, np.ones(60_000, bool),
                                       2_000_000) is None
    with monkeypatch.context() as m:
        m.setattr(native, "wide_gather_tables", lambda *a, **k: None)
        assert gk.build_wide_gather_tables(idx, np.ones(60_000, bool),
                                           2_000_000) is None


def test_native_compression_inputs_parity(monkeypatch):
    """Native occupied/forward-fill matches the NumPy specification,
    including duplicates (last wins), leading gaps, and empty slots."""
    from spfft_tpu import native
    from spfft_tpu.ops import gather_kernel as gk

    if not native.available():
        pytest.skip("native library unavailable")
    rng = np.random.default_rng(46)
    for trial in range(5):
        num_slots = int(rng.integers(50, 5000))
        n = int(rng.integers(1, num_slots))
        vi = rng.integers(0, num_slots, n)  # duplicates likely
        nat = gk.compression_gather_inputs(vi, num_slots)
        with monkeypatch.context() as m:
            m.setattr(native, "compression_inputs", lambda *a: None)
            py = gk.compression_gather_inputs(vi, num_slots)
        for got, want in zip(nat, py):
            np.testing.assert_array_equal(got[0], want[0])
            np.testing.assert_array_equal(got[1], want[1])
