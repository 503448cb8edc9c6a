"""Unit tests for the Pallas windowed-gather kernel (interpret mode on CPU)
and its plan-time chunked table builder, including non-monotone index
orders (the generalized decomposition) and the disorder fallback."""

import numpy as np
import pytest

import jax.numpy as jnp

from spfft_tpu.ops import gather_kernel as gk


def run_gather(src: np.ndarray, idx: np.ndarray, valid: np.ndarray,
               k_rows: int = 0):
    t = gk.build_monotone_gather_tables(idx, valid, len(src), k_rows=k_rows)
    assert t is not None
    out = gk.run_monotone_gather(jnp.asarray(src, jnp.float32), t,
                                 interpret=True)
    return np.asarray(out), t


def test_expansion_pattern():
    """Decompress-style: masked slots, increments <= 1."""
    rng = np.random.default_rng(0)
    L = 3000
    mask = rng.random(L) < 0.6
    n_src = int(mask.sum())
    src = rng.random((n_src, 2)).astype(np.float32)
    idx = np.maximum(np.cumsum(mask) - 1, 0)
    out, _ = run_gather(src, idx, mask)
    ref = np.zeros((L, 2), np.float32)
    ref[mask] = src
    np.testing.assert_array_equal(out, ref)


def test_compaction_pattern():
    """Compress-style: strictly increasing indices with gaps."""
    rng = np.random.default_rng(1)
    M = 5000
    idx = np.sort(rng.choice(M, 2500, replace=False)).astype(np.int64)
    src = rng.random((M, 2)).astype(np.float32)
    out, _ = run_gather(src, idx, np.ones(len(idx), bool))
    np.testing.assert_array_equal(out, src[idx])


def test_single_tile_and_exact_tile():
    rng = np.random.default_rng(2)
    for L in (100, gk.TILE):
        idx = np.arange(L)
        src = rng.random((L, 2)).astype(np.float32)
        out, _ = run_gather(src, idx, np.ones(L, bool))
        np.testing.assert_array_equal(out, src)


def test_large_span_chunks():
    """A tile whose source span exceeds one K-row window splits into several
    accumulation chunks instead of falling back (the spherical-cutoff edge
    case: sparsely-filled sticks with regular gaps)."""
    rng = np.random.default_rng(3)
    idx = np.arange(gk.TILE) * 16  # gaps of 16 elements: 128-row span
    n_src = int(idx[-1]) + 1
    src = rng.random((n_src, 2)).astype(np.float32)
    out, t = run_gather(src, idx, np.ones(len(idx), bool), k_rows=8)
    assert len(t.row0) > t.num_tiles  # really multi-chunk
    np.testing.assert_array_equal(out, src[idx])


def test_extreme_gaps_fall_back():
    """~0.4% DMA efficiency (one useful value per two 128-lane rows) is past
    the chunk ceiling: the builder declines and the XLA gather runs."""
    idx = np.arange(gk.TILE) * 2 * gk.TILE_LANE
    n_src = int(idx[-1]) + 1
    assert gk.build_monotone_gather_tables(
        idx, np.ones(len(idx), bool), n_src, k_rows=8) is None


def test_chunking_across_k_choices():
    """The result is invariant to the chosen window height."""
    rng = np.random.default_rng(4)
    M = 40000
    idx = np.sort(rng.choice(M, 3000, replace=False)).astype(np.int64)
    src = rng.random((M, 2)).astype(np.float32)
    ref = src[idx]
    for k in (8, 32, 128):
        out, _ = run_gather(src, idx, np.ones(len(idx), bool), k_rows=k)
        np.testing.assert_array_equal(out, ref)


def test_non_monotone_small_supported():
    """Non-monotone indices within one window are handled directly."""
    idx = np.array([5, 3, 7])
    src = np.arange(20, dtype=np.float32).reshape(10, 2)
    out, _ = run_gather(src, idx, np.ones(3, bool))
    np.testing.assert_array_equal(out, src[idx])


def test_block_shuffled_order_supported():
    """Sticks visited in shuffled order (z-sorted within each) — the
    realistic unsorted layout: per-tile windows stay bounded, the kernel
    path stays active, results match."""
    rng = np.random.default_rng(11)
    n_sticks, dim_z = 80, 64
    order = rng.permutation(n_sticks)
    idx = (order[:, None] * dim_z + np.arange(dim_z)[None, :]).reshape(-1)
    src = rng.random((n_sticks * dim_z, 2)).astype(np.float32)
    out, t = run_gather(src, idx, np.ones(len(idx), bool))
    np.testing.assert_array_equal(out, src[idx])


def test_fully_random_large_order_falls_back():
    """A big fully-shuffled index set exceeds the chunk ceiling and the
    builder declines (the XLA gather is the better program there)."""
    rng = np.random.default_rng(12)
    L = 1 << 17
    idx = rng.permutation(L).astype(np.int64)
    assert gk.build_monotone_gather_tables(idx, np.ones(L, bool), L) is None


def test_gather_inputs_unsorted_values():
    """compression_gather_inputs for an unsorted value order: decompress
    indices point at each slot's position in the USER order; round-trip
    through both directions reproduces the values."""
    rng = np.random.default_rng(13)
    num_slots = 400
    vi = rng.choice(num_slots, 120, replace=False)  # unsorted, unique
    (dec_idx, occ), (cmp_idx, cmp_valid) = \
        gk.compression_gather_inputs(vi, num_slots)
    vals = rng.random(120)
    slots = np.where(occ, vals[dec_idx], 0.0)
    expect = np.zeros(num_slots)
    expect[vi] = vals
    np.testing.assert_array_equal(slots, expect)
    np.testing.assert_array_equal(slots[cmp_idx][cmp_valid], vals)


def test_plan_pallas_path_interpret():
    """The plan's Pallas decompress tables reproduce the XLA scatter result
    when run through the kernel in interpret mode."""
    from spfft_tpu import TransformType, make_local_plan
    rng = np.random.default_rng(3)
    n = 16
    triplets = []
    for x in range(n):
        for y in range(n):
            if (x * n + y) % 3 == 0:
                for z in range(n):
                    triplets.append((x, y, z))
    triplets = np.asarray(triplets, np.int32)
    vals = (rng.uniform(-1, 1, len(triplets))
            + 1j * rng.uniform(-1, 1, len(triplets))).astype(np.complex64)
    pl_plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                              precision="single", use_pallas=True)
    if pl_plan._pallas is None:
        pytest.skip("pallas tables unavailable for this index set")
    t = pl_plan._pallas["dec"]
    src_il = np.stack([vals.real, vals.imag], axis=-1).astype(np.float32)
    sticks = np.asarray(gk.run_monotone_gather(jnp.asarray(src_il), t,
                                               interpret=True))
    ip = pl_plan.index_plan
    # tables cover the padded stick rows (plan._s_pad); pad slots are zero
    expect = np.zeros((pl_plan._s_pad * n, 2), np.float32)
    expect[ip.value_indices] = src_il
    np.testing.assert_array_equal(sticks, expect)


def test_plan_compress_tables_interpret():
    """The compress-direction tables invert decompress: gathering occupied
    slots returns the original values."""
    from spfft_tpu import TransformType, make_local_plan
    rng = np.random.default_rng(7)
    n = 16
    # gappy sticks: only a couple of z values per stick — the edge-stick
    # pattern that used to overflow the fixed span bound
    triplets = []
    for x in range(n):
        for y in range(0, n, 2):
            for z in (0, 1, n - 1):
                triplets.append((x, y, z))
    triplets = np.asarray(triplets, np.int32)
    pl_plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                              precision="single", use_pallas=True)
    assert pl_plan._pallas is not None and pl_plan._pallas["cmp"] is not None
    ip = pl_plan.index_plan
    vals_il = rng.random((ip.num_values, 2)).astype(np.float32)
    slots = np.zeros((ip.num_sticks * n, 2), np.float32)
    slots[ip.value_indices] = vals_il
    out = np.asarray(gk.run_monotone_gather(
        jnp.asarray(slots), pl_plan._pallas["cmp"], interpret=True))
    np.testing.assert_array_equal(out, vals_il)


def test_plan_shuffled_triplets_kernel_path():
    """Shuffled triplet order (not stick-major) still builds Pallas tables
    via the generalized windowed decomposition; both direction kernels
    reproduce the XLA scatter/gather semantics for the USER's order."""
    from spfft_tpu import TransformType, make_local_plan
    rng = np.random.default_rng(21)
    n = 12
    triplets = [(x, y, z) for x in range(n) for y in range(n)
                if (x + y) % 2 == 0 for z in range(n)]
    triplets = np.asarray(triplets, np.int32)
    triplets = triplets[rng.permutation(len(triplets))]
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single", use_pallas=True)
    assert plan._pallas is not None
    assert plan._pallas["dec"] is not None
    assert plan._pallas["cmp"] is not None
    ip = plan.index_plan
    vals_il = rng.random((ip.num_values, 2)).astype(np.float32)
    # decompress: slots in plan storage order from user-order values
    sticks = np.asarray(gk.run_monotone_gather(
        jnp.asarray(vals_il), plan._pallas["dec"], interpret=True))
    expect = np.zeros((plan._s_pad * n, 2), np.float32)
    expect[ip.value_indices] = vals_il
    np.testing.assert_array_equal(sticks, expect)
    # compress: user-order values back out of the slots
    out = np.asarray(gk.run_monotone_gather(
        jnp.asarray(expect), plan._pallas["cmp"], interpret=True))
    np.testing.assert_array_equal(out, vals_il)


def test_src_rows_covers_whole_source():
    """Regression: compress-direction tables must cover the full source
    array even when the last referenced index is far before its end
    (planar_from_interleaved zero-pads to src_rows * 128)."""
    # values only at the start of a 2048-slot source
    idx = np.arange(50)
    t = gk.build_monotone_gather_tables(idx, np.ones(50, bool), 2048)
    assert t is not None
    assert t.src_rows * gk.TILE_LANE >= 2048
    src = np.random.default_rng(0).random((2048, 2)).astype(np.float32)
    out = np.asarray(gk.run_monotone_gather(jnp.asarray(src), t,
                                            interpret=True))
    np.testing.assert_array_equal(out, src[idx])


def test_segmented_launch_matches_single(monkeypatch):
    """Past the SMEM chunk budget the gather splits into tile-aligned
    launches; force a tiny limit and check the concatenated segments equal
    the single-launch result."""
    monkeypatch.setattr(gk, "SEG_CHUNK_LIMIT", 7)
    rng = np.random.default_rng(41)
    M = 30000
    idx = np.sort(rng.choice(M, 15000, replace=False)).astype(np.int64)
    src = rng.random((M, 2)).astype(np.float32)
    t = gk.build_monotone_gather_tables(idx, np.ones(len(idx), bool), M)
    assert t is not None and len(t.segs) >= 2
    # segments are tile-aligned and cover everything exactly once
    assert t.segs[0][0] == 0 and t.segs[-1][1] == len(t.row0)
    assert t.segs[0][2] == 0 and t.segs[-1][3] == t.num_tiles
    for (a, b) in zip(t.segs, t.segs[1:]):
        assert a[1] == b[0] and a[3] == b[2]
    out = np.asarray(gk.run_monotone_gather(jnp.asarray(src), t,
                                            interpret=True))
    np.testing.assert_array_equal(out, src[idx])
    # batched source through the same segments: per-batch results equal
    src_b = np.stack([src, src * 2, src[::-1]])
    re, im = gk.planar_from_interleaved(jnp.asarray(src_b), t.src_rows)
    out_re, out_im = gk.monotone_gather(
        re, im, jnp.asarray(t.row0), jnp.asarray(t.out_tile),
        jnp.asarray(t.first), jnp.asarray(t.packed),
        span_rows=t.span_rows, src_rows=t.src_rows,
        num_tiles=t.num_tiles, interpret=True, segs=t.segs)
    out_b = np.asarray(gk.interleaved_from_planar(out_re, out_im,
                                                  t.num_out))
    for b in range(3):
        np.testing.assert_array_equal(out_b[b], src_b[b][idx])
    # distributed builds refuse segmentation (uniform stacked tables)
    assert gk.build_monotone_gather_tables(
        idx, np.ones(len(idx), bool), M, allow_segments=False) is None


def test_forced_pallas_on_double_rejected():
    from spfft_tpu import InvalidParameterError, TransformType, make_local_plan
    with pytest.raises(InvalidParameterError):
        make_local_plan(TransformType.C2C, 4, 4, 4, np.array([[0, 0, 0]]),
                        precision="double", use_pallas=True)


# -- wide-kernel (P tiles per grid step) tests --------------------------------

def run_wide(src: np.ndarray, idx: np.ndarray, valid: np.ndarray, **kw):
    t = gk.build_wide_gather_tables(idx, valid, len(src), **kw)
    assert t is not None
    out = gk.run_gather_values(jnp.asarray(src, jnp.float32), t,
                               interpret=True)
    return np.asarray(out), t


@pytest.mark.parametrize("fill", [0.55, 0.9])
def test_wide_expansion_pattern(fill):
    """Decompress-style: masked slots, increments <= 1 — two fill levels
    exercise different kp/K auto choices."""
    rng = np.random.default_rng(10)
    L = 40_000
    mask = rng.random(L) < fill
    n_src = int(mask.sum())
    src = rng.random((n_src, 2)).astype(np.float32)
    idx = np.maximum(np.cumsum(mask) - 1, 0)
    out, t = run_wide(src, idx, mask)
    assert isinstance(t, gk.WideGatherTables)
    ref = np.zeros((L, 2), np.float32)
    ref[mask] = src
    np.testing.assert_array_equal(out, ref)


def test_wide_compaction_pattern():
    rng = np.random.default_rng(11)
    M = 80_000
    idx = np.sort(rng.choice(M, 40_000, replace=False)).astype(np.int64)
    src = rng.random((M, 2)).astype(np.float32)
    out, t = run_wide(src, idx, np.ones(len(idx), bool))
    np.testing.assert_array_equal(out, src[idx])


def test_wide_multi_round_cover():
    """Per-tile spans exceeding kp force multiple rounds per super-tile
    (the revisiting-accumulation path)."""
    rng = np.random.default_rng(12)
    L = 3 * gk.WIDE_P * gk.TILE
    idx = (np.arange(L, dtype=np.int64) * 7) % (L // 2)  # scattered-ish
    idx = np.sort(idx.reshape(-1, gk.TILE), axis=1).reshape(-1)
    src = rng.random((L // 2, 2)).astype(np.float32)
    t = gk.build_wide_gather_tables(idx, np.ones(L, bool), L // 2,
                                    kp_rows=8)
    if t is None:
        pytest.skip("cover declined for this pattern")
    assert t.row0.shape[0] > t.num_super  # at least one multi-chunk tile
    out = np.asarray(gk.run_gather_values(
        jnp.asarray(src, jnp.float32), t, interpret=True))
    np.testing.assert_array_equal(out, src[idx])


def test_wide_block_shuffled_order():
    """Locally-coherent but globally shuffled order stays on the wide path."""
    rng = np.random.default_rng(13)
    M = 120_000
    n = 57_344  # 14 * 4096
    idx = np.sort(rng.choice(M, n, replace=False)).astype(np.int64)
    idx = idx.reshape(-1, 4096)[rng.permutation(n // 4096)].reshape(-1)
    src = rng.random((M, 2)).astype(np.float32)
    out, t = run_wide(src, idx, np.ones(n, bool))
    np.testing.assert_array_equal(out, src[idx])


def test_wide_random_order_falls_back():
    rng = np.random.default_rng(14)
    idx = rng.integers(0, 2_000_000, 60_000)
    assert gk.build_wide_gather_tables(
        idx, np.ones(len(idx), bool), 2_000_000) is None
    # build_best falls through to narrow, then None
    assert gk.build_best_gather_tables(
        idx, np.ones(len(idx), bool), 2_000_000) is None


def test_wide_no_valid_slots_zeroes_output():
    out, t = run_wide(np.ones((64, 2), np.float32),
                      np.zeros(5000, np.int64), np.zeros(5000, bool))
    np.testing.assert_array_equal(out, np.zeros((5000, 2), np.float32))


def test_wide_duplicate_indices():
    rng = np.random.default_rng(15)
    idx = np.repeat(np.arange(3000), 3)[:8192]
    src = rng.random((3000, 2)).astype(np.float32)
    out, _ = run_wide(src, idx, np.ones(8192, bool))
    np.testing.assert_array_equal(out, src[idx])


def test_wide_forced_geometry_rebuild():
    """Forcing common (kp, K) — the distributed uniformity pass — keeps
    results exact."""
    rng = np.random.default_rng(16)
    M = 60_000
    idx = np.sort(rng.choice(M, 30_000, replace=False)).astype(np.int64)
    src = rng.random((M, 2)).astype(np.float32)
    t0 = gk.build_wide_gather_tables(idx, np.ones(len(idx), bool), M)
    t1 = gk.build_wide_gather_tables(idx, np.ones(len(idx), bool), M,
                                     kp_rows=min(t0.kp_rows + 4, 32),
                                     k_rows=t0.span_rows + 8)
    # out-of-range forced kp is rejected, not silently overflowed into the
    # packed word's valid bit
    with pytest.raises(ValueError):
        gk.build_wide_gather_tables(idx, np.ones(len(idx), bool), M,
                                    kp_rows=40)
    out = np.asarray(gk.run_gather_values(jnp.asarray(src, jnp.float32),
                                          t1, interpret=True))
    np.testing.assert_array_equal(out, src[idx])


def test_wide_padded_tables_dummy_block():
    """pad_wide_tables_to appends no-op chunks targeting a dummy super-tile;
    running with num_super + 1 leaves the real output prefix unchanged.
    Its ``sub`` is the kernel's 1-D table, ``P // 4`` words a chunk."""
    rng = np.random.default_rng(17)
    M = 40_000
    idx = np.sort(rng.choice(M, 20_000, replace=False)).astype(np.int64)
    src = rng.random((M, 2)).astype(np.float32)
    t = gk.build_wide_gather_tables(idx, np.ones(len(idx), bool), M)
    C, w = t.row0.shape[0], t.p_tiles // 4
    padded = gk.pad_wide_tables_to(t, C + 7)
    assert padded[1].shape == ((C + 7) * w,)
    np.testing.assert_array_equal(padded[1][:C * w], t.sub.reshape(-1))
    assert not padded[1][C * w:].any()
    assert gk.pad_wide_tables_to(t, C)[1].shape == (C * w,)
    re, im = gk.planar_from_interleaved(jnp.asarray(src, jnp.float32),
                                        t.src_rows)
    out_re, out_im = gk.wide_gather(
        re, im, *(jnp.asarray(a) for a in padded), span_rows=t.span_rows,
        kp_rows=t.kp_rows, p_tiles=t.p_tiles, src_rows=t.src_rows,
        num_super=t.num_super + 1, interpret=True)
    got = gk.interleaved_from_planar(out_re, out_im, t.num_out)
    np.testing.assert_array_equal(np.asarray(got), src[idx])


def _limit_chunks(monkeypatch, chunks, p_tiles=gk.WIDE_P):
    """Shrink the SMEM word budget so one wide launch holds ``chunks``."""
    monkeypatch.setattr(gk, "SMEM_TABLE_WORDS", chunks * (3 + p_tiles // 4))
    assert gk.wide_chunk_limit(p_tiles) == chunks


def test_wide_chunk_limit_from_smem_budget():
    """A wide launch's scalar-prefetch tables take ``3 + P // 4`` SMEM
    words a chunk (row0, out_tile, first and the 1-D sub): 39,321
    chunks at P=8 fit the budget, which holds the 768^3 shard's 6,913."""
    assert gk.wide_chunk_limit() == gk.SMEM_TABLE_WORDS // 5 == 39_321
    assert gk.wide_chunk_limit(16) == gk.SMEM_TABLE_WORDS // 7
    assert gk.wide_chunk_limit() >= 6_913


def test_wide_segments(monkeypatch):
    """Chunk counts past the SMEM budget's chunk limit run as multiple
    tile-aligned launches with identical results; each segment's device
    ``sub`` is the 1-D slice of its chunks."""
    rng = np.random.default_rng(18)
    L = 12 * gk.WIDE_P * gk.TILE
    idx = np.arange(L, dtype=np.int64)
    src = rng.random((L, 2)).astype(np.float32)
    with monkeypatch.context() as m:
        _limit_chunks(m, 5)
        t = gk.build_wide_gather_tables(idx, np.ones(L, bool), L)
    assert t is not None and len(t.segs) >= 2
    assert all(c1 - c0 <= 5 for c0, c1, _, _ in t.segs)
    w = t.p_tiles // 4
    for (c0, c1, _, _), tabs in zip(t.segs, gk.gather_device_tables(t)):
        np.testing.assert_array_equal(np.asarray(tabs[1]),
                                      t.sub[c0:c1].reshape(-1))
        assert tabs[1].shape == ((c1 - c0) * w,)
    out = np.asarray(gk.run_gather_values(jnp.asarray(src, jnp.float32), t,
                                          interpret=True))
    np.testing.assert_array_equal(out, src)
    # the jitted entry slices the 1-D sub by chunk too
    re, im = gk.planar_from_interleaved(jnp.asarray(src, jnp.float32),
                                        t.src_rows)
    out_re, out_im = gk.wide_gather(
        re, im, jnp.asarray(t.row0), jnp.asarray(t.sub.reshape(-1)),
        jnp.asarray(t.out_tile), jnp.asarray(t.first),
        jnp.asarray(t.packed), span_rows=t.span_rows, kp_rows=t.kp_rows,
        p_tiles=t.p_tiles, src_rows=t.src_rows, num_super=t.num_super,
        interpret=True, segs=t.segs)
    got = gk.interleaved_from_planar(out_re, out_im, t.num_out)
    np.testing.assert_array_equal(np.asarray(got), src)


def test_wide_batched_split_over_step_budget(monkeypatch):
    """A batched launch whose B*C exceeds the chunk limit splits into
    per-slab launches (total-grid-step compile-crash guard)."""
    rng = np.random.default_rng(19)
    L = 4 * gk.WIDE_P * gk.TILE
    idx = np.arange(L, dtype=np.int64)
    src = rng.random((3, L, 2)).astype(np.float32)
    t = gk.build_wide_gather_tables(idx, np.ones(L, bool), L)
    assert t is not None
    re, im = gk.planar_from_interleaved(jnp.asarray(src), t.src_rows)
    with monkeypatch.context() as m:
        _limit_chunks(m, 2 * t.row0.shape[0])  # B=3 crosses, C alone not
        out_re, out_im = gk.run_gather(re, im, gk.gather_device_tables(t),
                                       t, interpret=True)
    got = np.asarray(gk.interleaved_from_planar(out_re, out_im, t.num_out))
    np.testing.assert_array_equal(got, src)
