"""Pallas TPU kernel for windowed gathers — the sparse compression hot path.

The decompress/compress stages move millions of sparse values between the
user's value array and the packed stick array (reference:
src/compression/compression_host.hpp, compression_gpu kernels). XLA lowers
arbitrary-index gathers on TPU to near-serial element loads (~80 ms for 13M
elements on v5e — measured), two orders of magnitude off HBM bandwidth.

The kernel computes ``out[j] = src[idx[j]] * mask[j]`` for an *arbitrary*
plan-time-constant index list by decomposing it into

  1. contiguous DMAs of K-row source windows (double-buffered across grid
     steps),
  2. K in-register row gathers via Mosaic's ``dynamic_gather``
     (``take_along_axis`` along lanes, indices < 128),
  3. a select-accumulate over the K candidate rows.

Each 1024-slot output tile owns one *chunk* per distinct K-row source
window its indices touch; a tile's chunks are consecutive grid steps that
accumulate into the same output block (the standard Pallas revisiting-
reduction pattern). When the value order is stick-major and z-ascending —
the layout the reference itself recommends for performance
(docs/source/details.rst "Data Distribution") — indices are monotone, every
tile touches the minimal number of windows, and the decomposition is
optimal. Locally-coherent but unsorted orders (shuffled sticks, z-sorted
within each) just emit more chunks and stay on the fast path; a truly
random order would blow the chunk count up, so the builder falls back
(returns None) when the modelled DMA traffic exceeds the measured XLA
gather cost. K is chosen per plan from the window-count distribution.

Per-chunk selector tables are precomputed on host at plan time and packed
into one int32 word per output slot: lane (bits 0-6), window row (bits 7-19),
validity (bit 20). Data is planar (separate real/imag (rows, 128) arrays):
the TPU lane dimension must be the innermost 128 and complex dtypes cannot
cross the pallas boundary.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_SUB = 8
TILE_LANE = 128
TILE = TILE_SUB * TILE_LANE  # output slots per tile

#: Candidate source-window heights (rows) for the chunk decomposition; the
#: builder picks the one minimising modelled DMA + compute cost.
K_CANDIDATES = (8, 16, 32, 64, 128)

_LANE_BITS = 7
_ROW_SHIFT = _LANE_BITS
_VALID_SHIFT = 20
_ROW_MASK = (1 << (_VALID_SHIFT - _ROW_SHIFT)) - 1

# Wide-kernel packed-word layout (int16): lane 0-6, row-in-subwindow 7-11,
# valid 12. kp_rows <= 32 so the row field needs only 5 bits.
_W_VALID_SHIFT = 12
_W_ROW_MASK = (1 << (_W_VALID_SHIFT - _ROW_SHIFT)) - 1


#: Max chunks per kernel launch: the three scalar-prefetch tables live in
#: SMEM (1 MB on v5e); 3 arrays x 4 B x 70k = 840 KB leaves headroom for
#: spills. Larger chunk counts are split into tile-aligned segments.
SEG_CHUNK_LIMIT = 70_000


@dataclasses.dataclass(frozen=True)
class MonotoneGatherTables:
    """Plan-time tables for one windowed gather direction."""

    row0: np.ndarray      # (C,) int32 — first source row of each chunk's DMA
    out_tile: np.ndarray  # (C,) int32 — output tile the chunk accumulates into
    first: np.ndarray     # (C,) int32 — 1 on a tile's first chunk
    packed: np.ndarray    # (C, 8, 128) int32 — lane | row << 7 | valid << 20
    num_out: int          # valid output slots (<= num_tiles * TILE)
    num_tiles: int        # G: output tiles
    src_rows: int         # M: padded source array rows
    span_rows: int        # K: DMA window height
    segs: tuple = ()      # ((c0, c1, t0, t1), ...) — tile-aligned launch
                          # segments keeping scalar-prefetch SMEM in budget;
                          # empty = single launch


def _tile_aligned_segments(first: np.ndarray, out_tile: np.ndarray,
                           num_tiles: int, limit: int) -> tuple:
    """Split chunk range [0, C) into segments of <= ``limit`` chunks whose
    boundaries land on a tile's FIRST chunk (so no output tile spans two
    launches and the revisiting accumulation stays within one call)."""
    C = int(first.shape[0])
    if C <= limit:
        return ()
    starts = np.flatnonzero(first == 1)
    segs = []
    c0 = 0
    while c0 < C:
        if C - c0 <= limit:
            c1 = C
        else:
            cand = starts[(starts > c0) & (starts <= c0 + limit)]
            if len(cand) == 0:  # one tile alone exceeds the limit: give up
                return None
            c1 = int(cand[-1])
        t0 = int(out_tile[c0])
        t1 = int(out_tile[c1 - 1]) + 1  # c1 > c0 always: cand > c0 or C
        segs.append((c0, c1, t0, t1))
        c0 = c1
    assert segs[-1][3] == num_tiles
    return tuple(segs)


#: Fallback ceiling: the kernel's cost scales with the chunk count C while
#: the XLA gather's scales with the output size (~G tiles); the measured
#: kernel advantage at C ≈ G is ~6x (scripts/sweep.py, 256^3 on v5e), so
#: past C ≈ 6G the decomposition stops paying for itself.
_CHUNK_BLOWUP_LIMIT = 6


def build_monotone_gather_tables(idx: np.ndarray, valid: np.ndarray,
                                 num_src: int, k_rows: int = 0,
                                 allow_segments: bool = True):
    """Build tables for ``out[j] = src[idx[j]] * valid[j]``.

    Args:
      idx: (L,) source indices, in any order (any in-range value where
        invalid). Monotone (non-decreasing) indices give the minimal chunk
        count; arbitrary order works as long as each 1024-slot output tile
        touches a bounded set of K-row source windows.
      valid: (L,) bool.
      num_src: size of the source array.
      k_rows: force the DMA window height (0 = choose from the window-count
        distribution).
      allow_segments: past SEG_CHUNK_LIMIT chunks the gather runs as
        several tile-aligned launches (scalar-prefetch SMEM budget);
        ``False`` declines instead — the stacked-uniform-table layout of
        distributed plans cannot segment per shard.
    Returns:
      MonotoneGatherTables, or None if ``idx`` is empty or so disordered
      that the chunk decomposition would be slower than the XLA gather
      (caller falls back).
    """
    L = int(idx.shape[0])
    if L == 0:
        return None
    idx = np.asarray(idx, np.int64)
    G = -(-L // TILE)
    pad = G * TILE - L
    idx_p = np.concatenate([idx, np.full(pad, idx[-1], np.int64)])
    valid_p = np.concatenate([np.asarray(valid, bool), np.zeros(pad, bool)])
    tiles = idx_p.reshape(G, TILE)
    rows = tiles // TILE_LANE                      # (G, TILE)
    rows_sorted = np.sort(rows, axis=1)            # per-tile, for windowing

    def chunks_per_tile(k):
        win = rows_sorted // k
        return 1 + (np.diff(win, axis=1) != 0).sum(axis=1)

    if k_rows:
        K = int(k_rows)
    else:
        # cost ~ chunks * (K DMA rows + fixed per-step overhead). The
        # overhead term is large: each grid step costs ~400-500 ns of
        # scalar bookkeeping + DMA issue regardless of K (measured at
        # 256^3: K=8 pair 30.7 ms vs K=32 23.9 ms) — weight it like ~64
        # DMA rows so the chooser trades window waste against step count.
        K = min(K_CANDIDATES,
                key=lambda k: int(chunks_per_tile(k).sum()) * (k + 64))
    win_sorted = rows_sorted // K
    # one chunk per (tile, distinct window); windows ascend within a tile so
    # a tile's chunks are consecutive grid steps (the revisiting pattern)
    new_win = np.concatenate([np.ones((G, 1), bool),
                              np.diff(win_sorted, axis=1) != 0], axis=1)
    chunks_t = new_win.sum(axis=1).astype(np.int64)
    C = int(chunks_t.sum())
    if C > _CHUNK_BLOWUP_LIMIT * G + 64:
        return None  # too disordered: XLA gather is the better program
    tile_of = np.repeat(np.arange(G, dtype=np.int64), chunks_t)
    win_ids = win_sorted[new_win].astype(np.int64)  # (C,) window per chunk
    win32 = (rows // K).astype(np.int32)  # int32 up front: the (C, TILE)
    rows32 = rows.astype(np.int32)  # temporaries are the peak allocation
    wc = win_ids[:, None].astype(np.int32)
    in_win = win32[tile_of] == wc                        # (C, TILE)
    row_in = np.clip(rows32[tile_of] - wc * K, 0, K - 1)
    m = in_win & valid_p.reshape(G, TILE)[tile_of]
    lanes = (tiles % TILE_LANE).astype(np.int32)  # (G, TILE), not (C, TILE)
    packed = (lanes[tile_of]
              | (row_in << _ROW_SHIFT)
              | (m.astype(np.int32) << _VALID_SHIFT))
    row0 = (win_ids * K).astype(np.int32)
    # first chunk of each tile initialises its output block
    first = np.zeros(C, np.int32)
    first[np.cumsum(chunks_t) - chunks_t] = 1
    # Cover the whole source array, not just the last referenced span: the
    # planar source is built by zero-PADDING the (num_src,) array to
    # src_rows * 128, which requires src_rows * 128 >= num_src even when the
    # trailing source region is never referenced.
    src_rows = max(int(row0.max()) + K, -(-int(num_src) // TILE_LANE))
    out_tile32 = tile_of.astype(np.int32)
    segs = _tile_aligned_segments(first, out_tile32, G, SEG_CHUNK_LIMIT)
    if segs is None or (segs and not allow_segments):
        return None
    return MonotoneGatherTables(
        row0=row0,
        out_tile=out_tile32,
        first=first,
        packed=packed.reshape(C, TILE_SUB, TILE_LANE),
        num_out=L, num_tiles=G, src_rows=src_rows, span_rows=K,
        segs=segs)


#: Wide-kernel geometry: output tiles processed per grid step. The narrow
#: kernel's cost is per-grid-step overhead (~450-500 ns/step measured at
#: 256^3 — BENCHMARKS.md roofline); amortising it over P tiles with ONE
#: K-row DMA window and per-tile sub-windows cuts the per-slot cost ~10-20x
#: (scripts/probe_wide_kernel.py: 196 ns/step for 8 tiles vs ~470 ns for 1).
WIDE_P = 8

#: Sub-window height candidates (rows selected per tile). Chosen from the
#: per-tile row-spread distribution: monotone decompress spans <= 9 rows
#: per 1024-slot tile (indices advance by <= 1 per slot), sparse compress
#: spans ~ 8/fill_fraction.
WIDE_KP_CANDIDATES = (8, 12, 16, 24, 32)

#: Scalar-prefetch budget of one launch's 1-D SMEM tables, in int32
#: words, shared by the wide gather and the fused super-tile kernels
#: (ops.fused_kernel). A 2-D table pads each row to 512 bytes of the
#: 1 MiB SMEM (a (C, 2) table overflowed it at C=2000); 1-D tables take
#: their words. 768 KiB leaves the rest of SMEM to the compiler, as the
#: narrow kernel's 70k-chunk cap does.
SMEM_TABLE_WORDS = 196_608


def wide_chunk_limit(p_tiles: int = WIDE_P) -> int:
    """Most chunks one wide launch holds: its scalar-prefetch tables
    (``row0``, ``out_tile``, ``first`` and the 1-D ``sub``) take
    ``3 + p_tiles // 4`` words a chunk of :data:`SMEM_TABLE_WORDS` —
    39,321 at P=8 (tests/test_tpu_compile.py::
    test_wide_launch_at_chunk_limit). Larger tables run as several
    tile-aligned launches."""
    return SMEM_TABLE_WORDS // (3 + p_tiles // 4)


#: Wide fallback ceiling, in chunks per super-tile: per-slot unit cost of
#: the wide step is C*(P*kp + 96)/(G_s*P*TILE); the XLA gather breaks even
#: with the narrow kernel at ~6 chunks/tile * (K+64) units (see
#: _CHUNK_BLOWUP_LIMIT), which translates to ~16 wide chunks per super-tile
#: at P=8, kp=16. Past that the decomposition loses to the XLA gather.
_WIDE_BLOWUP_LIMIT = 16


@dataclasses.dataclass(frozen=True)
class WideGatherTables:
    """Plan-time tables for one wide windowed-gather direction.

    Each chunk is one grid step covering a SUPER-TILE of ``p_tiles``
    1024-slot output tiles: one K-row DMA window shared by the step, and
    per-tile kp-row sub-windows at byte-packed offsets. Chunks of one
    super-tile are consecutive grid steps (revisiting accumulation)."""

    row0: np.ndarray      # (C,) int32 — DMA window start row
    sub: np.ndarray       # (C, P//4) int32 — per-tile sub-window offsets,
                          # byte-packed little-endian, relative to row0;
                          # the device table is its 1-D (C * P//4,) form
    out_tile: np.ndarray  # (C,) int32 — output super-tile index
    first: np.ndarray     # (C,) int32 — 1 on a super-tile's first chunk
    packed: np.ndarray    # (C, P*8, 128) int16 — lane | row-in-sub << 7
                          #  | valid << 12 (kp <= 32 keeps row in 5 bits;
                          # int16 halves table upload + streaming traffic,
                          # and P*8 sublanes align to the 16-row int16 tile)
    num_out: int          # valid output slots
    num_super: int        # G_s: super-tiles
    src_rows: int         # padded source rows
    span_rows: int        # K: DMA window height
    kp_rows: int          # kp: per-tile sub-window height
    p_tiles: int          # P: tiles per super-tile
    segs: tuple = ()      # ((c0, c1, t0, t1), ...) in super-tile units


def build_wide_gather_tables(idx: np.ndarray, valid: np.ndarray,
                             num_src: int, *, p_tiles: int = WIDE_P,
                             kp_rows: int = 0, k_rows: int = 0,
                             allow_segments: bool = True):
    """Build wide-kernel tables for ``out[j] = src[idx[j]] * valid[j]``.

    Same contract as :func:`build_monotone_gather_tables` (any order works;
    monotone is optimal; returns None on empty input or when the cover
    would be slower than the XLA gather), but covers ``p_tiles`` output
    tiles per chunk. ``kp_rows``/``k_rows`` force the sub-window/DMA-window
    heights (0 = choose from the data) — the distributed builder forces
    common values across shards so the SPMD program is uniform.
    """
    L = int(idx.shape[0])
    if L == 0:
        return None
    P = int(p_tiles)
    if P % 4 != 0:
        raise ValueError("p_tiles must be a multiple of 4 (byte packing)")
    if kp_rows and not 0 < int(kp_rows) <= _W_ROW_MASK + 1:
        raise ValueError(
            f"kp_rows must be in [1, {_W_ROW_MASK + 1}] — the packed "
            f"word's row field is {_W_VALID_SHIFT - _ROW_SHIFT} bits")
    native = _native_wide_tables(idx, valid, num_src, P, int(kp_rows),
                                 int(k_rows), allow_segments)
    if native is not None:
        return None if native == "blowup" else native
    SUPER = P * TILE
    idx = np.asarray(idx, np.int64)
    G_s = -(-L // SUPER)
    pad = G_s * SUPER - L
    idx_p = np.concatenate([idx, np.full(pad, idx[-1], np.int64)])
    valid_p = np.concatenate([np.asarray(valid, bool), np.zeros(pad, bool)])
    rows = (idx_p // TILE_LANE).astype(np.int32).reshape(G_s, P, TILE)
    lanes = (idx_p % TILE_LANE).astype(np.int32).reshape(G_s, P, TILE)
    vmask = valid_p.reshape(G_s, P, TILE)

    BIG = np.int32(2 ** 30)
    rmin = np.where(vmask, rows, BIG).min(axis=2)         # (G_s, P)
    rmax = np.where(vmask, rows, -1).max(axis=2)
    has = vmask.any(axis=2)
    spread = np.where(has, rmax - np.where(has, rmin, 0) + 1, 1)

    if kp_rows:
        kp = int(kp_rows)
    else:
        # Cost model: chunks(kp) ~ sum over super-tiles of the max per-tile
        # round count ceil(spread/kp); per-step cost ~ P*kp select rows plus
        # ~64 rows-equivalent of fixed overhead (DMA issue + scalar work —
        # calibrated against scripts/probe_wide_vs_narrow.py, where the
        # coverage-percentile chooser picked kp=32 for the 256^3 compress
        # direction and lost 2x per step to a barely-smaller chunk count).
        def cost(kp_c):
            rounds = -(-spread // kp_c)           # (G_s, P) ceil
            c_est = int(rounds.max(axis=1).sum()) if G_s else 1
            return c_est * (P * kp_c + 64)
        kp = min(WIDE_KP_CANDIDATES, key=cost)
    if k_rows:
        K = int(k_rows)
    else:
        base = np.where(has, rmin, BIG)
        b0 = base.min(axis=1)
        bspan = np.where(has, base - b0[:, None], 0).max(axis=1)
        q = int(np.quantile(bspan, 0.99)) if bspan.size else 0
        K = max(kp + 8, min(512, kp + 248,
                            int(np.ceil((q + kp) / 8.0) * 8)))
    if K - kp > 255:
        K = kp + 248  # sub-window offsets are byte-packed
    # Clamp window starts so every DMA window lies inside the EXACT source
    # extent ceil(num_src/128): src_rows then equals the exact extent and
    # the runtime's source zero-padding pass (a 53 MB copy per direction at
    # 256^3 — probe_r4_hlo) disappears. A clamped round covers fewer tiles
    # and simply takes another round; tiny sources (r_exact < K) keep the
    # padded form.
    r_exact = -(-int(num_src) // TILE_LANE)
    r_clamp = np.int32(r_exact - K) if (num_src > 0 and r_exact >= K) \
        else None

    # Multi-round cover: each round emits one chunk per still-active
    # super-tile. The minimum-base tile is always inside the window, so
    # every round covers at least kp rows of it — guaranteed progress.
    uncovered = vmask.copy()
    r0s, subs, packs, sts, rds = [], [], [], [], []
    rounds = 0
    total_chunks = 0
    while True:
        active = uncovered.any(axis=(1, 2))
        if rounds == 0:
            # every super-tile needs >= 1 chunk so its output block is
            # initialised even when it has no valid slots at all
            active = np.ones(G_s, bool)
        if not active.any():
            break
        a = np.flatnonzero(active)
        ar, av, al = rows[a], uncovered[a], lanes[a]
        base = np.where(av, ar, BIG).min(axis=2)          # (n_a, P)
        hasu = av.any(axis=2)
        r0 = np.where(hasu, base, BIG).min(axis=1)
        r0 = np.where(r0 == BIG, 0, r0).astype(np.int32)
        if r_clamp is not None:
            r0 = np.minimum(r0, r_clamp)
        # A tile participates if any of its rows fall inside the DMA
        # window; its kp-row sub-window saturates at the window top so
        # tail rows stay coverable when r0 is clamped (see r_clamp).
        inwin = hasu & (base <= r0[:, None] + (K - 1))
        basec = np.where(inwin, np.minimum(base, r0[:, None] + (K - kp)),
                         r0[:, None])
        cover = av & inwin[:, :, None] \
            & (ar >= basec[:, :, None]) & (ar < basec[:, :, None] + kp)
        sub_rel = np.clip(basec - r0[:, None], 0, K - kp).astype(np.int32)
        rin = np.clip(ar - basec[:, :, None], 0, kp - 1)
        packed = (al | (rin << _ROW_SHIFT)
                  | (cover.astype(np.int32) << _W_VALID_SHIFT))
        r0s.append(r0)
        subs.append(sub_rel)
        packs.append(packed.astype(np.int16))
        sts.append(a.astype(np.int32))
        rds.append(np.full(len(a), rounds, np.int32))
        uncovered[a] = av & ~cover
        rounds += 1
        total_chunks += len(a)
        if total_chunks > _WIDE_BLOWUP_LIMIT * G_s + 64:
            return None  # too disordered: the cover loses to XLA

    st_all = np.concatenate(sts)
    order = np.lexsort((np.concatenate(rds), st_all))
    st_o = st_all[order]
    row0 = np.concatenate(r0s)[order]
    sub_o = np.concatenate(subs)[order]                   # (C, P)
    packed_o = np.concatenate(packs)[order]               # (C, P, TILE)
    C = int(st_o.shape[0])
    first = np.zeros(C, np.int32)
    first[0] = 1
    first[1:] = (st_o[1:] != st_o[:-1]).astype(np.int32)

    words = np.zeros((C, P // 4), np.int32)
    for j in range(P):
        words[:, j // 4] |= sub_o[:, j].astype(np.int32) << (8 * (j % 4))

    src_rows = max(int(row0.max()) + K, -(-int(num_src) // TILE_LANE))
    segs = _tile_aligned_segments(first, st_o, G_s, wide_chunk_limit(P))
    if segs is None or (segs and not allow_segments):
        return None
    return WideGatherTables(
        row0=row0, sub=words, out_tile=st_o, first=first,
        packed=packed_o.reshape(C, P * TILE_SUB, TILE_LANE),
        num_out=L, num_super=G_s, src_rows=src_rows, span_rows=K,
        kp_rows=kp, p_tiles=P, segs=segs)


def _native_wide_tables(idx, valid, num_src, P, kp_rows, k_rows,
                        allow_segments):
    """Run the C++ cover (native/planner.cpp) when available: identical
    tables ~40x faster than the NumPy multi-round cover (which remains the
    executable specification, the fallback, and the parity oracle —
    tests/test_native_planner.py compares both table sets element-wise).
    Returns a WideGatherTables, the string "blowup" (caller falls back to
    the narrow kernel / XLA exactly as the NumPy builder's None), or None
    when the native library is unavailable."""
    from .. import native

    try:
        out = native.wide_gather_tables(
            np.asarray(idx, np.int64),
            np.asarray(valid, bool), num_src=int(num_src), p_tiles=P,
            kp_rows=kp_rows, k_rows=k_rows)
    except native.WideCoverBlowup:
        return "blowup"
    if out is None:
        return None
    row0, sub, out_tile, first, packed, kp, K, max_row0 = out
    L = int(np.asarray(idx).shape[0])
    G_s = -(-L // (P * TILE))
    src_rows = max(int(max_row0) + K, -(-int(num_src) // TILE_LANE))
    segs = _tile_aligned_segments(first, out_tile, G_s, wide_chunk_limit(P))
    if segs is None or (segs and not allow_segments):
        return "blowup"
    return WideGatherTables(
        row0=row0, sub=sub, out_tile=out_tile, first=first, packed=packed,
        num_out=L, num_super=G_s, src_rows=src_rows, span_rows=K,
        kp_rows=kp, p_tiles=P, segs=segs)


def build_best_gather_tables(idx, valid, num_src, allow_segments=True,
                             wide: Optional[bool] = None):
    """The preferred decomposition: wide kernel tables, falling back to the
    narrow single-tile kernel (whose per-tile windows tolerate somewhat
    different disorder patterns), then None (caller uses the XLA gather).
    ``wide=False`` forces narrow (testing)."""
    if wide is not False:
        t = build_wide_gather_tables(idx, valid, num_src,
                                     allow_segments=allow_segments)
        if t is not None:
            return t
    if wide is True:
        return None
    return build_monotone_gather_tables(idx, valid, num_src,
                                        allow_segments=allow_segments)


def compression_gather_inputs(value_indices, num_slots: int,
                              pad_values_to=None):
    """The (idx, valid) pairs for both compression directions.

    Decompress gathers slot <- value (idx = each occupied slot's position
    in the user's value array, forward-filled over unoccupied slots so a
    locally-coherent value order keeps the windows local); compress gathers
    value <- slot (idx = the flat value indices, optionally padded with
    repeats of the last index and valid=False — the padded-value layout of
    distributed shards). Works for ANY value order (duplicates resolve to
    the last occurrence, matching stages.decompress); single source of
    truth for local plan._init_pallas and the distributed per-shard tables.
    """
    from .. import native

    vi = np.asarray(value_indices, np.int64)
    n = len(vi)
    if n and (vi.min() < 0 or vi.max() >= num_slots):
        # the native path rejects these; the NumPy fancy-indexing fallback
        # would silently wrap negatives — fail identically on both
        raise IndexError(f"value index out of range [0, {num_slots})")
    nat = native.compression_inputs(vi, num_slots) if n else None
    if nat is not None:
        dec_idx, occupied = nat
    else:
        occupied = np.zeros(num_slots, bool)
        occupied[vi] = True
        pos = np.zeros(num_slots, np.int64)
        pos[vi] = np.arange(n, dtype=np.int64)  # last occurrence wins
        # forward-fill each unoccupied slot with the nearest occupied slot
        # at or below it (leading gap: the first occupied slot), so idx
        # stays local when the value order is; for sorted vi this reduces
        # to the running occupied count.
        if n:
            filled = np.maximum.accumulate(
                np.where(occupied, np.arange(num_slots, dtype=np.int64),
                         -1))
            filled = np.where(filled < 0, int(np.flatnonzero(occupied)[0]),
                              filled)
            dec_idx = pos[filled]
        else:
            dec_idx = np.zeros(num_slots, np.int64)
    out_n = n if pad_values_to is None else pad_values_to
    cmp_idx = np.zeros(out_n, np.int64)
    if n:
        cmp_idx[:n] = vi
        cmp_idx[n:] = vi[-1]
    cmp_valid = np.arange(out_n) < n
    return (dec_idx, occupied), (cmp_idx, cmp_valid)


def pad_tables_to(t: "MonotoneGatherTables", c_max: int):
    """Pad a table set to ``c_max`` chunks so shape-heterogeneous per-shard
    tables can be stacked into one SPMD-sharded array.

    Padding chunks are no-ops targeting a DUMMY output tile (index
    ``t.num_tiles``): all-zero packed words (valid=0, lane=0, row=0) and
    row0=0 (src_rows >= K always holds, so the DMA window is in range).
    The first padding chunk has first=1 so the dummy tile is initialised,
    never read-modify-written uninitialised. Callers must pass
    ``num_tiles + 1`` to ``monotone_gather`` and slice off the dummy tile
    (the flat real-output prefix is unchanged because the dummy is last).

    Returns (row0, out_tile, first, packed) padded to c_max rows.
    """
    pad = c_max - t.row0.shape[0]
    if pad < 0:
        raise ValueError("c_max smaller than existing chunk count")
    if pad == 0:
        return t.row0, t.out_tile, t.first, t.packed
    row0 = np.concatenate([t.row0, np.zeros(pad, np.int32)])
    out_tile = np.concatenate(
        [t.out_tile, np.full(pad, t.num_tiles, np.int32)])
    first = np.concatenate(
        [t.first, np.ones(1, np.int32), np.zeros(pad - 1, np.int32)])
    packed = np.concatenate(
        [t.packed, np.zeros((pad, TILE_SUB, TILE_LANE), np.int32)])
    return row0, out_tile, first, packed


def _tile_compute_win(K: int, t, win_re, win_im):
    """Per-tile compute on explicit (K, 128) window ARRAYS: decode the
    packed selector words ``t`` (8, 128), gather K candidate rows from
    the window, select-accumulate. Shared with the fused
    compression+DFT kernels (ops.fused_kernel), whose windows are
    computed in VMEM rather than DMA'd."""
    lane = t & (TILE_LANE - 1)
    row = (t >> _ROW_SHIFT) & _ROW_MASK
    m = (t >> _VALID_SHIFT).astype(jnp.float32)
    acc_re = jnp.zeros((TILE_SUB, TILE_LANE), jnp.float32)
    acc_im = jnp.zeros((TILE_SUB, TILE_LANE), jnp.float32)
    for k in range(K):
        sel = row == k
        src_re = jnp.broadcast_to(win_re[k][None, :],
                                  (TILE_SUB, TILE_LANE))
        src_im = jnp.broadcast_to(win_im[k][None, :],
                                  (TILE_SUB, TILE_LANE))
        acc_re += jnp.where(sel, jnp.take_along_axis(src_re, lane, axis=1), 0)
        acc_im += jnp.where(sel, jnp.take_along_axis(src_im, lane, axis=1), 0)
    return acc_re * m, acc_im * m


def _tile_compute(K: int, packed_ref, sc, slot):
    """Shared per-tile compute: decode the packed selector words, gather K
    candidate rows from the VMEM window, select-accumulate."""
    return _tile_compute_win(K, packed_ref[0], sc[slot, 0], sc[slot, 1])


def _kernel(K: int, row0_ref, out_tile_ref, first_ref, packed_ref,
            re_hbm, im_hbm, out_re_ref, out_im_ref, sc, sem):
    g = pl.program_id(0)
    n_g = pl.num_programs(0)

    def dma(gg, slot, chan, hbm):
        return pltpu.make_async_copy(
            hbm.at[pl.ds(row0_ref[gg], K), :], sc.at[slot, chan],
            sem.at[slot, chan])

    def start(gg):
        slot = jax.lax.rem(jnp.asarray(gg, jnp.int32), jnp.int32(2))
        dma(gg, slot, 0, re_hbm).start()
        dma(gg, slot, 1, im_hbm).start()

    @pl.when(g == 0)
    def _():
        start(0)

    @pl.when(g + 1 < n_g)
    def _():
        start(g + 1)

    slot = jax.lax.rem(jnp.asarray(g, jnp.int32), jnp.int32(2))
    dma(g, slot, 0, re_hbm).wait()
    dma(g, slot, 1, im_hbm).wait()

    acc_re, acc_im = _tile_compute(K, packed_ref, sc, slot)

    # Chunks of one output tile are consecutive grid steps mapping to the
    # same out block (revisiting): initialise on the first, accumulate after.
    @pl.when(first_ref[g] == 1)
    def _():
        out_re_ref[0] = acc_re
        out_im_ref[0] = acc_im

    @pl.when(first_ref[g] == 0)
    def _():
        out_re_ref[0] = out_re_ref[0] + acc_re
        out_im_ref[0] = out_im_ref[0] + acc_im


def _kernel_batched(K: int, row0_ref, out_tile_ref, first_ref, packed_ref,
                    re_hbm, im_hbm, out_re_ref, out_im_ref, sc, sem):
    """Batched variant: grid (B, C); batch b gathers from source slab b into
    output slab b through the SAME (batch-invariant) tables. The
    double-buffered DMA pipeline runs across the flattened (b, g) step
    sequence, prefetching across the batch boundary."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_g = pl.num_programs(1)
    step = b * n_g + g

    def dma(bb, gg, slot, chan, hbm):
        return pltpu.make_async_copy(
            hbm.at[bb, pl.ds(row0_ref[gg], K), :], sc.at[slot, chan],
            sem.at[slot, chan])

    def start(bb, gg, slot):
        dma(bb, gg, slot, 0, re_hbm).start()
        dma(bb, gg, slot, 1, im_hbm).start()

    @pl.when(step == 0)
    def _():
        start(0, 0, 0)

    @pl.when(step + 1 < n_b * n_g)
    def _():
        nxt_b = jnp.where(g + 1 < n_g, b, b + 1)
        nxt_g = jnp.where(g + 1 < n_g, g + 1, 0)
        start(nxt_b, nxt_g, jax.lax.rem(step + 1, jnp.int32(2)))

    slot = jax.lax.rem(step, jnp.int32(2))
    dma(b, g, slot, 0, re_hbm).wait()
    dma(b, g, slot, 1, im_hbm).wait()

    acc_re, acc_im = _tile_compute(K, packed_ref, sc, slot)

    @pl.when(first_ref[g] == 1)
    def _():
        out_re_ref[0, 0] = acc_re
        out_im_ref[0, 0] = acc_im

    @pl.when(first_ref[g] == 0)
    def _():
        out_re_ref[0, 0] = out_re_ref[0, 0] + acc_re
        out_im_ref[0, 0] = out_im_ref[0, 0] + acc_im


@functools.partial(jax.jit, static_argnames=("span_rows", "src_rows",
                                             "num_tiles", "interpret",
                                             "segs"))
def monotone_gather(re, im, row0, out_tile, first, packed, *,
                    span_rows: int, src_rows: int, num_tiles: int,
                    interpret: bool = False, segs: tuple = ()):
    """Run the windowed gather.

    Args:
      re, im: (src_rows, 128) float32 planar source — or (B, src_rows, 128)
        for a batch sharing the tables (each batch slab gathered into its
        own output slab).
      row0/out_tile/first/packed: device tables (see
        build_monotone_gather_tables).
      segs: tile-aligned launch segments from the table builder (static);
        each runs as its own pallas_call over its chunk slice and the
        per-segment outputs concatenate along the tile axis.
    Returns:
      (out_re, out_im): each (num_tiles, 8, 128) float32, with a leading B
      when the source was batched.
    """
    if segs:
        outs_re, outs_im = [], []
        for (c0, c1, t0, t1) in segs:
            o_re, o_im = _monotone_gather_call(
                re, im, row0[c0:c1], out_tile[c0:c1] - t0, first[c0:c1],
                packed[c0:c1], span_rows=span_rows, num_tiles=t1 - t0,
                interpret=interpret)
            outs_re.append(o_re)
            outs_im.append(o_im)
        axis = 1 if re.ndim == 3 else 0
        return (jnp.concatenate(outs_re, axis=axis),
                jnp.concatenate(outs_im, axis=axis))
    return _monotone_gather_call(re, im, row0, out_tile, first, packed,
                                 span_rows=span_rows, num_tiles=num_tiles,
                                 interpret=interpret)


def _monotone_gather_call(re, im, row0, out_tile, first, packed, *,
                          span_rows: int, num_tiles: int, interpret: bool,
                          carry=None):
    """One pallas_call over one chunk range (the whole table when
    unsegmented). ``carry`` as in :func:`_wide_gather_call`."""
    C = row0.shape[0]
    K = span_rows
    if carry is not None:
        return _monotone_gather_call_aliased(
            re, im, row0, out_tile, first, packed, span_rows=K,
            num_tiles=num_tiles, interpret=interpret, carry=carry)
    if re.ndim == 3:
        B = re.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,  # row0, out_tile, first
            grid=(B, C),
            in_specs=[
                pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, ot, fs: (g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=(
                pl.BlockSpec((1, 1, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, ot, fs: (b, ot[g], 0, 0)),
                pl.BlockSpec((1, 1, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, ot, fs: (b, ot[g], 0, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((2, 2, K, TILE_LANE), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        )
        out_shape = (
            jax.ShapeDtypeStruct((B, num_tiles, TILE_SUB, TILE_LANE),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, num_tiles, TILE_SUB, TILE_LANE),
                                 jnp.float32))
        return pl.pallas_call(
            functools.partial(_kernel_batched, K), out_shape=out_shape,
            grid_spec=grid_spec, interpret=interpret,
        )(row0, out_tile, first, packed, re, im)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,  # row0, out_tile, first
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                         lambda g, r0, ot, fs: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=(
            pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                         lambda g, r0, ot, fs: (ot[g], 0, 0)),
            pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                         lambda g, r0, ot, fs: (ot[g], 0, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((2, 2, K, TILE_LANE), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    out_shape = (
        jax.ShapeDtypeStruct((num_tiles, TILE_SUB, TILE_LANE), jnp.float32),
        jax.ShapeDtypeStruct((num_tiles, TILE_SUB, TILE_LANE), jnp.float32))
    return pl.pallas_call(
        functools.partial(_kernel, K), out_shape=out_shape,
        grid_spec=grid_spec, interpret=interpret,
    )(row0, out_tile, first, packed, re, im)


def _monotone_gather_call_aliased(re, im, row0, out_tile, first, packed, *,
                                  span_rows: int, num_tiles: int,
                                  interpret: bool, carry):
    """Narrow-kernel launch writing into an ALIASED full-size output pair
    (see _wide_gather_call's carry)."""
    C = row0.shape[0]
    K = span_rows
    base = functools.partial(_kernel_batched if re.ndim == 3 else _kernel, K)
    kern = lambda *r: base(*r[:6], *r[8:])  # drop the 2 unused carry refs
    scratch = [
        pltpu.VMEM((2, 2, K, TILE_LANE), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    carry_specs = [pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pl.ANY)]
    aliases = {6: 0, 7: 1}
    if re.ndim == 3:
        B = re.shape[0]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, C),
            in_specs=[
                pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, ot, fs: (g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ] + carry_specs,
            out_specs=(
                pl.BlockSpec((1, 1, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, ot, fs: (b, ot[g], 0, 0)),
                pl.BlockSpec((1, 1, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, ot, fs: (b, ot[g], 0, 0)),
            ),
            scratch_shapes=scratch,
        )
        out_shape = (
            jax.ShapeDtypeStruct((B, num_tiles, TILE_SUB, TILE_LANE),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, num_tiles, TILE_SUB, TILE_LANE),
                                 jnp.float32))
        return pl.pallas_call(
            kern, out_shape=out_shape, grid_spec=grid_spec,
            interpret=interpret, input_output_aliases=aliases,
        )(row0, out_tile, first, packed, re, im, *carry)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                         lambda g, r0, ot, fs: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + carry_specs,
        out_specs=(
            pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                         lambda g, r0, ot, fs: (ot[g], 0, 0)),
            pl.BlockSpec((1, TILE_SUB, TILE_LANE),
                         lambda g, r0, ot, fs: (ot[g], 0, 0)),
        ),
        scratch_shapes=scratch,
    )
    out_shape = (
        jax.ShapeDtypeStruct((num_tiles, TILE_SUB, TILE_LANE), jnp.float32),
        jax.ShapeDtypeStruct((num_tiles, TILE_SUB, TILE_LANE), jnp.float32))
    return pl.pallas_call(
        kern, out_shape=out_shape, grid_spec=grid_spec,
        interpret=interpret, input_output_aliases=aliases,
    )(row0, out_tile, first, packed, re, im, *carry)


def pad_wide_tables_to(t: WideGatherTables, c_max: int):
    """Wide analogue of :func:`pad_tables_to`: pad to ``c_max`` chunks with
    no-op chunks targeting a DUMMY super-tile (index ``t.num_super``) —
    all-invalid packed words, row0=0 (src_rows >= K always), sub=0. The
    first padding chunk has first=1 so the dummy block is initialised.
    Callers pass ``num_super + 1`` to :func:`wide_gather` and rely on the
    flat real-output prefix being unchanged (the dummy block is last).

    Returns the device tables (row0, sub, out_tile, first, packed) padded
    to c_max chunks, ``sub`` in its 1-D kernel form."""
    pad = c_max - t.row0.shape[0]
    if pad < 0:
        raise ValueError("c_max smaller than existing chunk count")
    if pad == 0:
        return t.row0, t.sub.reshape(-1), t.out_tile, t.first, t.packed
    P = t.p_tiles
    row0 = np.concatenate([t.row0, np.zeros(pad, np.int32)])
    sub = np.concatenate([t.sub, np.zeros((pad, P // 4), np.int32)]
                         ).reshape(-1)
    out_tile = np.concatenate(
        [t.out_tile, np.full(pad, t.num_super, np.int32)])
    first = np.concatenate(
        [t.first, np.ones(1, np.int32), np.zeros(pad - 1, np.int32)])
    packed = np.concatenate(
        [t.packed,
         np.zeros((pad, P * TILE_SUB, TILE_LANE), np.int16)])
    return row0, sub, out_tile, first, packed


def _wide_tile_compute(kp: int, t, win_re, win_im):
    """Per-tile compute of the wide kernel: decode one tile's packed block
    (already widened to int32), gather kp candidate rows from its
    (kp, 128) sub-window, select-accumulate."""
    lane = t & (TILE_LANE - 1)
    row = (t >> _ROW_SHIFT) & _W_ROW_MASK
    m = (t >> _W_VALID_SHIFT).astype(jnp.float32)
    acc_re = jnp.zeros((TILE_SUB, TILE_LANE), jnp.float32)
    acc_im = jnp.zeros((TILE_SUB, TILE_LANE), jnp.float32)
    for k in range(kp):
        sel = row == k
        sre = jnp.broadcast_to(win_re[k][None, :], (TILE_SUB, TILE_LANE))
        sim = jnp.broadcast_to(win_im[k][None, :], (TILE_SUB, TILE_LANE))
        acc_re += jnp.where(sel, jnp.take_along_axis(sre, lane, axis=1), 0)
        acc_im += jnp.where(sel, jnp.take_along_axis(sim, lane, axis=1), 0)
    return acc_re * m, acc_im * m


def _wide_step(kp: int, P: int, sub_ref, g, packed_blk, sc, slot, write):
    """Shared per-step body of the wide kernels: decode each tile's byte-
    packed sub-window offset, slice its (kp, 128) sub-window out of the
    DMA'd window, compute, and hand (p, acc_re, acc_im) to ``write`` for
    the output store. The int16 packed block is loaded and widened ONCE
    per step; per-tile rows are register slices. ``sub_ref`` is the 1-D
    (C * P//4,) table: chunk ``g``'s words start at ``g * (P // 4)``."""
    t_all = packed_blk[...].astype(jnp.int32)        # (P*8, 128)
    for p in range(P):
        word = sub_ref[g * (P // 4) + p // 4]
        sub = (word >> (8 * (p % 4))) & 0xFF
        win_re = sc[slot, 0, pl.ds(sub, kp), :]
        win_im = sc[slot, 1, pl.ds(sub, kp), :]
        t = t_all[p * TILE_SUB:(p + 1) * TILE_SUB]
        acc_re, acc_im = _wide_tile_compute(kp, t, win_re, win_im)
        write(p, acc_re, acc_im)


def _kernel_wide(K: int, kp: int, P: int, row0_ref, sub_ref, out_tile_ref,
                 first_ref, packed_ref, re_hbm, im_hbm, out_re_ref,
                 out_im_ref, sc, sem):
    g = pl.program_id(0)
    n_g = pl.num_programs(0)

    def dma(gg, slot, chan, hbm):
        return pltpu.make_async_copy(
            hbm.at[pl.ds(row0_ref[gg], K), :], sc.at[slot, chan],
            sem.at[slot, chan])

    def start(gg):
        slot = jax.lax.rem(jnp.asarray(gg, jnp.int32), jnp.int32(2))
        dma(gg, slot, 0, re_hbm).start()
        dma(gg, slot, 1, im_hbm).start()

    @pl.when(g == 0)
    def _():
        start(0)

    @pl.when(g + 1 < n_g)
    def _():
        start(g + 1)

    slot = jax.lax.rem(jnp.asarray(g, jnp.int32), jnp.int32(2))
    dma(g, slot, 0, re_hbm).wait()
    dma(g, slot, 1, im_hbm).wait()

    frst = first_ref[g]

    def write(p, acc_re, acc_im):
        @pl.when(frst == 1)
        def _():
            out_re_ref[p] = acc_re
            out_im_ref[p] = acc_im

        @pl.when(frst == 0)
        def _():
            out_re_ref[p] = out_re_ref[p] + acc_re
            out_im_ref[p] = out_im_ref[p] + acc_im

    _wide_step(kp, P, sub_ref, g, packed_ref[0], sc, slot, write)


def _kernel_wide_batched(K: int, kp: int, P: int, row0_ref, sub_ref,
                         out_tile_ref, first_ref, packed_ref, re_hbm,
                         im_hbm, out_re_ref, out_im_ref, sc, sem):
    """Batched wide variant: grid (B, C), batch-invariant tables, DMA
    pipeline prefetching across the batch boundary (see _kernel_batched)."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_g = pl.num_programs(1)
    step = b * n_g + g

    def dma(bb, gg, slot, chan, hbm):
        return pltpu.make_async_copy(
            hbm.at[bb, pl.ds(row0_ref[gg], K), :], sc.at[slot, chan],
            sem.at[slot, chan])

    def start(bb, gg, slot):
        dma(bb, gg, slot, 0, re_hbm).start()
        dma(bb, gg, slot, 1, im_hbm).start()

    @pl.when(step == 0)
    def _():
        start(0, 0, 0)

    @pl.when(step + 1 < n_b * n_g)
    def _():
        nxt_b = jnp.where(g + 1 < n_g, b, b + 1)
        nxt_g = jnp.where(g + 1 < n_g, g + 1, 0)
        start(nxt_b, nxt_g, jax.lax.rem(step + 1, jnp.int32(2)))

    slot = jax.lax.rem(step, jnp.int32(2))
    dma(b, g, slot, 0, re_hbm).wait()
    dma(b, g, slot, 1, im_hbm).wait()

    frst = first_ref[g]

    def write(p, acc_re, acc_im):
        @pl.when(frst == 1)
        def _():
            out_re_ref[0, p] = acc_re
            out_im_ref[0, p] = acc_im

        @pl.when(frst == 0)
        def _():
            out_re_ref[0, p] = out_re_ref[0, p] + acc_re
            out_im_ref[0, p] = out_im_ref[0, p] + acc_im

    _wide_step(kp, P, sub_ref, g, packed_ref[0], sc, slot, write)


@functools.partial(jax.jit, static_argnames=("span_rows", "kp_rows",
                                             "p_tiles", "src_rows",
                                             "num_super", "interpret",
                                             "segs"))
def wide_gather(re, im, row0, sub, out_tile, first, packed, *,
                span_rows: int, kp_rows: int, p_tiles: int, src_rows: int,
                num_super: int, interpret: bool = False, segs: tuple = ()):
    """Run the wide windowed gather.

    Args:
      re, im: (src_rows, 128) float32 planar source — or (B, src_rows, 128)
        batched.
      row0/sub/out_tile/first/packed: device tables (see
        build_wide_gather_tables; ``sub`` 1-D, ``p_tiles // 4`` words a
        chunk, as :func:`gather_device_tables` commits it).
    Returns:
      (out_re, out_im): each (num_super * p_tiles, 8, 128) float32, with a
      leading B when batched. Flat prefix = the num_out output slots.
    """
    if segs:
        outs_re, outs_im = [], []
        w = p_tiles // 4  # sub words a chunk
        for (c0, c1, t0, t1) in segs:
            o_re, o_im = wide_gather(
                re, im, row0[c0:c1], sub[c0 * w:c1 * w],
                out_tile[c0:c1] - t0,
                first[c0:c1], packed[c0:c1], span_rows=span_rows,
                kp_rows=kp_rows, p_tiles=p_tiles, src_rows=src_rows,
                num_super=t1 - t0, interpret=interpret)
            outs_re.append(o_re)
            outs_im.append(o_im)
        axis = 1 if re.ndim == 3 else 0
        return (jnp.concatenate(outs_re, axis=axis),
                jnp.concatenate(outs_im, axis=axis))
    if (re.ndim == 3
            and re.shape[0] * row0.shape[0] > wide_chunk_limit(p_tiles)):
        # Big batches run as per-slab launches (loses cross-batch DMA
        # prefetch only). The split dates from a compiler that failed on
        # B * C grid steps; the installed one's limit is the C-row SMEM
        # tables (wide_chunk_limit), which a batch does not grow.
        outs = [_wide_gather_call(re[b], im[b], row0, sub, out_tile, first,
                                  packed, span_rows=span_rows,
                                  kp_rows=kp_rows, p_tiles=p_tiles,
                                  num_super=num_super, interpret=interpret)
                for b in range(re.shape[0])]
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))
    return _wide_gather_call(re, im, row0, sub, out_tile, first, packed,
                             span_rows=span_rows, kp_rows=kp_rows,
                             p_tiles=p_tiles, num_super=num_super,
                             interpret=interpret)


def _wide_gather_call(re, im, row0, sub, out_tile, first, packed, *,
                      span_rows: int, kp_rows: int, p_tiles: int,
                      num_super: int, interpret: bool, carry=None):
    """One wide launch. ``carry`` (segmented tables only): the previous
    segment's full-size output pair, ALIASED into this launch's output —
    blocks this segment's ``out_tile`` never names keep the carried
    content, so multi-launch tables accumulate with zero copy traffic."""
    C = row0.shape[0]
    K, kp, P = span_rows, kp_rows, p_tiles
    kern = functools.partial(_kernel_wide_batched if re.ndim == 3
                             else _kernel_wide, K, kp, P)
    scratch = [
        pltpu.VMEM((2, 2, K, TILE_LANE), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    aliases = {7: 0, 8: 1} if carry is not None else {}
    carry_in = () if carry is None else tuple(carry)
    carry_specs = [] if carry is None else [
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if carry is not None:
        base = kern
        kern = lambda *r: base(*r[:7], *r[9:])  # drop unused carry refs
    if re.ndim == 3:
        B = re.shape[0]
        if B * C > wide_chunk_limit(P):
            # big batches run per slab (see wide_gather)
            outs = [_wide_gather_call(
                re[b], im[b], row0, sub, out_tile, first, packed,
                span_rows=K, kp_rows=kp, p_tiles=P, num_super=num_super,
                interpret=interpret,
                carry=None if carry is None else (carry[0][b], carry[1][b]))
                for b in range(B)]
            return (jnp.stack([o[0] for o in outs]),
                    jnp.stack([o[1] for o in outs]))
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,  # row0, sub, out_tile, first
            grid=(B, C),
            in_specs=[
                pl.BlockSpec((1, P * TILE_SUB, TILE_LANE),
                             lambda b, g, r0, sb, ot, fs: (g, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ] + carry_specs,
            out_specs=(
                pl.BlockSpec((1, P, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, sb, ot, fs: (b, ot[g], 0, 0)),
                pl.BlockSpec((1, P, TILE_SUB, TILE_LANE),
                             lambda b, g, r0, sb, ot, fs: (b, ot[g], 0, 0)),
            ),
            scratch_shapes=scratch,
        )
        out_shape = (
            jax.ShapeDtypeStruct((B, num_super * P, TILE_SUB, TILE_LANE),
                                 jnp.float32),
            jax.ShapeDtypeStruct((B, num_super * P, TILE_SUB, TILE_LANE),
                                 jnp.float32))
        return pl.pallas_call(
            kern, out_shape=out_shape, grid_spec=grid_spec,
            interpret=interpret, input_output_aliases=aliases,
        )(row0, sub, out_tile, first, packed, re, im, *carry_in)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,  # row0, sub, out_tile, first
        grid=(C,),
        in_specs=[
            pl.BlockSpec((1, P * TILE_SUB, TILE_LANE),
                         lambda g, r0, sb, ot, fs: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ] + carry_specs,
        out_specs=(
            pl.BlockSpec((P, TILE_SUB, TILE_LANE),
                         lambda g, r0, sb, ot, fs: (ot[g], 0, 0)),
            pl.BlockSpec((P, TILE_SUB, TILE_LANE),
                         lambda g, r0, sb, ot, fs: (ot[g], 0, 0)),
        ),
        scratch_shapes=scratch,
    )
    out_shape = (
        jax.ShapeDtypeStruct((num_super * P, TILE_SUB, TILE_LANE),
                             jnp.float32),
        jax.ShapeDtypeStruct((num_super * P, TILE_SUB, TILE_LANE),
                             jnp.float32))
    return pl.pallas_call(
        kern, out_shape=out_shape, grid_spec=grid_spec,
        interpret=interpret, input_output_aliases=aliases,
    )(row0, sub, out_tile, first, packed, re, im, *carry_in)


# -- uniform dispatch over the two table kinds -------------------------------

def gather_device_tables(t) -> tuple:
    """Device-committed tables for either kind: a tuple of per-SEGMENT
    table tuples (one entry for unsegmented tables). Slicing happens here
    at plan time — slicing shared tables inside the jitted hot path costs
    a 25 MB copy per execution (probe_r4_hlo). The wide ``sub`` table
    goes 1-D here (its 2-D form pads each row in SMEM)."""
    wide = isinstance(t, WideGatherTables)
    segs = t.segs if t.segs else ((0, t.row0.shape[0], 0,
                                   t.num_super if wide else t.num_tiles),)
    out = []
    for (c0, c1, t0, t1) in segs:
        if wide:
            out.append((jnp.asarray(t.row0[c0:c1]),
                        jnp.asarray(t.sub[c0:c1].reshape(-1)),
                        jnp.asarray(t.out_tile[c0:c1]),
                        jnp.asarray(t.first[c0:c1]),
                        jnp.asarray(t.packed[c0:c1])))
        else:
            out.append((jnp.asarray(t.row0[c0:c1]),
                        jnp.asarray(t.out_tile[c0:c1]),
                        jnp.asarray(t.first[c0:c1]),
                        jnp.asarray(t.packed[c0:c1])))
    return tuple(out)


@jax.named_scope("spfft.gather")
def run_gather(re, im, dev_tables: tuple, t, interpret: bool = False):
    """Run whichever kernel matches ``t`` (WideGatherTables or
    MonotoneGatherTables) on planar sources; returns (out_re, out_im)
    whose flat prefix holds the ``t.num_out`` output slots.

    Segmented tables run as one launch per segment. On real hardware the
    segments ACCUMULATE into one output buffer via pallas input/output
    aliasing (out_tile indices are absolute; blocks a segment never
    visits retain the previous launch's content) — zero concatenation
    traffic. Interpret mode keeps the concat path (the interpreter does
    not preserve unwritten blocks of aliased outputs).
    """
    wide = isinstance(t, WideGatherTables)
    segs = t.segs
    if not segs:
        if wide:
            return _wide_gather_call(
                re, im, *dev_tables[0], span_rows=t.span_rows,
                kp_rows=t.kp_rows, p_tiles=t.p_tiles,
                num_super=t.num_super, interpret=interpret)
        return _monotone_gather_call(
            re, im, *dev_tables[0], span_rows=t.span_rows,
            num_tiles=t.num_tiles, interpret=interpret)
    total = t.num_super if wide else t.num_tiles
    if interpret:
        outs = []
        for (c0, c1, t0, t1), tabs in zip(segs, dev_tables):
            if wide:
                row0, sub, ot, first, packed = tabs
                outs.append(_wide_gather_call(
                    re, im, row0, sub, ot - t0, first, packed,
                    span_rows=t.span_rows, kp_rows=t.kp_rows,
                    p_tiles=t.p_tiles, num_super=t1 - t0,
                    interpret=True))
            else:
                row0, ot, first, packed = tabs
                outs.append(_monotone_gather_call(
                    re, im, row0, ot - t0, first, packed,
                    span_rows=t.span_rows, num_tiles=t1 - t0,
                    interpret=True))
        axis = 1 if re.ndim == 3 else 0
        return (jnp.concatenate([o[0] for o in outs], axis=axis),
                jnp.concatenate([o[1] for o in outs], axis=axis))
    carry = None
    for tabs in dev_tables:
        if wide:
            carry = _wide_gather_call(
                re, im, *tabs, span_rows=t.span_rows, kp_rows=t.kp_rows,
                p_tiles=t.p_tiles, num_super=total, interpret=False,
                carry=carry)
        else:
            carry = _monotone_gather_call(
                re, im, *tabs, span_rows=t.span_rows, num_tiles=total,
                interpret=False, carry=carry)
    return carry


def run_gather_values(values_il, tables, device_tables=None,
                      interpret: bool = False):
    """Convenience wrapper for either table kind: interleaved (N, 2) source
    -> (num_out, 2) output.

    ``device_tables`` may supply the pre-committed jax arrays of
    :func:`gather_device_tables` to keep table upload off the hot path.
    """
    re, im = planar_from_interleaved(values_il, tables.src_rows)
    if device_tables is None:
        device_tables = gather_device_tables(tables)
    out_re, out_im = run_gather(re, im, device_tables, tables,
                                interpret=interpret)
    return interleaved_from_planar(out_re, out_im, tables.num_out)


def run_monotone_gather(values_il, tables: MonotoneGatherTables,
                        device_tables=None, interpret: bool = False):
    """Narrow-kernel alias of :func:`run_gather_values` (kept for callers
    that build MonotoneGatherTables explicitly)."""
    return run_gather_values(values_il, tables, device_tables, interpret)


@jax.named_scope("spfft.layout")
def planar_from_interleaved(values_il, src_rows: int, pair: bool = False):
    """Value array -> two zero-padded (src_rows, 128) planar arrays.

    Default layout is interleaved rows (N, 2) (batched: (B, N, 2));
    ``pair=True`` reads the planar-pair layout (2, N) (batched: (B, 2, N))
    — row 0 real, row 1 imaginary. The pair form exists because a large
    (N, 2) array at the jit boundary can be assigned TPU's T(8,128) tiled
    layout, padding the minor dim 2 -> 128 (64x memory — 36 GB at 512^3),
    while strided flat interleaves lower ~70x too slow; (2, N) row slices
    are both compact (4x sublane pad at most) and fast.
    """
    if pair:
        n = values_il.shape[-1]
        re_flat = values_il[..., 0, :]
        im_flat = values_il[..., 1, :]
    else:
        n = values_il.shape[-2]
        re_flat = values_il[..., 0]
        im_flat = values_il[..., 1]
    pad = src_rows * TILE_LANE - n
    batch = [(0, 0)] * (re_flat.ndim - 1)
    shape = re_flat.shape[:-1] + (src_rows, TILE_LANE)
    re = jnp.pad(re_flat, batch + [(0, pad)]).reshape(shape)
    im = jnp.pad(im_flat, batch + [(0, pad)]).reshape(shape)
    return re, im


@jax.named_scope("spfft.layout")
def planar_from_complex(x, src_rows: int):
    """Complex (S, Z) sticks — or batched (B, S, Z) — -> two zero-padded
    (src_rows, 128) planar arrays (leading B preserved). Goes straight
    from the complex values to planar so no big interleaved (N, 2)
    intermediate can be assigned the 64x-padded tiled layout (see
    planar_from_interleaved)."""
    batch = x.shape[:1] if x.ndim == 3 else ()
    re_flat = jnp.real(x).reshape(batch + (-1,))
    im_flat = jnp.imag(x).reshape(batch + (-1,))
    pad = [(0, 0)] * len(batch) + [(0, src_rows * TILE_LANE
                                    - re_flat.shape[-1])]
    shape = batch + (src_rows, TILE_LANE)
    return (jnp.pad(re_flat, pad).reshape(shape),
            jnp.pad(im_flat, pad).reshape(shape))


@jax.named_scope("spfft.layout")
def interleaved_from_planar(out_re, out_im, num_out: int,
                            pair: bool = False):
    """Kernel outputs -> (num_out, 2) interleaved ((B, num_out, 2) when
    batched); ``pair=True`` returns the planar-pair layout (2, num_out) /
    (B, 2, num_out) instead, never materialising a big (N, 2) shape (see
    planar_from_interleaved on why)."""
    if out_re.ndim == 4:
        B = out_re.shape[0]
        re = out_re.reshape(B, -1)[:, :num_out]
        im = out_im.reshape(B, -1)[:, :num_out]
    else:
        re = out_re.reshape(-1)[:num_out]
        im = out_im.reshape(-1)[:num_out]
    return jnp.stack([re, im], axis=-2 if pair else -1)
