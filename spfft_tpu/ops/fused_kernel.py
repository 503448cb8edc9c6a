"""Fused sparse-compression + z-stick-DFT Pallas TPU kernels.

The reference's single biggest GPU win is fusing the sparse compression
scatter/gather directly into the transform kernels (compression_gpu +
the z-stick FFT never round-trip through global memory). Our pipeline
reproduced both halves as *separate* Pallas kernels — the windowed
gather (:mod:`~spfft_tpu.ops.gather_kernel`) and the matmul-DFT z stage
(:mod:`~spfft_tpu.ops.dft_kernel`) — with the dense
``(num_sticks, dim_z)`` planar stick pair materialised in HBM between
them, in both directions. These kernels close that gap:

* :func:`run_decompress_zdft` (backward): each grid step DMAs one
  source window of values, gathers the 1024-slot tiles it covers into
  a VMEM scratch holding a SUPER-TILE of ``r_sticks`` whole sticks, and
  on a super-tile's last step reshapes the scratch to ``(r_sticks,
  dim_z)``, contracts it against the resident Karatsuba DFT matrices
  and writes only the *transformed* planar block. The dense pre-FFT
  stick intermediate never touches HBM.
* :func:`run_zdft_compress` (forward twin): each grid step needs one
  8-stick-aligned RAW stick window (Mosaic only DMAs whole 8-row tiles
  of a (sticks, dim_z) array). A step that opens a window DMAs and
  z-transforms it in VMEM (any FULL scaling folded into the matrices
  at plan time); every step then gathers its output tiles out of the
  transformed flat slot layout. The transformed stick array never
  touches HBM; the cost is a bounded DFT recompute where consecutive
  windows overlap, which the plan-time cost model gates
  (:func:`compress_recompute_rows`).

Geometry. A fused super-tile groups ``p_tiles`` consecutive 1024-slot
tiles so that ``r_sticks * dim_z == p_tiles * 1024`` exactly — whole
sticks per output block (64 sticks, 16 tiles at ``dim_z`` 256). Every
grid step carries a fixed cost (~0.3 us of scalar work and DMA issue
on a TPU v5e at 256^3, as much as a one-tile step's whole work), so
the tables say how much one step covers:

* ``super_tile`` (the default): the tables come from the WIDE gather
  decomposition (:func:`~spfft_tpu.ops.gather_kernel.
  build_wide_gather_tables`) with ``p_tiles`` tiles a chunk: one step
  gathers a whole super-tile (backward) or ``p_tiles`` output tiles
  (forward), each tile from its own ``kp``-row sub-window of the one
  window. A monotone value map takes one step per super-tile; a
  super-tile whose sources do not fit one window takes more
  (consecutive steps, revisiting accumulation).
* ``narrow``: chunks of the narrow decomposition, one 1024-slot tile a
  step. Kept for plans whose super-tile tables do not fit (scalar-
  prefetch SMEM, :data:`SMEM_TABLE_WORDS`; VMEM; the wide cover's
  chunk limit; the forward recompute model) and for the distributed
  plan's shape-uniform per-shard tables.

``dim_z % 128 == 0`` keeps every in-kernel reshape in the
lane-preserving / sublane-merge family that the existing two-stage
kernels (ops.dft_kernel._kernel2) already exercise on Mosaic, and
makes the forward window slice row-aligned.

Eligibility (:func:`eligible_dim` + the plan's gate): f32 only,
``dim_z % 128 == 0``, ``dim_z`` within the fused-kernel axis cap
(:func:`spfft_tpu.ops.dft_kernel.max_dim` — the VMEM/perf ceiling),
unsegmented narrow tables, and the forward recompute model under
:data:`RECOMPUTE_LIMIT`. Everything else falls back to the two-kernel
path — same math, same layouts — with the reason recorded through
``obs`` (``spfft_plan_pallas_fallback_total``); the geometry each fused
direction got is counted in ``spfft_plan_fused_geometry_total``.

``SPFFT_TPU_FUSED_COMPRESS=0`` disables the fused path;
``SPFFT_TPU_FUSED_INTERPRET=1`` activates it off-TPU, in interpret
mode, for the CPU A/B lane (``benchmark.py --fused``,
``make fused-smoke``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .dft_kernel import _dot, _kara
from . import gather_kernel as gk
from .gather_kernel import (TILE, TILE_LANE, TILE_SUB,
                            MonotoneGatherTables, _tile_compute_win,
                            _wide_tile_compute)

#: Target stick rows per backward super-tile: large enough that the
#: per-super-tile (r, dim_z) x (dim_z, dim_z) Karatsuba dot keeps the
#: MXU busy (>= 64 rows), small enough that the accumulation scratch
#: stays a footnote in the VMEM budget. Default for :func:`target_r`;
#: override per-experiment with ``SPFFT_TPU_FUSED_TARGET_R``.
TARGET_R = 64

#: Hard cap on 1024-slot tiles per super-tile (scratch rows =
#: p_tiles * 8; 64 tiles = 512 KB of f32 scratch per channel pair).
MAX_P_TILES = 64

#: Forward recompute ceiling: the fused forward z-transforms every
#: stick its chunk windows touch, so overlapping windows re-transform
#: sticks. The fused path declines when the modelled transformed rows
#: exceed this multiple of the unfused single pass (num_sticks rows) —
#: past it the DFT recompute outweighs the saved HBM round trip of the
#: transformed stick array (2 * num_sticks * dim_z * 8 bytes).
#: Default for :func:`recompute_limit`; override per-experiment with
#: ``SPFFT_TPU_FUSED_RECOMPUTE_LIMIT``.
RECOMPUTE_LIMIT = 4.0


def target_r() -> int:
    """Effective backward super-tile row target: the
    ``SPFFT_TPU_FUSED_TARGET_R`` env override (clamped to [8, 512],
    read per plan build so chip-profile retuning needs no code change)
    or :data:`TARGET_R`."""
    raw = os.environ.get("SPFFT_TPU_FUSED_TARGET_R", "").strip()
    if raw:
        try:
            return max(8, min(int(raw), 512))
        except ValueError:
            pass
    return TARGET_R


def recompute_limit() -> float:
    """Effective forward recompute ceiling: the
    ``SPFFT_TPU_FUSED_RECOMPUTE_LIMIT`` env override (clamped to
    [1.0, 64.0], read per plan build) or :data:`RECOMPUTE_LIMIT`."""
    raw = os.environ.get("SPFFT_TPU_FUSED_RECOMPUTE_LIMIT", "").strip()
    if raw:
        try:
            return max(1.0, min(float(raw), 64.0))
        except ValueError:
            pass
    return RECOMPUTE_LIMIT

#: Per-kernel VMEM budget the geometry chooser stays under — the
#: single-stage DFT kernel's ceiling (ops.dft_kernel._VMEM_BUDGET); the
#: 256^3 fused kernels compile under it (tests/test_tpu_compile.py).
_VMEM_BUDGET = int(5.5 * 1024 * 1024)


def enabled() -> bool:
    """Fused compression+DFT is on by default where eligible;
    ``SPFFT_TPU_FUSED_COMPRESS=0`` disables (read per decision so tests
    and the benchmark A/B flag can flip it)."""
    return os.environ.get("SPFFT_TPU_FUSED_COMPRESS", "1").strip() != "0"


def interpret_forced() -> bool:
    """``SPFFT_TPU_FUSED_INTERPRET=1`` activates the fused kernels
    off-TPU, where they run in interpret mode — the CPU A/B and smoke
    lane (numbers there are honest overhead-only, like the overlap
    round's CPU A/B). On a TPU backend the kernels always compile for
    the chip; the variable changes nothing there."""
    return os.environ.get("SPFFT_TPU_FUSED_INTERPRET", "").strip() == "1"


def eligible_dim(dim_z: int):
    """Gate on the z-axis length alone. Returns ``None`` when eligible,
    else the fallback-reason string."""
    from . import dft_kernel as dk
    if dim_z <= 0 or dim_z % TILE_LANE != 0:
        return "dimz_not_multiple_128"
    if dim_z > dk.max_dim():
        return "dimz_over_cap"
    if not dk.fits1(dim_z, dim_z):
        return "vmem"
    return None


def super_tile_geometry(dim_z: int):
    """``(r_sticks, p_tiles)`` with ``r_sticks * dim_z == p_tiles *
    TILE`` exactly: whole sticks per super-tile, whole 1024-slot gather
    tiles per super-tile."""
    g = math.gcd(dim_z, TILE)
    r_min = TILE // g          # sticks per minimal super-tile
    p_min = dim_z // g         # 1024-slot tiles per minimal super-tile
    k = max(1, -(-target_r() // r_min))
    k = min(k, max(1, MAX_P_TILES // p_min))
    return r_min * k, p_min * k


def _fits_backward(dim_z: int, p_tiles: int, span_rows: int,
                   complete: bool = False, step_tiles: int = 1) -> bool:
    mats = 3 * dim_z * dim_z
    window = 2 * 2 * span_rows * TILE_LANE
    scratch = 2 * p_tiles * TILE_SUB * TILE_LANE
    out = 2 * 2 * p_tiles * TILE  # double-buffered output blocks
    words = step_tiles * TILE     # double-buffered selector block
    # hermitian completion: in-kernel one-hot mirror matrix + iota
    # transients (generous — the compiler reuses most of them)
    mirror = 4 * dim_z * dim_z if complete else 0
    return (mats + window + scratch + out + words + mirror) * 4 \
        <= _VMEM_BUDGET


def _fits_forward(dim_z: int, win_sticks: int, step_tiles: int = 1) -> bool:
    mats = 3 * dim_z * dim_z
    window = 2 * win_sticks * dim_z              # raw-stick DMA buffer
    work = 6 * win_sticks * dim_z                # transformed + flat views
    out = 2 * 2 * step_tiles * TILE
    words = step_tiles * TILE
    return (mats + window + work + out + words) * 4 <= _VMEM_BUDGET


#: Scalar-prefetch budget of the super-tile kernels, in int32 words. The
#: tables are 1-D (a 2-D table pads each row to 512 bytes of the 1 MiB
#: SMEM, gather_kernel.WIDE_SEG_CHUNK_LIMIT): ``4 + p_tiles`` words a
#: grid step backward, ``5 + p_tiles`` forward. 768 KiB leaves the rest
#: of SMEM to the compiler, as the narrow kernel's 70k-chunk cap does.
SMEM_TABLE_WORDS = 196_608


# -- plan-time tables --------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedDecompressTables:
    """Backward fused tables. Chunk order is super-tile-major (the
    revisiting order), so a super-tile's chunks are consecutive grid
    steps. ``toff`` set = the ``super_tile`` geometry (one chunk gathers
    all ``p_tiles`` tiles of its super-tile from ``kp_rows``-row
    sub-windows); ``pos`` set = ``narrow`` (one chunk, one tile)."""

    row0: np.ndarray     # (C,) int32 — DMA window start row
    sfirst: np.ndarray   # (C,) int32 — 1 on a super-tile's first chunk
    slast: np.ndarray    # (C,) int32 — 1 on a super-tile's last chunk
    sup: np.ndarray      # (C,) int32 — output super-tile index
    packed: np.ndarray   # narrow: (C, 8, 128) int32 selector words;
                         # super_tile: (C, p_tiles*8, 128) int16
    dim_z: int
    r_sticks: int        # sticks per super-tile (output block rows)
    p_tiles: int         # 1024-slot tiles per super-tile
    num_super: int       # output blocks
    num_sticks: int      # valid stick rows (callers slice [:num_sticks])
    src_rows: int        # padded source rows
    span_rows: int       # K: DMA window height
    #: narrow: (C,) int32 — the chunk's tile index WITHIN its
    #: super-tile (scratch slot)
    pos: Optional[np.ndarray] = None
    #: super_tile: (C * p_tiles,) int32 — each tile's sub-window start
    #: row inside its chunk's DMA window
    toff: Optional[np.ndarray] = None
    kp_rows: int = 0     # super_tile: sub-window rows per tile
    #: r2c hermitian (0,0)-stick completion info: (2,) int32
    #: ``[z_sup, z_row]`` — the zero stick's super-tile and its row
    #: within it — or None for plans that need no in-kernel completion
    #: (c2c, or r2c without the (0,0) stick). ``z_sup = -1`` is the
    #: "never matches" sentinel the distributed shape-uniform tables
    #: use for shards that don't own the zero stick.
    zinfo: Optional[np.ndarray] = None

    @property
    def geometry(self) -> str:
        return "narrow" if self.toff is None else "super_tile"

    @property
    def grid_steps(self) -> int:
        """Grid steps per transform (per field of a batch)."""
        return int(self.row0.shape[0])


@dataclasses.dataclass(frozen=True)
class FusedCompressTables:
    """Forward fused tables: the compress gather's chunks with their
    source windows re-expressed as RAW STICK ranges (the kernel
    transforms them in VMEM before gathering). ``toff`` set = the
    ``super_tile`` geometry (a chunk gathers ``p_tiles`` consecutive
    output tiles); ``off`` set = ``narrow`` (one tile a chunk)."""

    s0: np.ndarray       # (C,) int32 — first raw stick of the chunk's
                         # stick window (a multiple of 8)
    out_tile: np.ndarray  # (C,) int32 — output block (p_tiles tiles)
    first: np.ndarray    # (C,) int32 — 1 on a block's first chunk
    wnew: np.ndarray     # (C,) int32 — 1 on a stick window's first chunk
                         # (DMA + z-transform); 0 reuses the window
    packed: np.ndarray   # narrow: (C, 8, 128) int32 selector words;
                         # super_tile: (C, p_tiles*8, 128) int16
    dim_z: int
    win_sticks: int      # S_w: raw sticks per stick window
    num_tiles: int       # output value tiles (p_tiles per block)
    num_out: int         # valid output slots
    src_sticks: int      # padded raw-stick rows the source must carry
    span_rows: int       # K: the decomposition's window height
    #: narrow: (C,) int32 — transformed-window start row of the chunk's
    #: K-row window, relative to s0, in the flat (rows, 128) layout
    off: Optional[np.ndarray] = None
    #: super_tile: (C * p_tiles,) int32 — each tile's kp-row sub-window
    #: start in the same flat layout
    toff: Optional[np.ndarray] = None
    p_tiles: int = 1     # output tiles per chunk
    kp_rows: int = 0     # super_tile: sub-window rows per tile

    @property
    def geometry(self) -> str:
        return "narrow" if self.toff is None else "super_tile"

    @property
    def grid_steps(self) -> int:
        """Grid steps per transform (per field of a batch)."""
        return int(self.s0.shape[0])


def build_fused_decompress_tables(t: MonotoneGatherTables, dim_z: int,
                                  num_sticks: int,
                                  zero_stick_id: Optional[int] = None):
    """Narrow-geometry backward tables from narrow decompress gather
    tables, or a fallback-reason string.

    ``zero_stick_id`` (r2c plans that own the (0,0) stick) folds the
    hermitian stick completion into the kernel: the zero stick's
    super-tile / row position rides along as the ``zinfo`` scalar pair
    and the kernel mirror-fills its empty z half before the z-DFT."""
    reason = eligible_dim(dim_z)
    if reason:
        return reason
    if t.segs:
        return "segmented"
    complete = zero_stick_id is not None
    r_sticks, p_tiles = super_tile_geometry(dim_z)
    if not _fits_backward(dim_z, p_tiles, t.span_rows, complete):
        return "vmem"
    sup = t.out_tile // p_tiles
    pos = t.out_tile - sup * p_tiles
    sfirst, slast = _first_last(sup)
    num_super = -(-t.num_tiles // p_tiles)
    return FusedDecompressTables(
        row0=t.row0, pos=pos.astype(np.int32), sfirst=sfirst,
        slast=slast, sup=sup.astype(np.int32), packed=t.packed,
        dim_z=int(dim_z), r_sticks=r_sticks, p_tiles=p_tiles,
        num_super=num_super, num_sticks=int(num_sticks),
        src_rows=t.src_rows, span_rows=t.span_rows,
        zinfo=_zinfo(zero_stick_id, r_sticks))


def _first_last(blk: np.ndarray):
    """Per-chunk flags of a block-major chunk order: 1 on each block's
    first / last chunk."""
    C = int(blk.shape[0])
    first = np.zeros(C, np.int32)
    last = np.zeros(C, np.int32)
    first[0] = 1
    last[-1] = 1
    first[1:] |= (blk[1:] != blk[:-1]).astype(np.int32)
    last[:-1] |= (blk[1:] != blk[:-1]).astype(np.int32)
    return first, last


def _zinfo(zero_stick_id: Optional[int], r_sticks: int):
    if zero_stick_id is None:
        return None
    zid = int(zero_stick_id)
    return np.array([zid // r_sticks, zid % r_sticks], np.int32)


def _super_tile_cover(idx, valid, num_src: int, dim_z: int, extra: int):
    """The wide gather decomposition at the fused super-tile width, or a
    reason string: ``p_tiles`` is a multiple of 4 (the wide packing),
    the cover stays under the wide chunk limit, and its 1-D scalar
    tables (``extra + p_tiles`` words a chunk) under
    :data:`SMEM_TABLE_WORDS`. Returns ``(wide_tables, toff)`` with each
    tile's sub-window start relative to its chunk's ``row0``."""
    reason = eligible_dim(dim_z)
    if reason:
        return reason
    _, p_tiles = super_tile_geometry(dim_z)
    if p_tiles % 4:
        return "p_tiles"
    w = gk.build_wide_gather_tables(idx, valid, num_src, p_tiles=p_tiles)
    if w is None:
        return "value_order"
    if w.row0.shape[0] * (extra + p_tiles) > SMEM_TABLE_WORDS:
        return "smem"
    shifts = 8 * (np.arange(p_tiles) % 4)
    toff = (w.sub[:, np.arange(p_tiles) // 4] >> shifts) & 0xFF
    return w, toff.astype(np.int32)


def build_super_decompress_tables(idx, valid, num_values: int, dim_z: int,
                                  num_sticks: int,
                                  zero_stick_id: Optional[int] = None):
    """``super_tile``-geometry backward tables, or a reason string.

    ``(idx, valid)`` are the decompress gather inputs over the plan's
    slot space (:func:`~spfft_tpu.ops.gather_kernel.
    compression_gather_inputs`). Only the first ``num_sticks`` sticks
    are covered: a super-tile past them would transform slots that
    hold no value, so the output has ``ceil(num_sticks / r_sticks)``
    blocks and the caller pads it to its own stick count."""
    r_sticks, p_tiles = super_tile_geometry(dim_z)
    n = int(num_sticks) * int(dim_z)
    out = _super_tile_cover(idx[:n], valid[:n], num_values, dim_z, 4)
    if isinstance(out, str):
        return out
    w, toff = out
    if not _fits_backward(dim_z, p_tiles, w.span_rows,
                          zero_stick_id is not None, p_tiles):
        return "vmem"
    sfirst, slast = _first_last(w.out_tile)
    return FusedDecompressTables(
        row0=w.row0, toff=toff.reshape(-1), sfirst=sfirst, slast=slast,
        sup=w.out_tile, packed=w.packed, dim_z=int(dim_z),
        r_sticks=r_sticks, p_tiles=p_tiles, num_super=w.num_super,
        num_sticks=int(num_sticks), src_rows=w.src_rows,
        span_rows=w.span_rows, kp_rows=w.kp_rows,
        zinfo=_zinfo(zero_stick_id, r_sticks))


#: Minimum raw sticks per narrow forward stick window. Consecutive
#: chunks whose source windows fit one stick window share its DMA and
#: z-transform, so a taller window transforms fewer sticks twice: at
#: the 256^3 sphere the cost model's recompute factor is 5.5 with
#: per-chunk 24-stick windows and 1.0 with shared 48-stick ones (1.5 at
#: 128^3, 1.25 at 320^3).
FWD_WIN_STICKS = 48


def _win_sticks(span_rows: int, dim_z: int) -> int:
    """Raw sticks per narrow forward stick window: a whole number of
    8-row tiles, since Mosaic refuses a DMA slice of an (8, 128)-tiled
    (sticks, dim_z) array whose start or height is not a multiple of 8
    (tests/test_tpu_compile.py), and tall enough to hold any chunk's
    K-row source window after its start is rounded down to a tile."""
    q = dim_z // TILE_LANE
    need = -(-(span_rows + q - 1) // q) + TILE_SUB - 1
    return max(-(-need // TILE_SUB) * TILE_SUB, FWD_WIN_STICKS)


def _stick_windows(lo, hi, w: int):
    """Greedy cover of the chunks' raw-stick ranges ``[lo, hi)`` by
    stick windows of ``w`` sticks, in chunk order: a chunk reuses the
    current window when its range fits inside it, else opens a new one
    at its first stick rounded down to a tile. Returns per-chunk
    (s0, wnew) and the window count."""
    s0 = np.empty_like(lo)
    wnew = np.zeros(lo.shape[0], np.int32)
    cur = -1
    for g in range(lo.shape[0]):
        if cur < 0 or lo[g] < cur or hi[g] > cur + w:
            cur = int(lo[g]) // TILE_SUB * TILE_SUB
            wnew[g] = 1
        s0[g] = cur
    return s0, wnew, int(wnew.sum())


def _narrow_windows(t: MonotoneGatherTables, dim_z: int):
    q = dim_z // TILE_LANE
    row0 = t.row0.astype(np.int64)
    lo = row0 // q
    hi = (row0 + t.span_rows + q - 1) // q      # exclusive stick bound
    s0, wnew, n_win = _stick_windows(lo, hi, _win_sticks(t.span_rows,
                                                          dim_z))
    return s0, row0 - s0 * q, wnew, n_win


def _super_windows(w: "gk.WideGatherTables", toff: np.ndarray,
                   dim_z: int):
    """Stick windows of super-tile compress chunks: each chunk's raw
    sticks run from the one holding its window's first row to the one
    holding its highest tile's last sub-window row, and the window
    height is the tallest such range after its start is rounded down to
    an 8-stick tile, rounded up to a tile. Returns (s0, wnew, n_win,
    win_sticks)."""
    q = dim_z // TILE_LANE
    row0 = w.row0.astype(np.int64)
    lo = row0 // q
    hi = (row0 + toff.max(axis=1) + w.kp_rows + q - 1) // q
    need = int((hi - lo // TILE_SUB * TILE_SUB).max(initial=1))
    win = -(-need // TILE_SUB) * TILE_SUB
    s0, wnew, n_win = _stick_windows(lo, hi, win)
    return s0, wnew, n_win, win


def compress_recompute_rows(t, dim_z: int) -> int:
    """Stick rows the fused forward would z-transform in total (each
    stick window is transformed once) — the cost model's numerator.
    ``t`` is the narrow compress gather tables, or the super-tile
    forward's :class:`FusedCompressTables`."""
    if isinstance(t, FusedCompressTables):
        return int(t.wnew.sum()) * t.win_sticks
    return _narrow_windows(t, dim_z)[3] * _win_sticks(t.span_rows, dim_z)


def build_fused_compress_tables(t: MonotoneGatherTables, dim_z: int,
                                num_sticks: int):
    """Narrow-geometry forward tables from narrow compress gather
    tables, or a fallback-reason string. The cost-model gate declines
    when the window-overlap DFT recompute exceeds
    :data:`RECOMPUTE_LIMIT` times the unfused single transform pass."""
    reason = eligible_dim(dim_z)
    if reason:
        return reason
    if t.segs:
        return "segmented"
    q = dim_z // TILE_LANE
    win_sticks = _win_sticks(t.span_rows, dim_z)
    if not _fits_forward(dim_z, win_sticks):
        return "vmem"
    s0, off, wnew, n_win = _narrow_windows(t, dim_z)
    if n_win * win_sticks > recompute_limit() * max(int(num_sticks), 1):
        return "recompute_blowup"
    # window rows [row0, row0+K) of the flat (rows, 128) transformed
    # layout live inside raw sticks [s0, s0 + win_sticks)
    assert int((off + t.span_rows).max(initial=0)) <= win_sticks * q
    return FusedCompressTables(
        s0=s0.astype(np.int32), off=off.astype(np.int32),
        out_tile=t.out_tile, first=t.first, wnew=wnew, packed=t.packed,
        dim_z=int(dim_z), win_sticks=win_sticks,
        num_tiles=t.num_tiles, num_out=t.num_out,
        src_sticks=_src_sticks(s0, win_sticks, num_sticks),
        span_rows=t.span_rows)


def _src_sticks(s0, win_sticks: int, num_sticks: int) -> int:
    """The DMA always reads the STATIC win_sticks rows from s0, so the
    source must be padded to the furthest row any window's DMA touches."""
    return max(int((s0 + win_sticks).max(initial=0)), int(num_sticks))


def build_super_compress_tables(idx, valid, num_slots: int, dim_z: int,
                                num_sticks: int):
    """``super_tile``-geometry forward tables, or a reason string:
    ``(idx, valid)`` are the compress gather inputs over
    ``num_slots = num_sticks * dim_z`` slots. The same recompute gate
    as the narrow forward counts the new stick windows."""
    out = _super_tile_cover(idx, valid, num_slots, dim_z, 5)
    if isinstance(out, str):
        return out
    w, toff = out
    q = dim_z // TILE_LANE
    s0, wnew, n_win, win = _super_windows(w, toff, dim_z)
    if n_win * win > recompute_limit() * max(int(num_sticks), 1):
        return "recompute_blowup"
    if not _fits_forward(dim_z, win, w.p_tiles):
        return "vmem"
    # each tile's sub-window, as a row of the flat (rows, 128) layout of
    # its chunk's transformed stick window
    flat = w.row0[:, None].astype(np.int64) + toff - s0[:, None] * q
    assert int((flat + w.kp_rows).max(initial=0)) <= win * q
    return FusedCompressTables(
        s0=s0.astype(np.int32), toff=flat.astype(np.int32).reshape(-1),
        out_tile=w.out_tile, first=w.first, wnew=wnew, packed=w.packed,
        dim_z=int(dim_z), win_sticks=win,
        num_tiles=w.num_super * w.p_tiles, num_out=w.num_out,
        src_sticks=_src_sticks(s0, win, num_sticks),
        span_rows=w.span_rows, p_tiles=w.p_tiles, kp_rows=w.kp_rows)


def decompress_device_tables(t: FusedDecompressTables) -> tuple:
    """Device-committed table tuple for :func:`run_decompress_zdft`:
    ``(row0, pos | toff, sfirst, slast, sup, packed)`` plus the
    ``zinfo`` completion pair when the plan carries one (the kernel
    signature is static on its presence, so c2c plans trace the exact
    program they always did)."""
    place = t.pos if t.toff is None else t.toff
    base = (jnp.asarray(t.row0), jnp.asarray(place),
            jnp.asarray(t.sfirst), jnp.asarray(t.slast),
            jnp.asarray(t.sup), jnp.asarray(t.packed))
    if t.zinfo is None:
        return base
    return base + (jnp.asarray(t.zinfo),)


def compress_device_tables(t: FusedCompressTables) -> tuple:
    """Device-committed table tuple for :func:`run_zdft_compress`:
    ``(s0, off | toff, out_tile, first, wnew, packed)``."""
    place = t.off if t.toff is None else t.toff
    return (jnp.asarray(t.s0), jnp.asarray(place),
            jnp.asarray(t.out_tile), jnp.asarray(t.first),
            jnp.asarray(t.wnew), jnp.asarray(t.packed))


def commit_mats(mats) -> tuple:
    """Device-committed Karatsuba DFT matrix triple. Any FULL scaling
    is already folded into the matrix VALUES at plan time —
    compile-time scaling, the kernels never multiply by a runtime
    scalar."""
    return tuple(jnp.asarray(np.asarray(m, np.float32)) for m in mats)


# -- backward kernel: gather-decompress -> z-DFT -----------------------------

def _complete_zero_stick(R, dz, xr, xi, is_z, z_row):
    """In-kernel r2c hermitian completion of the (0,0) stick, on the
    RAW (pre-z-DFT) super-tile rows: fill each empty z slot from its
    conjugate mirror ``F(-z) = conj(F(z))`` — exactly the unfused
    ``where(nz, v, ±roll(v[::-1], 1))`` of the two-kernel path
    (plan._backward_rest_tp), expressed as a one-hot MXU contraction
    because Mosaic has no ``rev`` lowering. One-hot rows make the dot a
    single exact f32 product per element, so the fused and unfused
    paths stay bit-identical. ``is_z`` (this super-tile owns the zero
    stick) and ``z_row`` arrive as DATA, not trace constants, so one
    compiled program serves every shard of a distributed plan."""
    # row z_row as a masked sublane reduction: Mosaic has no lowering
    # for dynamic_slice on a value, and adding zeros is exact
    zsel = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) == z_row
    row_r = jnp.sum(jnp.where(zsel, xr, 0.0), axis=0, keepdims=True)
    row_i = jnp.sum(jnp.where(zsel, xi, 0.0), axis=0, keepdims=True)
    jj = jax.lax.broadcasted_iota(jnp.int32, (dz, dz), 0)
    kk = jax.lax.broadcasted_iota(jnp.int32, (dz, dz), 1)
    jk = jj + kk
    # M[j, k] = 1 iff (j + k) % dz == 0, so (row @ M)[k] = row[(dz-k)%dz]
    mir = jnp.where((jk == 0) | (jk == dz), 1.0, 0.0).astype(jnp.float32)
    mir_r = _dot(row_r, mir)
    mir_i = _dot(row_i, mir)
    nz = (row_r != 0.0) | (row_i != 0.0)
    new_r = jnp.where(nz, row_r, mir_r)
    new_i = jnp.where(nz, row_i, -mir_i)
    rowsel = zsel & is_z
    return jnp.where(rowsel, new_r, xr), jnp.where(rowsel, new_i, xi)


def _step_tiles(kp, P, g, place_ref, packed_ref, window):
    """The output tiles of one grid step, as ``(p, acc_re, acc_im)``.
    ``super_tile`` (``kp > 0``): all ``P`` tiles of the chunk, tile p
    gathered from the kp-row sub-window that starts at row
    ``place_ref[g * P + p]`` of the window (the int16 selector block is
    loaded and widened once a step). ``narrow`` (``kp == 0``): one tile
    from the whole window. ``window(start, rows)`` reads a planar row
    range of the step's source window — from a ref, since Mosaic lowers
    a dynamic row offset on a 128-lane ref load but not
    ``dynamic_slice`` on a value."""
    if not kp:
        win_re, win_im = window(None, None)
        return [(None,) + _tile_compute_win(win_re.shape[0],
                                            packed_ref[0], win_re,
                                            win_im)]
    t_all = packed_ref[0].astype(jnp.int32)           # (P*8, 128)
    tiles = []
    for p in range(P):
        win_re, win_im = window(place_ref[g * P + p], kp)
        tiles.append((p,) + _wide_tile_compute(
            kp, t_all[p * TILE_SUB:(p + 1) * TILE_SUB], win_re, win_im))
    return tiles


def _dec_zdft_body(K, P, R, dz, kp, complete, g, place_ref, sfirst_ref,
                   slast_ref, sup_ref, zinfo_ref, packed_ref,
                   cr_ref, ci_ref, cs_ref, write, acc, sc, slot):
    """Shared per-step body of the backward fused kernel. ``write``
    stores the transformed (R, dz) planar pair on the super-tile's last
    chunk; DMA wait has already happened. ``complete`` statically gates
    the r2c (0,0)-stick hermitian completion (``zinfo_ref`` is None —
    and never read — without it)."""
    def window(start, rows):
        if start is None:
            return sc[slot, 0], sc[slot, 1]
        return (sc[slot, 0, pl.ds(start, rows), :],
                sc[slot, 1, pl.ds(start, rows), :])

    tiles = _step_tiles(kp, P, g, place_ref, packed_ref, window)

    @pl.when(sfirst_ref[g] == 1)
    def _():
        acc[0] = jnp.zeros((P * TILE_SUB, TILE_LANE), jnp.float32)
        acc[1] = jnp.zeros((P * TILE_SUB, TILE_LANE), jnp.float32)

    for p, acc_re, acc_im in tiles:
        rows = (pl.ds(place_ref[g] * TILE_SUB, TILE_SUB) if p is None
                else slice(p * TILE_SUB, (p + 1) * TILE_SUB))
        acc[0, rows] = acc[0, rows] + acc_re
        acc[1, rows] = acc[1, rows] + acc_im

    @pl.when(slast_ref[g] == 1)
    def _():
        xr = acc[0].reshape(R, dz)
        xi = acc[1].reshape(R, dz)
        if complete:
            xr, xi = _complete_zero_stick(
                R, dz, xr, xi, zinfo_ref[0] == sup_ref[g], zinfo_ref[1])
        yr, yi = _kara(xr, xi, cr_ref[...], ci_ref[...], cs_ref[...])
        write(yr, yi)


def _dec_refs(complete, refs):
    if complete:
        (row0_ref, place_ref, sfirst_ref, slast_ref, sup_ref, zinfo_ref,
         packed_ref, cr_ref, ci_ref, cs_ref, re_hbm, im_hbm,
         out_r_ref, out_i_ref, acc, sc, sem) = refs
    else:
        (row0_ref, place_ref, sfirst_ref, slast_ref, sup_ref,
         packed_ref, cr_ref, ci_ref, cs_ref, re_hbm, im_hbm,
         out_r_ref, out_i_ref, acc, sc, sem) = refs
        zinfo_ref = None
    return (row0_ref, (place_ref, sfirst_ref, slast_ref, sup_ref,
                       zinfo_ref, packed_ref, cr_ref, ci_ref, cs_ref),
            re_hbm, im_hbm, out_r_ref, out_i_ref, acc, sc, sem)


def _kernel_dec_zdft(K, P, R, dz, kp, complete, *refs):
    (row0_ref, body_refs, re_hbm, im_hbm, out_r_ref, out_i_ref, acc, sc,
     sem) = _dec_refs(complete, refs)
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

    def write(yr, yi):
        out_r_ref[...] = yr
        out_i_ref[...] = yi

    _dec_zdft_body(K, P, R, dz, kp, complete, g, *body_refs, write, acc,
                   sc, slot)


def _kernel_dec_zdft_batched(K, P, R, dz, kp, complete, *refs):
    """Batched grid (B, C): batch b gathers+transforms slab b through
    the shared tables; DMA pipeline prefetches across the batch
    boundary (the gather kernels' pattern)."""
    (row0_ref, body_refs, re_hbm, im_hbm, out_r_ref, out_i_ref, acc, sc,
     sem) = _dec_refs(complete, refs)
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

    def write(yr, yi):
        out_r_ref[0] = yr
        out_i_ref[0] = yi

    _dec_zdft_body(K, P, R, dz, kp, complete, g, *body_refs, write, acc,
                   sc, slot)


def _words_spec(t, batched: bool):
    """BlockSpec of the per-chunk selector block: (8, 128) int32 words
    narrow, (p_tiles*8, 128) int16 super_tile."""
    rows = t.packed.shape[1]
    if batched:
        return pl.BlockSpec((1, rows, TILE_LANE), lambda b, g, *_: (g, 0, 0))
    return pl.BlockSpec((1, rows, TILE_LANE), lambda g, *_: (g, 0, 0))


@jax.named_scope("spfft.decompress_z")
def run_decompress_zdft(re, im, dev_tables: tuple, mats: tuple,
                        t: FusedDecompressTables,
                        interpret: bool = False):
    """Gathered decompress + z-DFT in one ``pallas_call``.

    Args:
      re, im: (src_rows, 128) planar f32 value source — or
        (B, src_rows, 128) batched.
      dev_tables: :func:`decompress_device_tables` output.
      mats: :func:`commit_mats` backward z-DFT triple.
    Returns:
      (sr, si): transformed planar sticks, each
      ``(num_super * r_sticks, dim_z)`` f32 (leading B when batched);
      rows ``[:num_sticks]`` are the valid sticks, any row past them
      is zero.
    """
    from .. import faults as _faults
    _faults.check_site("kernel.launch")  # trace time: once per compile
    C = int(t.row0.shape[0])
    K, P, R, dz, kp = t.span_rows, t.p_tiles, t.r_sticks, t.dim_z, t.kp_rows
    complete = t.zinfo is not None
    n_scalar = 6 if complete else 5  # (+ zinfo) row0, pos|toff, sfirst,
    scratch = [                      # slast, sup
        pltpu.VMEM((2, P * TILE_SUB, TILE_LANE), jnp.float32),
        pltpu.VMEM((2, 2, K, TILE_LANE), jnp.float32),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    mat_specs = [pl.BlockSpec((dz, dz), lambda *a: (0, 0))] * 3
    hbm_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    batched = re.ndim == 3
    if batched:
        B = re.shape[0]
        grid = (B, C)
        out_spec = pl.BlockSpec((1, R, dz),
                                lambda b, g, r0, pc, sf, sl, sp, *_:
                                (b, sp[g], 0))
        out_rows = (B, t.num_super * R, dz)
        kern = _kernel_dec_zdft_batched
    else:
        grid = (C,)
        out_spec = pl.BlockSpec((R, dz),
                                lambda g, r0, pc, sf, sl, sp, *_:
                                (sp[g], 0))
        out_rows = (t.num_super * R, dz)
        kern = _kernel_dec_zdft
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_scalar, grid=grid,
        in_specs=[_words_spec(t, batched)] + mat_specs + hbm_specs,
        out_specs=(out_spec, out_spec), scratch_shapes=scratch)
    out_shape = (jax.ShapeDtypeStruct(out_rows, jnp.float32),) * 2
    assert len(dev_tables) == (7 if complete else 6)
    row0, place, sfirst, slast, sup, packed = dev_tables[:6]
    zex = dev_tables[6:]
    cr, ci, cs = mats
    return pl.pallas_call(
        functools.partial(kern, K, P, R, dz, kp, complete),
        out_shape=out_shape, grid_spec=grid_spec, interpret=interpret,
    )(row0, place, sfirst, slast, sup, *zex, packed, cr, ci, cs, re, im)


# -- forward kernel: z-DFT -> windowed compress gather -----------------------

def _zdft_cmp_body(kp, P, g, place_ref, first_ref, wnew_ref, packed_ref,
                   cr_ref, ci_ref, cs_ref, sc, flat, wait, prefetch,
                   store, K=None):
    """Shared per-step body of the forward fused kernel. On a stick
    window's first chunk: wait for its DMA, z-transform it into the
    ``flat`` (rows, 128) scratch, then start the next window's DMA into
    the now-free raw buffer. Every chunk then gathers its output tiles
    out of ``flat`` (narrow: its K-row window at ``place_ref[g]``;
    super_tile: each tile's kp-row sub-window) and hands each to
    ``store(p, first, acc_re, acc_im)``."""
    @pl.when(wnew_ref[g] == 1)
    def _():
        wait()
        yr, yi = _kara(sc[0], sc[1], cr_ref[...], ci_ref[...], cs_ref[...])
        # (S_w, q*128) -> (S_w*q, 128): lane-preserving leading-dim split
        flat[0] = yr.reshape(flat.shape[1], TILE_LANE)
        flat[1] = yi.reshape(flat.shape[1], TILE_LANE)

    prefetch()

    def window(start, rows):
        if start is None:
            start, rows = place_ref[g], K
        return (flat[0, pl.ds(start, rows), :],
                flat[1, pl.ds(start, rows), :])

    frst = first_ref[g]
    for p, acc_re, acc_im in _step_tiles(kp, P, g, place_ref, packed_ref,
                                         window):
        store(0 if p is None else p, frst, acc_re, acc_im)


def _win_copy(s0_ref, gg, S_w, hbm, sc, sem, chan, batch=None):
    src = hbm if batch is None else hbm.at[batch]
    return pltpu.make_async_copy(
        src.at[pl.ds(pl.multiple_of(s0_ref[gg], TILE_SUB), S_w), :],
        sc.at[chan], sem.at[chan])


def _kernel_zdft_cmp(K, S_w, kp, P, s0_ref, place_ref, out_tile_ref,
                     first_ref, wnew_ref, packed_ref, cr_ref, ci_ref,
                     cs_ref, re_hbm, im_hbm, out_re_ref, out_im_ref, sc,
                     flat, sem):
    g = pl.program_id(0)
    n_g = pl.num_programs(0)

    def copies(gg):
        return (_win_copy(s0_ref, gg, S_w, re_hbm, sc, sem, 0),
                _win_copy(s0_ref, gg, S_w, im_hbm, sc, sem, 1))

    @pl.when(g == 0)
    def _():
        for c in copies(0):
            c.start()

    def wait():
        for c in copies(g):
            c.wait()

    def prefetch():
        nxt = jnp.minimum(g + 1, n_g - 1)

        @pl.when((g + 1 < n_g) & (wnew_ref[nxt] == 1))
        def _():
            for c in copies(nxt):
                c.start()

    def store(p, frst, acc_re, acc_im):
        @pl.when(frst == 1)
        def _():
            out_re_ref[p] = acc_re
            out_im_ref[p] = acc_im

        @pl.when(frst == 0)
        def _():
            out_re_ref[p] = out_re_ref[p] + acc_re
            out_im_ref[p] = out_im_ref[p] + acc_im

    _zdft_cmp_body(kp, P, g, place_ref, first_ref, wnew_ref, packed_ref,
                   cr_ref, ci_ref, cs_ref, sc, flat, wait, prefetch, store,
                   K)


def _kernel_zdft_cmp_batched(K, S_w, kp, P, s0_ref, place_ref,
                             out_tile_ref, first_ref, wnew_ref, packed_ref,
                             cr_ref, ci_ref, cs_ref, re_hbm, im_hbm,
                             out_re_ref, out_im_ref, sc, flat, sem):
    """Batched grid (B, C): every batch walks the same windows (chunk 0
    always opens one), and the prefetch crosses the batch boundary."""
    b = pl.program_id(0)
    g = pl.program_id(1)
    n_b = pl.num_programs(0)
    n_g = pl.num_programs(1)

    def copies(bb, gg):
        return (_win_copy(s0_ref, gg, S_w, re_hbm, sc, sem, 0, bb),
                _win_copy(s0_ref, gg, S_w, im_hbm, sc, sem, 1, bb))

    @pl.when((b == 0) & (g == 0))
    def _():
        for c in copies(0, 0):
            c.start()

    def wait():
        for c in copies(b, g):
            c.wait()

    def prefetch():
        last = g + 1 == n_g
        nxt_b = jnp.where(last, b + 1, b)
        nxt_g = jnp.where(last, 0, g + 1)

        @pl.when((nxt_b < n_b) & (wnew_ref[nxt_g] == 1))
        def _():
            for c in copies(nxt_b, nxt_g):
                c.start()

    def store(p, frst, acc_re, acc_im):
        @pl.when(frst == 1)
        def _():
            out_re_ref[0, p] = acc_re
            out_im_ref[0, p] = acc_im

        @pl.when(frst == 0)
        def _():
            out_re_ref[0, p] = out_re_ref[0, p] + acc_re
            out_im_ref[0, p] = out_im_ref[0, p] + acc_im

    _zdft_cmp_body(kp, P, g, place_ref, first_ref, wnew_ref, packed_ref,
                   cr_ref, ci_ref, cs_ref, sc, flat, wait, prefetch, store,
                   K)


@jax.named_scope("spfft.z_compress")
def run_zdft_compress(sr, si, dev_tables: tuple, mats: tuple,
                      t: FusedCompressTables,
                      interpret: bool = False):
    """z-DFT + windowed compress gather in one ``pallas_call``.

    Args:
      sr, si: (src_sticks, dim_z) planar f32 RAW (un-transformed)
        sticks — or (B, src_sticks, dim_z) batched. Rows past the
        plan's num_sticks must be zero.
      dev_tables: :func:`compress_device_tables` output.
      mats: :func:`commit_mats` forward z-DFT triple (scaling folded
        into the matrices).
    Returns:
      (out_re, out_im): each (num_tiles, 8, 128) f32 (leading B when
      batched); the flat prefix holds the ``num_out`` output values.
    """
    from .. import faults as _faults
    _faults.check_site("kernel.launch")  # trace time: once per compile
    C = int(t.s0.shape[0])
    K, S_w, dz, kp = t.span_rows, t.win_sticks, t.dim_z, t.kp_rows
    P = t.p_tiles
    q = dz // TILE_LANE
    scratch = [
        pltpu.VMEM((2, S_w, dz), jnp.float32),
        pltpu.VMEM((2, S_w * q, TILE_LANE), jnp.float32),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    mat_specs = [pl.BlockSpec((dz, dz), lambda *a: (0, 0))] * 3
    hbm_specs = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    batched = sr.ndim == 3
    if batched:
        B = sr.shape[0]
        grid = (B, C)
        out_spec = pl.BlockSpec((1, P, TILE_SUB, TILE_LANE),
                                lambda b, g, s0, pc, ot, *_:
                                (b, ot[g], 0, 0))
        out_rows = (B, t.num_tiles, TILE_SUB, TILE_LANE)
        kern = _kernel_zdft_cmp_batched
    else:
        grid = (C,)
        out_spec = pl.BlockSpec((P, TILE_SUB, TILE_LANE),
                                lambda g, s0, pc, ot, *_: (ot[g], 0, 0))
        out_rows = (t.num_tiles, TILE_SUB, TILE_LANE)
        kern = _kernel_zdft_cmp
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,  # s0, off|toff, out_tile, first, wnew
        grid=grid,
        in_specs=[_words_spec(t, batched)] + mat_specs + hbm_specs,
        out_specs=(out_spec, out_spec), scratch_shapes=scratch)
    out_shape = (jax.ShapeDtypeStruct(out_rows, jnp.float32),) * 2
    s0, place, out_tile, first, wnew, packed = dev_tables
    cr, ci, cs = mats
    return pl.pallas_call(
        functools.partial(kern, K, S_w, kp, P),
        out_shape=out_shape, grid_spec=grid_spec, interpret=interpret,
    )(s0, place, out_tile, first, wnew, packed, cr, ci, cs, sr, si)


@jax.named_scope("spfft.layout")
def pad_sticks_planar(sr, si, src_sticks: int):
    """Zero-pad planar (num_sticks, dim_z) stick channels — or batched
    (B, num_sticks, dim_z) — to the ``src_sticks`` rows the forward
    kernel's window DMAs may touch (a handful of rows; XLA folds the
    pad into the producing op's output buffer)."""
    pad = src_sticks - sr.shape[-2]
    if pad <= 0:
        return sr, si
    widths = [(0, 0)] * (sr.ndim - 2) + [(0, pad), (0, 0)]
    return jnp.pad(sr, widths), jnp.pad(si, widths)
