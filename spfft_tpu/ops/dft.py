"""Matmul-DFT: FFT stages as MXU dot_generals against plan-time matrices.

XLA:TPU lowers ``jnp.fft`` to DFT *convolutions* (O(N^2) matmuls at
operand_precision=highest) plus 67 MB-class internal layout copies per 2D
transform (measured at 256^3 — scripts/probe_r4_hlo.py). Expressing the
same DFT as explicit minor-axis dot_generals against f32 matrix constants
is strictly better on this hardware:

  * same MXU cost, none of the internal layout copies (measured 1.5 ms
    faster on the 256^3 fused pair, scripts/probe_r4_dft2.py);
  * Karatsuba 3-mult complex multiply (3 dots instead of 4);
  * normalisation constants fold into the matrices (zero extra passes);
  * works for ANY length, primes included, and supports half-spectrum
    real transforms directly — no XLA C2R op, which sidesteps the TPU
    backend's rank-3 irfft corruption (see stages._irfft_last);
  * stages can stay PLANAR (separate re/im f32 arrays), avoiding the
    X64SplitLow/High machinery XLA wraps around complex dtypes.

Accuracy: HIGHEST-precision dots measure ~1e-7 relative error per pass
vs numpy's FFT (256-point, scripts/probe_r4_dft.py); lower precisions
fail the library's 1e-6 contract and are not offered.

The O(N^2) flop count is intentional: at the stick/plane lengths this
library sees (<= ~512) the MXU eats the DFT matmul at a higher effective
rate than any O(N log N) decomposition we measured — a four-step radix-2
split halves MXU flops but loses the gain to butterfly HBM passes
(scripts/probe_r4_dft2.py). ``MATMUL_DFT_MAX`` caps the direct form;
composite axes above it run a TWO-STAGE Cooley-Tukey factorization
N = N1*N2 (both <= the cap): reshape (…, N1, N2), stage-1 dot over N1,
one planar twiddle multiply (fused elementwise), stage-2 dot over N2,
and a final minor-axes swap — keeping 768/1024-class axes off the
conv-lowered ``jnp.fft`` TPU path entirely (round-4 verdict item; the
reference gets arbitrary N from FFTW plans, fftw_plan_1d.hpp:74-94).
Axes above the cap with no such factorization run the DIRECT form up
to ``MATMUL_DFT_DIRECT_FALLBACK_MAX`` (primes have no cheaper matmul
route); only lengths beyond that fall back to ``jnp.fft`` in
ops.stages.

Reference parity: these replace the reference's FFTW/cuFFT plan objects
(reference: src/fft/fftw_plan_1d.hpp:74-94, src/fft/transform_1d_gpu.hpp)
— the "plan" here is the matrix constant pair embedded in the executable.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np

#: Longest axis the direct matmul-DFT PREFERS; composite lengths above
#: it run the two-stage Cooley-Tukey split (fewer MXU flops).
MATMUL_DFT_MAX = 512

#: Unfactorable lengths (primes, and composites whose smallest balanced
#: split exceeds the cap) still run the DIRECT matmul form up to this
#: length. Bluestein at the padded length (2048 for N=1021) can cost
#: FEWER MACs (~0.59M/row via two-stage 2048 passes vs 1.04M direct)
#: but spends THREE grid-scale passes plus chirp elementwise traffic
#: where the direct form spends one — the same movement-vs-flops trade
#: the measured radix-split experiment lost (module docstring;
#: probe_r4_dft2.py) — and the jnp.fft fallback is the conv-lowered
#: O(N^2) TPU path with the compile-explosion hazard the matmul layer
#: exists to avoid (scripts/probe_fftcompile.py). Beyond this cap the
#: N^2 flops genuinely dominate and jnp.fft remains (the reference
#: gets any N from FFTW, fftw_plan_1d.hpp:74-94).
MATMUL_DFT_DIRECT_FALLBACK_MAX = 1024


def _mdft_covered_len(n: int) -> bool:
    """A length the matmul layer can execute: direct (incl. the direct
    fallback for unfactorable lengths) or two-stage."""
    return (n <= MATMUL_DFT_DIRECT_FALLBACK_MAX
            or two_stage_factor(n) is not None)


def _direct_form_len(n: int) -> bool:
    """Lengths whose matrix builders yield PLAIN matrix tuples — the
    split-window row/column selections and the hermitian x-stage need
    them (TwoStageMats does not row/column-select). Composite lengths
    above the cap return TwoStageMats from c2c_mats and so do NOT
    qualify; unfactorable ones up to the direct fallback cap do."""
    return n <= MATMUL_DFT_MAX or (
        two_stage_factor(n) is None
        and n <= MATMUL_DFT_DIRECT_FALLBACK_MAX)

_HIGHEST = jax.lax.Precision.HIGHEST

BACKWARD = +1   # unnormalised inverse DFT (e^{+2 pi i k n / N})
FORWARD = -1    # plain DFT


# Matrix caches are bounded (round-4 advisor finding): scale is folded
# into the keys, and per-plan scales (1/global_size) plus split-x window
# tuples make entries effectively per-plan — an unbounded cache leaks
# O(n^2) f32 matrices for the process lifetime in plan-churning servers.
# 32 entries cover every axis of a handful of live plans; evicted
# matrices rebuild in milliseconds at the next plan construction.
#
# _dft_mats additionally caps resident BYTES (round-5 advisor finding):
# an entry-count bound alone lets 32 prime-fallback triples at n>512
# (a 1021 axis costs ~12.5 MB per triple) pin ~400 MB for the process
# lifetime of a long-lived server; the byte-aware LRU below evicts
# oldest-first once the total exceeds the budget, so worst-case
# residency stays bounded regardless of axis mix.
DFT_MATS_CACHE_BYTES = 96 * 1024 * 1024


class _ByteLRU:
    """A thread-safe LRU keyed like ``functools.lru_cache`` but bounded
    by BOTH entry count and the summed ``nbytes`` of the cached arrays
    (serve-registry plans build concurrently from worker threads).
    Provides ``cache_clear()`` for drop-in compatibility."""

    def __init__(self, builder, max_entries: int, max_bytes: int):
        self._builder = builder
        self._max_entries = max_entries
        self._max_bytes = max_bytes
        self._store = collections.OrderedDict()  #: guarded by _lock
        self._bytes = 0                          #: guarded by _lock
        self._lock = threading.Lock()

    def __call__(self, *key):
        with self._lock:
            hit = self._store.get(key)
            if hit is not None:
                self._store.move_to_end(key)
                return hit
        val = self._builder(*key)  # build outside the lock (ms-scale)
        nbytes = sum(int(m.nbytes) for m in val)
        with self._lock:
            if key not in self._store:
                self._store[key] = val
                self._bytes += nbytes
            while len(self._store) > 1 \
                    and (self._bytes > self._max_bytes
                         or len(self._store) > self._max_entries):
                _, old = self._store.popitem(last=False)
                self._bytes -= sum(int(m.nbytes) for m in old)
        return val

    def cache_clear(self) -> None:
        with self._lock:
            self._store.clear()
            self._bytes = 0

    @property
    def cache_bytes(self) -> int:
        with self._lock:
            return self._bytes


def _build_dft_mats(n: int, sign: int, scale: float):
    """(Cr, Ci, Cs) f32 numpy constants for the length-``n`` DFT with
    ``scale`` folded in; Cs = Cr + Ci pre-summed for the Karatsuba form."""
    k = np.arange(n)
    m = np.exp(sign * 2j * np.pi * np.outer(k, k) / n) * scale
    cr = np.ascontiguousarray(m.real.astype(np.float32))
    ci = np.ascontiguousarray(m.imag.astype(np.float32))
    return cr, ci, np.ascontiguousarray(cr + ci)


_dft_mats = _ByteLRU(_build_dft_mats, max_entries=32,
                     max_bytes=DFT_MATS_CACHE_BYTES)


@functools.lru_cache(maxsize=32)
def _rdft_mats(n: int, scale: float):
    """Forward real-to-halfspectrum matrices (n, n//2+1): Yr = X @ Cr,
    Yi = X @ Ci (reference rfft layout, dim_x_freq = n//2+1 —
    reference: src/parameters/parameters.cpp:49)."""
    xf = n // 2 + 1
    k = np.arange(xf)
    m = np.exp(-2j * np.pi * np.outer(np.arange(n), k) / n) * scale
    return (np.ascontiguousarray(m.real.astype(np.float32)),
            np.ascontiguousarray(m.imag.astype(np.float32)))


@functools.lru_cache(maxsize=32)
def _irdft_mats(n: int, scale: float):
    """Halfspectrum-to-real matrices (n//2+1, n): x = Yr @ A + Yi @ B.

    From hermitian symmetry: x[m] = sum_k w[k] (Yr[k] cos(2 pi k m / n)
    - Yi[k] sin(2 pi k m / n)) with w = 1 for the self-conjugate bins
    (k=0 and, for even n, k=n/2) and 2 otherwise. The doubling absorbs
    the missing negative-frequency half; no complex op and no XLA C2R
    involved (the TPU backend's rank-3 irfft silently corrupts large
    batches — see stages._irfft_last).
    """
    xf = n // 2 + 1
    k = np.arange(xf)
    w = np.full(xf, 2.0)
    w[0] = 1.0
    if n % 2 == 0:
        w[-1] = 1.0
    ang = 2 * np.pi * np.outer(k, np.arange(n)) / n
    # x[m] = sum_k w Re(Y[k] e^{+i ang}) = sum_k w (Yr cos - Yi sin)
    a = (w[:, None] * np.cos(ang)) * scale
    b = (w[:, None] * -np.sin(ang)) * scale
    return (np.ascontiguousarray(a.astype(np.float32)),
            np.ascontiguousarray(b.astype(np.float32)))


def _sub_rows(mats, rows):
    """Row-select a matrix pair/triple: the split-x path applies the DFT
    from only the occupied input positions ``rows`` (wrapped windows are
    just non-contiguous row selections — no roll/pad stage needed)."""
    rows = np.asarray(rows)
    return tuple(np.ascontiguousarray(m[rows]) for m in mats)


def _sub_cols(mats, cols):
    """Column-select a matrix pair/triple: produce only the occupied
    output positions ``cols``."""
    cols = np.asarray(cols)
    return tuple(np.ascontiguousarray(m[:, cols]) for m in mats)


def _dot(a, c):
    """(..., K) @ (K, M) -> (..., M) at HIGHEST precision."""
    return jax.lax.dot_general(a, jnp.asarray(c),
                               (((a.ndim - 1,), (0,)), ((), ())),
                               precision=_HIGHEST)


# -- planar complex DFT ------------------------------------------------------

def pdft_last(xr, xi, mats):
    """Complex DFT along the minor axis on planar operands.

    Karatsuba 3-mult: P1 = Xr Cr, P2 = Xi Ci, P3 = (Xr+Xi)(Cr+Ci);
    Yr = P1 - P2, Yi = P3 - P1 - P2 (the (Cr+Ci) sum is a plan-time
    constant, so the extra operand add is on the small matrix, not the
    data). Dispatches to the two-stage Cooley-Tukey form when ``mats``
    is a :class:`TwoStageMats` (axis length above ``MATMUL_DFT_MAX``).
    """
    if isinstance(mats, TwoStageMats):
        return _pdft_two_stage(xr, xi, mats)
    cr, ci, cs = mats
    p1 = _dot(xr, cr)
    p2 = _dot(xi, ci)
    p3 = _dot(xr + xi, cs)
    return p1 - p2, p3 - p1 - p2


def _dot_mid(a, c):
    """(..., K, M) @ (K, J) -> (..., M, J) at HIGHEST precision:
    contracts the SECOND-minor axis (dot_general appends the rhs free
    dim after the lhs free dims, so the result needs no transpose)."""
    return jax.lax.dot_general(a, jnp.asarray(c),
                               (((a.ndim - 2,), (0,)), ((), ())),
                               precision=_HIGHEST)


def _pdft_mid(xr, xi, mats):
    """Karatsuba complex DFT contracting the second-minor axis."""
    cr, ci, cs = mats
    p1 = _dot_mid(xr, cr)
    p2 = _dot_mid(xi, ci)
    p3 = _dot_mid(xr + xi, cs)
    return p1 - p2, p3 - p1 - p2


def _pdft_two_stage(xr, xi, m: "TwoStageMats"):
    """Two-stage Cooley-Tukey DFT of length n1*n2 on planar minor-axis
    operands. With n = i1*n2 + i2 and k = k2*n1 + k1:

      X[k] = sum_{i2} W_{n2}^{i2 k2} * T[i2, k1]
             * sum_{i1} x[i1*n2 + i2] W_{n1}^{i1 k1}

    stage 1 contracts i1 (second-minor after the reshape) producing
    (..., i2, k1); the twiddle T[i2, k1] = W_n^{i2 k1} is a fused
    elementwise complex multiply; stage 2 contracts i2 producing
    (..., k1, k2); the final swap orders flat k = k2*n1 + k1. Total
    flops ~ n*(n1+n2) vs n^2 direct — 16x fewer at n=1024."""
    lead = xr.shape[:-1]
    n = m.n1 * m.n2
    xr = xr.reshape(lead + (m.n1, m.n2))
    xi = xi.reshape(lead + (m.n1, m.n2))
    ar, ai = _pdft_mid(xr, xi, m.mats1)          # (..., n2, k1)
    tr, ti = jnp.asarray(m.tr), jnp.asarray(m.ti)
    br = ar * tr - ai * ti
    bi = ar * ti + ai * tr
    yr, yi = _pdft_mid(br, bi, m.mats2)          # (..., k1, k2)
    yr = jnp.swapaxes(yr, -1, -2).reshape(lead + (n,))
    yi = jnp.swapaxes(yi, -1, -2).reshape(lead + (n,))
    return yr, yi


def cdft_last(x, mats):
    """Complex-dtype wrapper of :func:`pdft_last` (drop-in inside jit:
    XLA splits/joins the complex pair for free). Routed through the
    fused-kernel dispatch so the distributed stage wrappers
    (ops.stages.z_backward etc., executing inside shard_map on a real
    TPU mesh) get the fused stage too."""
    yr, yi = pdft_last_opt(jnp.real(x), jnp.imag(x), mats)
    return yr + 1j * yi


# -- real transforms ---------------------------------------------------------

def prdft_last(x, mats):
    """Real forward DFT along the minor axis -> planar half spectrum
    (..., n//2+1): two dots, half the flops of the complex form."""
    a, b = mats
    return _dot(x, a), _dot(x, b)


def pirdft_last(yr, yi, mats):
    """Planar half spectrum -> real inverse along the minor axis
    (..., n): two dots; hermitian doubling folded into the matrices."""
    a, b = mats
    return _dot(yr, a) + _dot(yi, b)


# -- fused-kernel dispatch ---------------------------------------------------
#
# The plan pipelines call these instead of the raw stage functions: on a
# TPU backend with f32 operands and plain (non-Cooley-Tukey) matrices the
# stage executes as a fused Pallas kernel (ops.dft_kernel — one HBM read,
# one write, Karatsuba combine in VMEM); everything else takes the XLA
# form above, same math and layouts. SPFFT_TPU_FUSED_STAGE=0 forces the
# XLA form everywhere (the probes' A/B knob).

def _fused_ok(xr, *mats_list, cap=None) -> bool:
    from . import dft_kernel as dk
    return (dk.enabled() and xr.dtype == jnp.float32
            and dk.eligible_mats(*mats_list, cap=cap))


def _fits2_ok(mode, xr, mats1, mats2) -> bool:
    """Shared two-stage dispatch gate: 3-D f32 operand, eligible plain
    matrices (eligible_mats rejects TwoStageMats and over-cap axes),
    kernel enabled, and the mode's VMEM fit."""
    if xr.ndim != 3 or not _fused_ok(xr, mats1, mats2):
        return False
    from . import dft_kernel as dk
    return dk.fits2(mode, xr.shape[1], xr.shape[2],
                    mats1[0].shape[1], mats2[0].shape[1])


def pdft_last_opt(xr, xi, mats):
    """:func:`pdft_last` through the fused stage kernel when eligible.
    Complex 3-matrix tuples only — a 2-matrix rdft tuple would pass the
    shared eligibility check (it is valid for the two-stage kernels) but
    crash the single-stage kernel's unpack.

    2-D operands (the z-stages, and the vmapped batched z-stages) take
    the kernel up to the full matmul cap: standalone the kernel beats
    the XLA stage at 384/512 too (4.09 vs 4.82 / 12.63 vs 13.58 ms —
    probe_r5_colblock.py); the >320 pair-level LOSS that set
    dft_kernel.max_dim() comes from the materialised swapaxes between
    kernel xy stages (XLA dots absorb those transposes via layout
    freedom, Pallas boundaries cannot), which a z-stage does not have."""
    if (not isinstance(mats, TwoStageMats) and len(mats) == 3
            and _fused_ok(xr, mats, cap=(MATMUL_DFT_MAX if xr.ndim == 2
                                         else None))):
        from . import dft_kernel as dk
        if dk.fits1(*np.shape(mats[0])):
            return dk.pdft_last(xr, xi, mats)
    return pdft_last(xr, xi, mats)


def _swap_pair(gr, gi):
    return jnp.swapaxes(gr, -1, -2), jnp.swapaxes(gi, -1, -2)


@jax.named_scope("spfft.xy")
def pdft2_minor(xr, xi, mats1, mats2):
    """[minor DFT (mats1), transpose, minor DFT (mats2)] on planar
    complex ``(P, A, B)`` operands -> ``(P, B', A')``: one fused kernel
    when eligible, else the three-pass XLA form with per-stage fusion."""
    if _fits2_ok("cc", xr, mats1, mats2):
        from . import dft_kernel as dk
        return dk.pdft2(xr, xi, mats1, mats2)
    gr, gi = pdft_last_opt(xr, xi, mats1)
    gr, gi = _swap_pair(gr, gi)
    return pdft_last_opt(gr, gi, mats2)


@jax.named_scope("spfft.xy")
def prdft2_minor(x, mats1, mats2):
    """R2C head twin of :func:`pdft2_minor`: real in, rdft stage 1."""
    if _fits2_ok("rc", x, mats1, mats2):
        from . import dft_kernel as dk
        return dk.prdft2(x, mats1, mats2)
    gr, gi = prdft_last(x, mats1)
    gr, gi = _swap_pair(gr, gi)
    return pdft_last_opt(gr, gi, mats2)


def cdft2_xy(x, mats_minor, mats_mid):
    """[minor-axis DFT (mats_minor), mid-axis DFT (mats_mid)] on a
    complex ``(..., mid, minor)`` operand -> ``(..., k_mid, k_minor)``
    — the distributed xy-stage shape (ops.stages.xy_*_c2c). One fused
    kernel with both transposes in VMEM when eligible; otherwise the
    XLA pair of stages around materialised swaps."""
    xr, xi = jnp.real(x), jnp.imag(x)
    if _fits2_ok("cc", xr, mats_minor, mats_mid):
        from . import dft_kernel as dk
        yr, yi = dk.pdft2_swapped(xr, xi, mats_minor, mats_mid)
        return yr + 1j * yi
    y = cdft_last(x, mats_minor)
    y = cdft_last(jnp.swapaxes(y, -1, -2), mats_mid)
    return jnp.swapaxes(y, -1, -2)


@jax.named_scope("spfft.xy")
def pdft2_minor_cr(xr, xi, mats1, mats2):
    """C2R tail twin of :func:`pdft2_minor`: irdft stage 2, real out."""
    if _fits2_ok("cr", xr, mats1, mats2):
        from . import dft_kernel as dk
        return dk.pdft2_cr(xr, xi, mats1, mats2)
    gr, gi = pdft_last_opt(xr, xi, mats1)
    gr, gi = _swap_pair(gr, gi)
    return pirdft_last(gr, gi, mats2)


# -- stage-level helpers (mats builders with scale folding) ------------------

@dataclasses.dataclass(frozen=True)
class TwoStageMats:
    """Plan-time constants of the two-stage Cooley-Tukey DFT (see
    :func:`_pdft_two_stage`): stage matrices for the two factors plus
    the planar (n2, n1) twiddle. The caller's scale is folded into the
    stage-2 matrices."""

    n1: int
    n2: int
    mats1: tuple
    mats2: tuple
    tr: np.ndarray
    ti: np.ndarray


@functools.lru_cache(maxsize=1024)
def two_stage_factor(n: int):
    """The balanced factorization ``(n1, n2)`` with ``n1 * n2 == n``,
    both factors <= ``MATMUL_DFT_MAX`` and ``n1 + n2`` minimal (fewest
    MXU flops) — or ``None`` when ``n`` fits the direct form or has no
    such factorization (primes above the cap)."""
    if n <= MATMUL_DFT_MAX:
        return None
    import math
    for n1 in range(math.isqrt(n), 1, -1):
        if n % n1 == 0:
            n2 = n // n1
            if n1 <= MATMUL_DFT_MAX and n2 <= MATMUL_DFT_MAX:
                return n1, n2
            return None  # n2 only grows as n1 shrinks
    return None


def matmul_dft_limit() -> int:
    """Largest axis length the matmul-DFT layer can ever cover (the
    two-stage form with both factors at the cap). Individual lengths
    still need a valid factorization — gate with
    :func:`use_matmul_dft`."""
    return MATMUL_DFT_MAX * MATMUL_DFT_MAX


@functools.lru_cache(maxsize=32)
def _two_stage_mats(n: int, s: int, scale: float) -> TwoStageMats:
    n1, n2 = two_stage_factor(n)
    ang = s * 2 * np.pi * np.outer(np.arange(n2), np.arange(n1)) / n
    return TwoStageMats(n1, n2, _dft_mats(n1, s, 1.0),
                        _dft_mats(n2, s, scale),
                        np.ascontiguousarray(np.cos(ang).astype(np.float32)),
                        np.ascontiguousarray(np.sin(ang).astype(np.float32)))


def c2c_mats(n: int, sign: int, scale: float = 1.0):
    """Matrices for a complex length-``n`` DFT; ``scale`` is folded in.
    ``sign=BACKWARD`` with ``scale=1`` gives the library's unnormalised
    inverse (ifft * n — docs/source/details.rst 'Transform Definition'
    semantics, matching stages.z_backward). Lengths above
    ``MATMUL_DFT_MAX`` return :class:`TwoStageMats` (pdft_last
    dispatches on the type)."""
    s = +1 if sign == BACKWARD else -1
    # BACKWARD is the unnormalised inverse: e^{+...} with no 1/n — the
    # caller's extra scale folds directly either way
    if n > MATMUL_DFT_MAX:
        if two_stage_factor(n) is not None:
            return _two_stage_mats(n, s, float(scale))
        if n <= MATMUL_DFT_DIRECT_FALLBACK_MAX:
            # unfactorable (prime-class) length: direct form (see
            # MATMUL_DFT_DIRECT_FALLBACK_MAX for the flop rationale)
            return _dft_mats(n, s, float(scale))
        raise ValueError(
            f"axis length {n} exceeds MATMUL_DFT_MAX={MATMUL_DFT_MAX} "
            f"with no two-factor split and exceeds the direct fallback "
            f"cap {MATMUL_DFT_DIRECT_FALLBACK_MAX} — gate with "
            f"use_matmul_dft()")
    return _dft_mats(n, s, float(scale))


def r2c_mats(n: int, scale: float = 1.0):
    return _rdft_mats(n, float(scale))


def c2r_mats(n: int, scale: float = 1.0):
    """Unnormalised inverse real transform: irfft * n equivalents."""
    return _irdft_mats(n, float(scale))


@functools.lru_cache(maxsize=32)
def sub_rows_mats(n: int, sign: int, rows: tuple, scale: float = 1.0):
    """Row-selected complex DFT matrices (cached per window): the
    split-x contraction from the occupied positions only."""
    return _sub_rows(c2c_mats(n, sign, scale), np.asarray(rows))


@functools.lru_cache(maxsize=32)
def sub_cols_mats(n: int, sign: int, cols: tuple, scale: float = 1.0):
    """Column-selected complex DFT matrices (cached per window)."""
    return _sub_cols(c2c_mats(n, sign, scale), np.asarray(cols))


@functools.lru_cache(maxsize=32)
def sub_rows_c2r_mats(n: int, rows: tuple, scale: float = 1.0):
    """Row-selected inverse-real matrices: half-spectrum window -> dense
    real axis (hermitian weights ride along with their rows)."""
    return _sub_rows(c2r_mats(n, scale), np.asarray(rows))


@functools.lru_cache(maxsize=32)
def sub_cols_r2c_mats(n: int, cols: tuple, scale: float = 1.0):
    """Column-selected forward-real matrices: real axis -> half-spectrum
    window."""
    return _sub_cols(r2c_mats(n, scale), np.asarray(cols))


def mdft_axes(dtype, *dims, direct=(), direct_any=()) -> bool:
    """THE shared matmul-DFT routing predicate (one home so the plan
    pipeline, the stage-level xy gates and the precision model cannot
    drift): every axis in ``dims`` must be coverable (direct or
    two-stage — per axis, not just the max: one unfactorable axis above
    the fallback cap must fail the whole gate). Axes in ``direct``
    additionally need PLAIN c2c matrices (split-window row/column
    selections of ``c2c_mats``; composite lengths above the cap return
    TwoStageMats and do not qualify). Axes in ``direct_any`` need only
    a real-transform builder (``r2c_mats``/``c2r_mats`` are plain
    direct matrices at ANY length up to the fallback cap — composite
    768-class R2C x-axes included)."""
    return (all(use_matmul_dft(d, dtype) for d in dims)
            and all(_direct_form_len(d) for d in direct)
            and all(d <= MATMUL_DFT_DIRECT_FALLBACK_MAX
                    for d in direct_any))


def mdft_coverable(dims, hermitian: bool = False) -> bool:
    """Backend-independent STRUCTURAL half of the routing predicate:
    could these axes run the matmul-DFT forms at all (direct or
    two-stage; hermitian x-axis = ``dims[0]`` direct-only)? Used by the
    precision model, which must not depend on the importing process's
    backend."""
    ok = all(_mdft_covered_len(d) for d in dims)
    return ok and (not hermitian
                   or dims[0] <= MATMUL_DFT_DIRECT_FALLBACK_MAX)


def use_matmul_dft(n: int, dtype) -> bool:
    """Route a length-``n`` axis through the matmul DFT? TPU backend,
    single precision, direct form or a valid two-stage factorization.
    CPU keeps pocketfft (a real O(N log N) FFT); double precision keeps
    jnp.fft (f64 dots are emulated and slow on TPU, and the double path
    is CPU-bound anyway — docs/precision.md)."""
    import os
    single = jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                  jnp.dtype(jnp.complex64))
    covered = _mdft_covered_len(n)
    if os.environ.get("SPFFT_TPU_FORCE_MATMUL_DFT") == "1":
        return single and covered  # force past the backend gate
    if os.environ.get("SPFFT_TPU_NO_MATMUL_DFT") == "1":
        return False
    return jax.default_backend() == "tpu" and covered and single
