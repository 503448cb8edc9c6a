"""Fused Pallas matmul-DFT stage kernels (TPU hot path).

The XLA form of a planar DFT stage (:func:`spfft_tpu.ops.dft.pdft_last`)
is three ``dot_general`` ops plus an elementwise Karatsuba combine. XLA
cannot carry one fused elementwise chain across three matmuls, so at
grid scale every stage materialises p1/p2/p3 and the (xr+xi) operand sum
as HBM intermediates around the dots. These kernels do the dots and the
combine per row tile entirely in VMEM — one HBM read of the operands,
one write of the results:

* :func:`pdft_last` — one stage, minor-axis contraction. Measured
  0.796 ms vs 1.087 ms for the XLA form at the 256^3 stage shape
  (M=65536, N=256), identical accuracy (rel 8.2e-8 vs numpy f64 —
  scripts/probe_r5_fused_stage.py).
* :func:`pdft2` (+ ``prdft2``/``pdft2_cr`` R2C twins) — TWO stages with
  the inter-stage transpose done in VMEM: stage-1 dot over the minor
  axis, swap of the two minor axes, stage-2 dot over the new minor
  axis. This removes the materialised grid-sized ``swapaxes`` pass
  between the xy stages. Measured 1.62 ms vs 2.07 ms for the XLA
  three-pass form at 256^3 (scripts/probe_r5_fused2d.py); the fused
  form is MXU-bound (~1.57 ms of 6-pass f32 matmul at this shape), so
  it sits at the precision ladder's floor.

Precision: Mosaic honours ``Precision.HIGHEST`` for f32 dots (measured
rel 8.1e-8 on a 256-point pass, identical to XLA HIGHEST —
scripts/probe_r5_pallas_dot.py), which is what keeps the library's
1e-6 contract available; ``Precision.HIGH`` is *rejected* by Mosaic and
DEFAULT fails the contract, so the kernels are HIGHEST-only.

Eligibility (:func:`eligible_mats` + :func:`fits2`): TPU backend, f32
operands, plain matrix tuples (the two-stage Cooley-Tukey path keeps
its XLA form), and axis lengths that fit the VMEM tiling budget.
Everything else falls back to the XLA path — same math, same layouts.
Disable with ``SPFFT_TPU_FUSED_STAGE=0`` (the A/B knob used by the
probes).

Reference parity: these kernels fuse what the reference runs as separate
batched FFTW/cuFFT executes plus explicit pack/unpack transposes
(reference: src/fft/transform_1d_host.hpp:76-118, the local transpose in
src/transpose/transpose_host.hpp:94-154); on TPU the transpose lives in
VMEM inside the same kernel instead of being a strided plan.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST
_DN = (((1,), (0,)), ((), ()))

#: Empirical ceiling of the fused kernels, independent of the matmul-DFT
#: cap: above 320 the two-stage xy kernel no longer fits VMEM, and the
#: single-stage kernel alone measures a net LOSS against the XLA stages
#: (same-session interleaved A/B: 384^3 pair 56.5 vs 54.2 ms, 512^3
#: 161.2 vs 148.2 — the shrunken row tiles forced by the compile
#: ceiling spend more on matrix streaming than the combine fusion
#: saves), while <= 320 wins (256^3 12.3 -> 10.5, 320^3 36.6 -> 33.1).
_EMPIRICAL_MAX = 320


def max_dim() -> int:
    """Longest axis the fused kernels accept: the empirical VMEM/perf
    ceiling clamped to the CURRENT matmul-DFT cap. Reads
    ``dft.MATMUL_DFT_MAX`` per call (module-attribute access, never
    bound at import) so monkeypatched/retuned caps propagate to kernel
    eligibility immediately (round-5 advisor finding)."""
    from . import dft
    return min(_EMPIRICAL_MAX, dft.MATMUL_DFT_MAX)

#: Per-kernel VMEM budget (bytes) the single-stage tile chooser aims
#: under. The compile ceiling on v5e is ~5.5 MB by the footprint
#: formula: Mosaic's double-buffering of streamed tiles and dot
#: accumulators roughly doubles it. The installed compiler accepts the
#: tiles this budget picks (256-class tm=1024, 384 -> tm=512,
#: 512 -> tm=256) and runs out of VMEM on the next tile up at 384 and
#: 512 (tests/test_tpu_compile.py::test_stage_kernel_compiles).
_VMEM_BUDGET = int(5.5 * 1024 * 1024)


def enabled() -> bool:
    """Fused stages are on by default on TPU; ``SPFFT_TPU_FUSED_STAGE=0``
    disables (read per trace so tests can flip it)."""
    return os.environ.get("SPFFT_TPU_FUSED_STAGE", "1").strip() != "0" \
        and jax.default_backend() == "tpu"


def _plain_mats(mats) -> bool:
    """True for a tuple of plain 2-D arrays (rejects TwoStageMats and
    anything else the XLA path special-cases)."""
    return (isinstance(mats, tuple) and len(mats) in (2, 3)
            and all(isinstance(m, (np.ndarray, jnp.ndarray)) and m.ndim == 2
                    for m in mats))


def eligible_mats(*mats_list, cap=None) -> bool:
    """All matrix tuples are plain and within the axis cap (default
    :func:`max_dim`; the z-stage dispatch passes the full matmul cap —
    see dft.pdft_last_opt)."""
    limit = max_dim() if cap is None else cap
    for mats in mats_list:
        if not _plain_mats(mats):
            return False
        if any(d > limit for m in mats for d in m.shape):
            return False
    return True


def _dot(a, c):
    return jax.lax.dot_general(a, c, _DN, precision=_HI,
                               preferred_element_type=jnp.float32)


def _kara(ar, ai, cr, ci, cs):
    """Karatsuba 3-mult complex DFT on 2-D planar operands."""
    p1 = _dot(ar, cr)
    p2 = _dot(ai, ci)
    p3 = _dot(ar + ai, cs)
    return p1 - p2, p3 - p1 - p2


# -- single fused stage ------------------------------------------------------

def _stage_kernel(xr_ref, xi_ref, cr_ref, ci_ref, cs_ref, yr_ref, yi_ref):
    yr, yi = _kara(xr_ref[...], xi_ref[...],
                   cr_ref[...], ci_ref[...], cs_ref[...])
    yr_ref[...] = yr
    yi_ref[...] = yi


def _stage_tm(k: int, mo: int):
    """Row-tile size: large tiles amortise the resident matrices; shrink
    until 2 in + 2 out tiles + 3 matrices fit the VMEM budget. Returns
    ``None`` when even tm=128 exceeds the budget (the matrices alone
    overflow it at retuned caps) — dispatchers must treat that as
    ineligible and keep the XLA form, mirroring the fits2/plane_tp
    pattern, instead of risking a Mosaic compile crash (round-5 advisor
    finding)."""
    for tm in (1024, 512, 256, 128):
        if (2 * tm * k + 2 * tm * mo + 3 * k * mo) * 4 <= _VMEM_BUDGET:
            return tm
    return None


def fits1(k: int, mo: int) -> bool:
    """Whether the single-stage kernel fits the VMEM budget at this
    matrix shape — the fits2 twin for :func:`pdft_last` dispatch."""
    return _stage_tm(k, mo) is not None


def pdft_last(xr, xi, mats, interpret: bool = False):
    """Fused planar complex DFT along the minor axis — drop-in for the
    eligible subset of :func:`spfft_tpu.ops.dft.pdft_last`."""
    cr, ci, cs = (jnp.asarray(m) for m in mats)
    k, mo = cr.shape
    lead = xr.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    tm = _stage_tm(k, mo)
    assert tm is not None, "caller must gate on fits1"
    yr, yi = pl.pallas_call(
        _stage_kernel,
        grid=(pl.cdiv(m, tm),),
        in_specs=[
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((tm, k), lambda i: (i, 0)),
            pl.BlockSpec((k, mo), lambda i: (0, 0)),
            pl.BlockSpec((k, mo), lambda i: (0, 0)),
            pl.BlockSpec((k, mo), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tm, mo), lambda i: (i, 0)),
            pl.BlockSpec((tm, mo), lambda i: (i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((m, mo), jnp.float32)] * 2,
        interpret=interpret,
    )(xr.reshape(m, k), xi.reshape(m, k), cr, ci, cs)
    return yr.reshape(lead + (mo,)), yi.reshape(lead + (mo,))


# -- fused two-stage (stage1 · in-VMEM transpose · stage2) -------------------

#: (input channels, output channels, stage-1 matrices, stage-2 matrices)
#: per two-stage kernel mode — the single source for operand counts and
#: VMEM sizing, shared by the eligibility gate, the driver and the
#: kernel body. 'cc' = complex->complex both stages; 'rc' = real-input
#: rdft stage 1 (R2C forward head); 'cr' = real-output irdft stage 2
#: (R2C backward tail).
_MODE_CHANNELS = {"cc": (2, 2, 3, 3), "rc": (1, 2, 2, 3),
                  "cr": (2, 1, 3, 2)}

#: Tighter budget for the two-stage kernels: their in-VMEM transpose and
#: two live dot accumulators cost Mosaic more than the footprint formula
#: sees (a tp=4 256-class kernel, ~7.8 MB by the formula, fails to
#: compile on v5e — probe_r5_fused2d.py), so aim well under half VMEM.
_VMEM_BUDGET2 = 5 * 1024 * 1024


def plane_tp(a_in, b_in, b_out, a_out, n_chan_in, n_chan_out,
             mats_elems):
    """Planes per grid step for the two-stage kernels, sized to VMEM
    (input + intermediate + output tiles per plane plus the resident
    matrices). ``None`` when even one plane per step does not fit —
    callers must fall back to the single-stage form."""
    per_plane = (n_chan_in * a_in * b_in + 2 * a_in * b_out
                 + n_chan_out * b_out * a_out) * 4
    mats = mats_elems * 4
    for tp in (4, 2, 1):
        if tp * per_plane + mats <= _VMEM_BUDGET2:
            return tp
    return None


def _tp2(mode: str, a_in: int, b_in: int, b_out: int, a_out: int):
    ci, co, m1, m2 = _MODE_CHANNELS[mode]
    return plane_tp(a_in, b_in, b_out, a_out, ci, co,
                    m1 * b_in * b_out + m2 * a_in * a_out)


def fits2(mode: str, a_in: int, b_in: int, b_out: int, a_out: int) -> bool:
    """Whether the two-stage kernel of ``mode`` ('cc'/'rc'/'cr') fits
    the VMEM budget at these axis lengths."""
    return _tp2(mode, a_in, b_in, b_out, a_out) is not None


def _kernel2(mode, swap_out, *refs):
    """Shared two-stage kernel body: stage-1 dot over the minor axis,
    in-VMEM swap of the two minor axes, stage-2 dot over the new minor
    axis. Operand refs are laid out [inputs, stage-1 mats, stage-2 mats,
    outputs] per ``_MODE_CHANNELS[mode]``. ``swap_out`` stores the
    result transposed back to ``(tp, a_out, b_out)`` — the layout the
    distributed xy wrappers end in — with one more in-VMEM swap instead
    of a materialised HBM pass."""
    n_in, n_out, m1, m2 = _MODE_CHANNELS[mode]
    ins = refs[:n_in]
    c1 = [r[...] for r in refs[n_in:n_in + m1]]
    c2 = [r[...] for r in refs[n_in + m1:n_in + m1 + m2]]
    outs = refs[n_in + m1 + m2:]
    tp, a_in, b_in = ins[0].shape
    b_out = c1[0].shape[1]
    flat = [r[...].reshape(tp * a_in, b_in) for r in ins]
    if mode == "rc":
        gr, gi = _dot(flat[0], c1[0]), _dot(flat[0], c1[1])
    else:
        gr, gi = _kara(flat[0], flat[1], *c1)
    gr = jnp.swapaxes(gr.reshape(tp, a_in, b_out), -1, -2) \
        .reshape(tp * b_out, a_in)
    gi = jnp.swapaxes(gi.reshape(tp, a_in, b_out), -1, -2) \
        .reshape(tp * b_out, a_in)

    def store(ref, h):
        h = h.reshape(tp, b_out, h.shape[1])
        ref[...] = jnp.swapaxes(h, -1, -2) if swap_out else h

    if mode == "cr":
        store(outs[0], _dot(gr, c2[0]) + _dot(gi, c2[1]))
    else:
        hr, hi = _kara(gr, gi, *c2)
        store(outs[0], hr)
        store(outs[1], hi)


def _run2(mode, ins, mats1, mats2, interpret, swap_out=False):
    c1 = tuple(jnp.asarray(m) for m in mats1)
    c2 = tuple(jnp.asarray(m) for m in mats2)
    n_in, n_out, m1, m2 = _MODE_CHANNELS[mode]
    p, a_in, b_in = ins[0].shape
    b_out = c1[0].shape[1]
    a_out = c2[0].shape[1]
    tp = _tp2(mode, a_in, b_in, b_out, a_out)
    assert tp is not None, "caller must gate on fits2"
    oshape = (a_out, b_out) if swap_out else (b_out, a_out)
    return pl.pallas_call(
        functools.partial(_kernel2, mode, swap_out),
        grid=(pl.cdiv(p, tp),),
        in_specs=[pl.BlockSpec((tp, a_in, b_in), lambda i: (i, 0, 0))] * n_in
        + [pl.BlockSpec((b_in, b_out), lambda i: (0, 0))] * m1
        + [pl.BlockSpec((a_in, a_out), lambda i: (0, 0))] * m2,
        out_specs=[pl.BlockSpec((tp,) + oshape,
                                lambda i: (i, 0, 0))] * n_out,
        out_shape=[jax.ShapeDtypeStruct((p,) + oshape,
                                        jnp.float32)] * n_out,
        interpret=interpret,
    )(*ins, *c1, *c2)


def pdft2(xr, xi, mats1, mats2, interpret: bool = False):
    """Fused [stage-1 minor dot, transpose, stage-2 minor dot] on planar
    complex operands: ``(P, A, B) -> (P, B', A')`` — replaces
    ``pdft_last(mats1) ; swapaxes(-1, -2) ; pdft_last(mats2)``."""
    yr, yi = _run2("cc", (xr, xi), mats1, mats2, interpret)
    return yr, yi


def prdft2(x, mats1, mats2, interpret: bool = False):
    """R2C head twin of :func:`pdft2`: real input, stage 1 is the
    half-spectrum real DFT (two dots), stage 2 complex."""
    yr, yi = _run2("rc", (x,), mats1, mats2, interpret)
    return yr, yi


def pdft2_cr(xr, xi, mats1, mats2, interpret: bool = False):
    """C2R tail twin of :func:`pdft2`: stage 1 complex, stage 2 the real
    inverse DFT (two dots into one real output)."""
    return _run2("cr", (xr, xi), mats1, mats2, interpret)[0]


def pdft2_swapped(xr, xi, mats1, mats2, interpret: bool = False):
    """:func:`pdft2` with the result stored back in ``(P, A', B')``
    order (one more in-VMEM swap) — the layout the distributed xy stage
    wrappers produce, replacing their two materialised ``swapaxes``
    passes (ops.stages._cdft_mid)."""
    yr, yi = _run2("cc", (xr, xi), mats1, mats2, interpret, swap_out=True)
    return yr, yi
