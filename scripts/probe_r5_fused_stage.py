#!/usr/bin/env python
"""Round-5 probe: fused Pallas matmul-DFT stage vs the XLA 3-dot form.

The XLA pdft_last materializes p1/p2/p3/(xr+xi) as grid-sized HBM
intermediates around the combine (three matmuls cannot share one fused
elementwise chain). A Pallas kernel does the three dots + combine per
row tile entirely in VMEM: one read of (xr, xi), one write of (yr, yi).
Measured with the shared sync-cancelling estimator
(utils/benchtime.py).

Usage: python scripts/probe_r5_fused_stage.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from spfft_tpu.ops import dft
from spfft_tpu.utils.benchtime import diff_estimate_seconds

_HI = jax.lax.Precision.HIGHEST


def _kernel(xr_ref, xi_ref, cr_ref, ci_ref, cs_ref, yr_ref, yi_ref):
    a = xr_ref[...]
    b = xi_ref[...]
    dn = (((1,), (0,)), ((), ()))
    p1 = jax.lax.dot_general(a, cr_ref[...], dn, precision=_HI,
                             preferred_element_type=jnp.float32)
    p2 = jax.lax.dot_general(b, ci_ref[...], dn, precision=_HI,
                             preferred_element_type=jnp.float32)
    p3 = jax.lax.dot_general(a + b, cs_ref[...], dn, precision=_HI,
                             preferred_element_type=jnp.float32)
    yr_ref[...] = p1 - p2
    yi_ref[...] = p3 - p1 - p2


def fused_pdft_last(xr, xi, mats, tm=1024):
    cr, ci, cs = (jnp.asarray(m) for m in mats)
    k, mo = cr.shape
    lead = xr.shape[:-1]
    m = int(np.prod(lead)) if lead else 1
    xr2 = xr.reshape(m, k)
    xi2 = xi.reshape(m, k)
    grid = (pl.cdiv(m, tm),)
    yr, yi = pl.pallas_call(
        _kernel,
        grid=grid,
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
    )(xr2, xi2, cr, ci, cs)
    return yr.reshape(lead + (mo,)), yi.reshape(lead + (mo,))


def sync(pair):
    return float(np.asarray(jnp.real(pair[0]).ravel()[0]))


def bench(fn, xr, xi, mats, chain=4, reps=16):
    def body(a, b):
        for _ in range(chain):
            a, b = fn(a, b, mats)
        return a, b
    g = jax.jit(body)
    sync(g(xr, xi))

    def grp(k):
        t0 = time.perf_counter()
        o = (xr, xi)
        for _ in range(k):
            o = g(xr, xi)
        sync(o)
        return time.perf_counter() - t0
    est = diff_estimate_seconds(grp, reps=reps)
    return est.seconds / chain


def main():
    n = int(os.environ.get("N", 256))
    m = int(os.environ.get("M", 256 * 256))
    rng = np.random.default_rng(5)
    xr64 = rng.standard_normal((m, n))
    xi64 = rng.standard_normal((m, n))
    mats = dft.c2c_mats(n, dft.BACKWARD)
    cr64, ci64 = np.asarray(mats[0], np.float64), np.asarray(mats[1], np.float64)
    ref_r = xr64 @ cr64 - xi64 @ ci64

    xr = jnp.asarray(xr64, jnp.float32)
    xi = jnp.asarray(xi64, jnp.float32)

    for label, fn in [("xla ", dft.pdft_last),
                      ("plls", fused_pdft_last)]:
        yr, yi = jax.jit(lambda a, b: fn(a, b, mats))(xr, xi)
        err = np.linalg.norm(np.asarray(yr, np.float64) - ref_r) / \
            np.linalg.norm(ref_r)
        t = bench(fn, xr, xi, mats)
        gb = (4 * m * n * 4) / 1e9
        print(f"{label} N={n} M={m}: {t*1e3:7.3f} ms/stage  rel {err:.3e}  "
              f"eff {(gb/t):6.1f} GB/s", flush=True)

    # awkward shapes: M not tile-aligned, K != Mout (r2c-like sub-rows)
    for (mm, kk) in [(51471, 256), (33333, 129)]:
        xr64 = rng.standard_normal((mm, kk))
        xi64 = rng.standard_normal((mm, kk))
        sub = dft.sub_rows_mats(n, dft.BACKWARD, tuple(range(kk))) \
            if kk != n else dft.c2c_mats(n, dft.BACKWARD)
        cr64, ci64 = (np.asarray(sub[0], np.float64),
                      np.asarray(sub[1], np.float64))
        ref_r = xr64 @ cr64 - xi64 @ ci64
        a = jnp.asarray(xr64, jnp.float32)
        b = jnp.asarray(xi64, jnp.float32)
        yr, yi = jax.jit(lambda p, q: fused_pdft_last(p, q, sub))(a, b)
        err = np.linalg.norm(np.asarray(yr, np.float64) - ref_r) / \
            np.linalg.norm(ref_r)
        print(f"plls M={mm} K={kk}: rel {err:.3e}", flush=True)


if __name__ == "__main__":
    main()
