#!/usr/bin/env python
"""Probe: Mosaic support + cost for the round-3 "wide" gather kernel design.

Questions:
  1. Can a kernel read a (kp, 128) sub-window of VMEM scratch at a TRACED
     sublane offset (``sc[slot, chan, pl.ds(sub, kp)]``)? Aligned (multiple
     of 8) and unaligned variants.
  2. What is the per-grid-step cost of the wide structure (P tiles/step,
     one K-row DMA, P * kp select rows) vs the narrow kernel's measured
     ~450-500 ns/step?

Run on the TPU: ``python scripts/probe_wide_kernel.py``.
"""
import functools
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def probe_dynamic_slice(aligned: bool):
    """Tiny kernel: out[i] = win[sub + i] for a traced sub read from SMEM."""
    K, kp = 64, 16

    def kernel(sub_ref, x_ref, o_ref):
        sub = sub_ref[0]
        o_ref[...] = x_ref[pl.ds(sub, kp), :]

    x = jnp.arange(K * 128, dtype=jnp.float32).reshape(K, 128)
    sub = jnp.array([8 if aligned else 5], jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((K, 128), lambda g, s: (0, 0))],
            out_specs=pl.BlockSpec((kp, 128), lambda g, s: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((kp, 128), jnp.float32),
    )(sub, x)
    want = np.asarray(x)[int(sub[0]):int(sub[0]) + kp]
    ok = np.array_equal(np.asarray(out), want)
    return ok


def probe_dynamic_row(aligned: bool):
    """Per-row variant: read single rows at traced offsets."""
    K, kp = 64, 16

    def kernel(sub_ref, x_ref, o_ref):
        sub = sub_ref[0]
        acc = jnp.zeros((kp, 128), jnp.float32)
        for k in range(kp):
            acc = acc.at[k].set(x_ref[sub + k, :])
        o_ref[...] = acc

    x = jnp.arange(K * 128, dtype=jnp.float32).reshape(K, 128)
    sub = jnp.array([8 if aligned else 5], jnp.int32)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec((K, 128), lambda g, s: (0, 0))],
            out_specs=pl.BlockSpec((kp, 128), lambda g, s: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((kp, 128), jnp.float32),
    )(sub, x)
    want = np.asarray(x)[int(sub[0]):int(sub[0]) + kp]
    return np.array_equal(np.asarray(out), want)


def time_step_structure(P: int, kp: int, K: int, C: int, reps: int = 20):
    """A skeleton of the wide kernel: C grid steps, each DMAs K rows from
    HBM, does P * kp select-gather rows, accumulates into P output tiles.
    Tables are trivial (identity-ish) — measures structure cost only."""

    TILE_SUB, TILE_LANE = 8, 128

    def kernel(row0_ref, sub_ref, packed_ref, re_hbm, o_ref, sc, sem):
        g = pl.program_id(0)
        n_g = pl.num_programs(0)

        def dma(gg, slot):
            return pltpu.make_async_copy(
                re_hbm.at[pl.ds(row0_ref[gg], K), :], sc.at[slot],
                sem.at[slot])

        @pl.when(g == 0)
        def _():
            dma(0, 0).start()

        @pl.when(g + 1 < n_g)
        def _():
            dma(g + 1, jax.lax.rem(g + 1, jnp.int32(2))).start()

        slot = jax.lax.rem(g, jnp.int32(2))
        dma(g, slot).wait()

        for p in range(P):
            word = sub_ref[g, p // 4]
            sub = (word >> (8 * (p % 4))) & 0xFF
            t = packed_ref[0, p]
            lane = t & 127
            row = (t >> 7) & 0x1FFF
            m = (t >> 20).astype(jnp.float32)
            acc = jnp.zeros((TILE_SUB, TILE_LANE), jnp.float32)
            win = sc[slot, pl.ds(sub, kp), :]
            for k in range(kp):
                sel = row == k
                src = jnp.broadcast_to(win[k][None, :],
                                       (TILE_SUB, TILE_LANE))
                acc += jnp.where(sel, jnp.take_along_axis(src, lane, axis=1),
                                 0)
            o_ref[p] = acc * m

    rng = np.random.default_rng(0)
    src_rows = C + K + 8
    re = jnp.asarray(rng.standard_normal((src_rows, 128)), jnp.float32)
    row0 = jnp.asarray(np.arange(C, dtype=np.int32))
    sub = jnp.asarray(rng.integers(0, min(8, K - kp), (C, 2)).astype(np.int32))
    packed = jnp.asarray(
        (rng.integers(0, 128, (C, P, 8, 128))
         | (rng.integers(0, kp, (C, P, 8, 128)) << 7)
         | (1 << 20)).astype(np.int32))

    f = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(C,),
            in_specs=[
                pl.BlockSpec((1, P, 8, 128), lambda g, r0, s: (g, 0, 0, 0)),
                pl.BlockSpec(memory_space=pltpu.ANY),
            ],
            out_specs=pl.BlockSpec((P, 8, 128), lambda g, r0, s: (g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, K, 128), jnp.float32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C * P, 8, 128), jnp.float32),
    )
    # Per-call dispatch cost swamps one kernel: time R scanned
    # kernel steps inside ONE executable, subtract a calibration scan
    # (perturb + consume only), exactly as scripts/profile_stages.py does.
    R = 20

    def scan_seconds(body):
        def run(x0):
            def step(c, _):
                xp = c * jnp.float32(1.0 + 1e-7)
                return xp, jnp.mean(body(xp))
            _, ys = jax.lax.scan(step, x0, None, length=R)
            return ys
        h = jax.jit(run)
        out = h(re)
        float(np.asarray(out.ravel()[0]))
        t0 = time.perf_counter()
        for _ in range(3):
            out = h(re)
        float(np.asarray(out.ravel()[0]))
        return (time.perf_counter() - t0) / 3

    calib = scan_seconds(lambda xp: xp)
    total = scan_seconds(lambda xp: f(row0, sub, packed, xp))
    dt = (total - calib) / R
    return dt, dt / C


if __name__ == "__main__":
    for name, fn in (("dyn-slice aligned", lambda: probe_dynamic_slice(True)),
                     ("dyn-slice unaligned",
                      lambda: probe_dynamic_slice(False)),
                     ("dyn-row aligned", lambda: probe_dynamic_row(True)),
                     ("dyn-row unaligned", lambda: probe_dynamic_row(False))):
        try:
            ok = fn()
            print(f"{name}: {'OK' if ok else 'WRONG RESULT'}")
        except Exception as e:
            print(f"{name}: FAIL — {type(e).__name__}: {str(e)[:300]}")

    for P, kp, K, C in ((8, 16, 80, 1600), (8, 10, 80, 1600),
                        (16, 10, 160, 800), (4, 10, 48, 3200),
                        (8, 16, 80, 100)):
        try:
            dt, per = time_step_structure(P, kp, K, C)
            print(f"P={P} kp={kp} K={K} C={C}: total {dt*1e3:.3f} ms, "
                  f"{per*1e9:.0f} ns/step, "
                  f"{C*P*1024/dt/1e9:.2f} Gslot/s")
        except Exception as e:
            print(f"P={P} kp={kp} K={K} C={C}: FAIL — "
                  f"{type(e).__name__}: {str(e)[:300]}")
