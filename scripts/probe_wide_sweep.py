#!/usr/bin/env python
"""Sweep wide-kernel geometry (P, kp, K) per direction at north-star scale.

Reports min-of-N scanned measurements (run-to-run variance makes single
runs unreliable — VERDICT r2). DIM=256, N=3 by default.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from spfft_tpu.ops import gather_kernel as gk
from spfft_tpu.indexing import build_index_plan
from spfft_tpu.types import TransformType
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

R = int(os.environ.get("REPS", 20))
N = int(os.environ.get("N", 3))


def sync(x):
    float(np.asarray(jnp.real(jax.tree_util.tree_leaves(x)[0]).ravel()[0]))


def scan_seconds_min(body, x):
    def run(x0):
        def step(c, _):
            xp = jax.tree_util.tree_map(
                lambda a: a * a.dtype.type(1.0 + 1e-7), c)
            out = body(xp)
            return xp, sum(jnp.mean(o) for o in jax.tree_util.tree_leaves(out))
        _, ys = jax.lax.scan(step, x0, None, length=R)
        return ys
    f = jax.jit(run)
    out = f(x); sync(out)
    best = np.inf
    for _ in range(N):
        t0 = time.perf_counter()
        out = f(x); sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def bench(name, idx, valid, num_src, combos):
    rng = np.random.default_rng(1)
    src = rng.standard_normal(num_src).astype(np.float32)
    srci = rng.standard_normal(num_src).astype(np.float32)
    want = np.where(valid, src[np.clip(idx, 0, num_src - 1)], 0)
    results = []
    for (P, kp, K) in combos:
        try:
            t = gk.build_wide_gather_tables(idx, valid, num_src, p_tiles=P,
                                            kp_rows=kp, k_rows=K)
        except Exception as e:
            print(f"{name} P={P} kp={kp} K={K}: build fail {e}")
            continue
        if t is None:
            print(f"{name} P={P} kp={kp} K={K}: tables=None")
            continue
        dev = gk.gather_device_tables(t)
        pad = t.src_rows * 128 - num_src
        re = jnp.asarray(np.pad(src, (0, pad)).reshape(t.src_rows, 128))
        im = jnp.asarray(np.pad(srci, (0, pad)).reshape(t.src_rows, 128))
        try:
            out = gk.run_gather(re, im, dev, t)
            got = np.asarray(out[0]).reshape(-1)[:t.num_out]
            ok = np.allclose(got, want, atol=1e-5)
            cal = scan_seconds_min(lambda x: (x[0], x[1]), (re, im))
            tot = scan_seconds_min(
                lambda x: gk.run_gather(x[0], x[1], dev, t), (re, im))
            dt = (tot - cal) / R
        except Exception as e:
            print(f"{name} P={P} kp={kp} K={t.span_rows}: run fail "
                  f"{type(e).__name__} {str(e)[:150]}")
            continue
        C = t.row0.shape[0]
        print(f"{name} P={P} kp={t.kp_rows} K={t.span_rows}: "
              f"{'OK' if ok else 'MISMATCH'} C={C} -> {dt*1e3:.3f} ms "
              f"({dt/C*1e9:.0f} ns/step)", flush=True)
        results.append((dt, P, t.kp_rows, t.span_rows))
    if results:
        best = min(results)
        print(f"{name} BEST: {best[0]*1e3:.3f} ms at P={best[1]} "
              f"kp={best[2]} K={best[3]}", flush=True)


def main():
    n = int(os.environ.get("DIM", "256"))
    triplets = spherical_cutoff_triplets(n)
    p = build_index_plan(TransformType.C2C, n, n, n, triplets)
    vi = p.value_indices.astype(np.int64)
    num_slots = p.num_sticks * p.dim_z
    print(f"dim={n} values={p.num_values} slots={num_slots}", flush=True)
    (dec_idx, occ), (cmp_idx, cmp_valid) = gk.compression_gather_inputs(
        vi, num_slots)
    bench("decompress", dec_idx, occ, p.num_values,
          [(8, 12, 0), (8, 16, 0), (16, 12, 0), (16, 16, 0), (8, 8, 0),
           (16, 8, 0)])
    bench("compress", cmp_idx, cmp_valid, num_slots,
          [(8, 12, 0), (8, 12, 128), (8, 16, 128), (16, 12, 0),
           (16, 16, 0), (8, 24, 0)])


if __name__ == "__main__":
    print("devices:", jax.devices(), flush=True)
    main()
