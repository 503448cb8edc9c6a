#!/usr/bin/env python
"""Round-5 probe: per-dispatch floor vs compute scaling.

probe_r5_fused_stage measured ONE 256-point stage at ~11 ms — the same
wall-clock as the whole 6-stage fused pair. If a fixed per-dispatch cost
(host round trip) dominates, chaining k stages inside one jit
should stay nearly flat in k; if compute dominates, it should scale
linearly. Also times a trivial add dispatch as the floor reference.

Usage: python scripts/probe_r5_dispatch_floor.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from spfft_tpu.ops import dft

N = 256
M = 256 * 256


def bench(g, args, inner=5, reps=12):
    out = g(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = g(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def main():
    rng = np.random.default_rng(5)
    xr = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    xi = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    mats = dft.c2c_mats(N, dft.BACKWARD)

    t = bench(jax.jit(lambda a, b: (a + 1.0, b)), (xr, xi))
    print(f"trivial add dispatch : {t*1e3:7.3f} ms", flush=True)

    for k in (1, 2, 4, 8):
        def chain(a, b, k=k):
            for _ in range(k):
                a, b = dft.pdft_last(a, b, mats)
            return a, b
        t = bench(jax.jit(chain), (xr, xi))
        print(f"{k} chained stages    : {t*1e3:7.3f} ms "
              f"({t*1e3/k:6.3f} ms/stage)", flush=True)


if __name__ == "__main__":
    main()
