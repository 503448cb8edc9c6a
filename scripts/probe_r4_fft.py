#!/usr/bin/env python
"""Round-4 probe: why do the xy FFTs cost 2.4+1.9 ms in the fused pair but
1.1+0.85 isolated? Tries optimization-barrier placements and FFT
decompositions on the 256^3 pair. Uses min-of-reps (a measurement can
stall for seconds; see the 417 ms artifact in probe_r4_layout).

Usage: DIM=256 python scripts/probe_r4_fft.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from spfft_tpu import TransformType, make_local_plan
from spfft_tpu.ops import stages
from spfft_tpu.ops import gather_kernel as gk
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

R = int(os.environ.get("REPS", 20))
BAR = jax.lax.optimization_barrier


def sync(out):
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(np.asarray(jax.numpy.real(leaf).ravel()[0]))


def _perturb(x):
    return jax.tree_util.tree_map(lambda v: v * v.dtype.type(1.0 + 1e-7), x)


def _consume(y):
    leaves = jax.tree_util.tree_leaves(y)
    tot = 0.0
    for leaf in leaves:
        if jnp.iscomplexobj(leaf):
            tot = tot + jnp.mean(jnp.real(leaf)) + jnp.mean(jnp.imag(leaf))
        else:
            tot = tot + jnp.mean(leaf)
    return tot


def _scan_seconds(body, x, reps=4):
    def run(x0):
        def step(c, _):
            xp = _perturb(c)
            return xp, _consume(body(xp))
        _, ys = jax.lax.scan(step, x0, None, length=R)
        return ys
    f = jax.jit(run)
    out = f(x)
    sync(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = f(x)
        sync(out)
        best = min(best, time.perf_counter() - t0)
    return best


def timeit(name, body, x, calib_s):
    total = _scan_seconds(body, x)
    dt = (total - calib_s) / R
    print(f"{name:52s} {dt*1e3:8.3f} ms", flush=True)
    return dt


def main(n: int):
    triplets = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single")
    p = plan.index_plan
    N, S, Z = p.num_values, p.num_sticks, p.dim_z
    dec_t = plan._pallas["dec"]
    cmp_t = plan._pallas["cmp"]
    tables = plan._tables
    print(f"== dim={n} values={N} sticks={S} R={R} min-of-reps ==",
          flush=True)

    rng = np.random.default_rng(0)
    values = (rng.uniform(-1, 1, N)
              + 1j * rng.uniform(-1, 1, N)).astype(np.complex64)
    values_il = jax.device_put(plan._coerce_values(values))
    cal_il = _scan_seconds(lambda v: v, values_il)
    print(f"calib {cal_il/R*1e3:.3f} ms/step", flush=True)

    def dec(v):
        return plan._decompress(v, tables)

    def cmp_(s):
        return plan._compress(s, tables, None)

    def unpack(s):
        return stages.sticks_to_grid(s, tables["col_inv"], p.dim_y,
                                     p.dim_x_freq)

    def pack(g):
        return stages.grid_to_sticks(g, tables["scatter_cols"])

    scale = np.float32(n * n)

    def pair(v, *, bar_pre=False, bar_post=False, split1d=False,
             bar_unpack=False):
        s = stages.z_backward(dec(v))
        g = unpack(s)
        if bar_unpack:
            g = BAR(g)
        # xy backward
        if split1d:
            g = jnp.fft.ifft(BAR(g) if bar_pre else g, axis=-2)
            g = jnp.fft.ifft(BAR(g) if bar_pre else g, axis=-1) * scale
        else:
            g = jnp.fft.ifft2(BAR(g) if bar_pre else g,
                              axes=(-2, -1)) * scale
        if bar_post:
            g = BAR(g)
        # xy forward
        if split1d:
            g = jnp.fft.fft(BAR(g) if bar_pre else g, axis=-1)
            g = jnp.fft.fft(BAR(g) if bar_pre else g, axis=-2)
        else:
            g = jnp.fft.fft2(BAR(g) if bar_pre else g, axes=(-2, -1))
        if bar_post:
            g = BAR(g)
        return cmp_(stages.z_forward(pack(g)))

    import functools
    timeit("pair base (no barriers at 256^3)",
           functools.partial(pair), values_il, cal_il)
    timeit("pair bar before xy FFT operands",
           functools.partial(pair, bar_pre=True), values_il, cal_il)
    timeit("pair bar after unpack only",
           functools.partial(pair, bar_unpack=True), values_il, cal_il)
    timeit("pair bar pre+post xy FFTs",
           functools.partial(pair, bar_pre=True, bar_post=True),
           values_il, cal_il)
    timeit("pair xy as 1D ffts (no bar)",
           functools.partial(pair, split1d=True), values_il, cal_il)
    timeit("pair xy as 1D ffts + bar_pre",
           functools.partial(pair, split1d=True, bar_pre=True),
           values_il, cal_il)

    # isolated xy ffts on a materialised grid, for reference
    grid0 = jax.jit(lambda v: unpack(stages.z_backward(dec(v))))(values_il)
    cal_g = _scan_seconds(lambda g: g, grid0)
    timeit("isolated ifft2 (materialised operand)",
           lambda g: jnp.fft.ifft2(g, axes=(-2, -1)) * scale, grid0, cal_g)
    timeit("isolated ifft2+fft2 chain",
           lambda g: jnp.fft.fft2(jnp.fft.ifft2(g, axes=(-2, -1)) * scale,
                                  axes=(-2, -1)), grid0, cal_g)
    timeit("isolated ifft2+fft2 chain, bar between",
           lambda g: jnp.fft.fft2(BAR(jnp.fft.ifft2(g, axes=(-2, -1))
                                      * scale), axes=(-2, -1)),
           grid0, cal_g)


if __name__ == "__main__":
    print(f"devices: {jax.devices()}", flush=True)
    main(int(os.environ.get("DIM", "256")))
