#!/usr/bin/env python
"""Round-4 probe: where does the 256^3 fused pair spend its 12 ms?

Three questions, all on the real device with scanned-iteration timing
(see scripts/profile_stages.py for why single dispatches can't resolve
per-stage times):

1. Reproduce the round-3 fused pair (interleaved (N, 2) boundary).
2. Time the same pair with a PLANAR (rows, 128) value boundary — the
   interleaved<->planar conversion passes around the gather kernels
   removed (VERDICT round-3 item 1).
3. Bisect the pipeline with incremental prefix compositions to locate
   the gap between the stage sum (~7.9 ms) and the fused pair (12 ms).

Usage: DIM=256 python scripts/probe_r4_layout.py
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


def _scan_seconds(body, x, reps=3):
    def run(x0):
        def step(c, _):
            xp = _perturb(c)
            return xp, _consume(body(xp))
        _, ys = jax.lax.scan(step, x0, None, length=R)
        return ys
    f = jax.jit(run)
    out = f(x)
    sync(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(x)
    sync(out)
    return (time.perf_counter() - t0) / reps


def timeit(name, body, x, calib_s):
    total = _scan_seconds(body, x)
    dt = (total - calib_s) / R
    print(f"{name:44s} {dt*1e3:8.3f} ms", flush=True)
    return dt


def main(n: int):
    triplets = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single")
    p = plan.index_plan
    N, S, Z = p.num_values, p.num_sticks, p.dim_z
    assert plan._pallas_active
    dec_t = plan._pallas["dec"]
    cmp_t = plan._pallas["cmp"]
    tables = plan._tables
    print(f"== dim={n} values={N} sticks={S} dec_segs={len(dec_t.segs)} "
          f"cmp_segs={len(cmp_t.segs)} dec_rows={dec_t.src_rows} "
          f"cmp_rows={cmp_t.src_rows} R={R} ==", flush=True)

    rng = np.random.default_rng(0)
    values = (rng.uniform(-1, 1, N)
              + 1j * rng.uniform(-1, 1, N)).astype(np.complex64)
    values_il = jax.device_put(plan._coerce_values(values))

    Rv = dec_t.src_rows  # planar value rows (dec source)
    re0 = jnp.asarray(np.pad(values.real.astype(np.float32),
                             (0, Rv * 128 - N)).reshape(Rv, 128))
    im0 = jnp.asarray(np.pad(values.imag.astype(np.float32),
                             (0, Rv * 128 - N)).reshape(Rv, 128))

    cal_il = _scan_seconds(lambda v: v, values_il)
    cal_pl = _scan_seconds(lambda v: v, (re0, im0))
    print(f"calib interleaved {cal_il/R*1e3:.3f} ms/step, "
          f"planar {cal_pl/R*1e3:.3f} ms/step", flush=True)

    # 1. round-3 pair, interleaved boundary
    timeit("pair interleaved (round-3)",
           lambda v: plan._forward_impl(plan._backward_impl(v, tables),
                                        tables, scaled=False),
           values_il, cal_il)

    # 2. planar-boundary pair
    def dec_planar(re, im):
        out_re, out_im = gk.run_gather(re, im, tables["dec_tabs"], dec_t)
        flat = (out_re.reshape(-1)[:dec_t.num_out]
                + 1j * out_im.reshape(-1)[:dec_t.num_out])
        return flat.reshape(S, Z)

    def cmp_planar(sticks):
        re, im = gk.planar_from_complex(sticks, cmp_t.src_rows)
        out_re, out_im = gk.run_gather(re, im, tables["cmp_tabs"], cmp_t)
        rows = out_re.shape[0] * 8
        re_f = out_re.reshape(rows, 128)
        im_f = out_im.reshape(rows, 128)
        if rows < Rv:
            re_f = jnp.pad(re_f, ((0, Rv - rows), (0, 0)))
            im_f = jnp.pad(im_f, ((0, Rv - rows), (0, 0)))
        else:
            re_f, im_f = re_f[:Rv], im_f[:Rv]
        return re_f, im_f

    def pair_planar(c):
        re, im = c
        sticks = dec_planar(re, im)
        space = plan._backward_rest(sticks, tables)
        sticks2 = plan._forward_head(space, tables)
        return cmp_planar(sticks2)

    timeit("pair planar boundary", pair_planar, (re0, im0), cal_pl)

    # 3. conversion passes in isolation
    timeit("conv: interleaved->planar (dec input)",
           lambda v: gk.planar_from_interleaved(v, dec_t.src_rows),
           values_il, cal_il)

    def conv_out(c):
        re, im = c
        return gk.interleaved_from_planar(re, im, N)
    timeit("conv: planar->interleaved (cmp output)", conv_out,
           (re0, im0), cal_pl)

    # 4. incremental prefix compositions, planar boundary
    def pfx1(c):
        return gk.run_gather(c[0], c[1], tables["dec_tabs"], dec_t)

    def pfx2(c):
        return dec_planar(*c)

    def pfx3(c):
        return stages.z_backward(dec_planar(*c))

    def pfx4(c):
        s = stages.z_backward(dec_planar(*c))
        return stages.sticks_to_grid(s, tables["col_inv"], p.dim_y,
                                     p.dim_x_freq)

    def pfx5(c):
        s = stages.z_backward(dec_planar(*c))
        g = stages.sticks_to_grid(s, tables["col_inv"], p.dim_y,
                                  p.dim_x_freq)
        return stages.xy_backward_c2c(g)

    def pfx6(c):
        s = stages.z_backward(dec_planar(*c))
        g = stages.sticks_to_grid(s, tables["col_inv"], p.dim_y,
                                  p.dim_x_freq)
        return stages.xy_forward_c2c(stages.xy_backward_c2c(g))

    def pfx7(c):
        s = stages.z_backward(dec_planar(*c))
        g = stages.sticks_to_grid(s, tables["col_inv"], p.dim_y,
                                  p.dim_x_freq)
        g = stages.xy_forward_c2c(stages.xy_backward_c2c(g))
        return stages.grid_to_sticks(g, tables["scatter_cols"])

    def pfx8(c):
        s = stages.z_backward(dec_planar(*c))
        g = stages.sticks_to_grid(s, tables["col_inv"], p.dim_y,
                                  p.dim_x_freq)
        g = stages.xy_forward_c2c(stages.xy_backward_c2c(g))
        return stages.z_forward(stages.grid_to_sticks(
            g, tables["scatter_cols"]))

    prev = 0.0
    for name, fn in [("dec kernel only", pfx1),
                     ("+ complex sticks", pfx2),
                     ("+ z ifft", pfx3),
                     ("+ unpack", pfx4),
                     ("+ xy ifft2", pfx5),
                     ("+ xy fft2", pfx6),
                     ("+ pack", pfx7),
                     ("+ z fft", pfx8),
                     ("+ compress (full planar pair)", pair_planar)]:
        dt = timeit(f"prefix {name}", fn, (re0, im0), cal_pl)
        print(f"{'':46s} delta {max(dt-prev, 0)*1e3:8.3f} ms", flush=True)
        prev = dt


if __name__ == "__main__":
    print(f"devices: {jax.devices()}", flush=True)
    main(int(os.environ.get("DIM", "256")))
