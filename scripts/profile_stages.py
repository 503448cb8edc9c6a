#!/usr/bin/env python
"""Per-stage wall-clock + achieved-GB/s breakdown of the north-star pipeline
on the real device, against a measured device-copy floor.

Per-call dispatch cost swamps per-stage device time at small sizes — so
each stage is timed as ONE
executable running R scanned iterations (carry = the stage input, perturbed
by a cheap elementwise pass each step so XLA cannot hoist the loop-invariant
stage out of the scan). The perturbation pass is measured by a calibration
scan and subtracted. Hard-synced via host readback.

GB/s is *effective*: the stage's logical bytes (elements read + written
once, c64=8B) over device time — FFT stages do more internal passes, so
their effective number understates hardware traffic; the copy-floor row
calibrates what "bandwidth-bound" means on this chip.

Usage: DIM=256 python scripts/profile_stages.py   (or DIMS="64 128 256")
"""
import sys
import os
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax
import jax.numpy as jnp

from spfft_tpu import TransformType, make_local_plan
from spfft_tpu.ops import stages
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

C64 = 8  # bytes
R = int(os.environ.get("REPS", 20))


def sync(out):
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(np.asarray(jax.numpy.real(leaf).ravel()[0]))


def _perturb(x):
    return x * x.dtype.type(1.0 + 1e-7)


def _consume(y):
    """Reduce the WHOLE output to a scalar: consuming a single element
    would let XLA dead-code-eliminate most of a gather stage (and parts
    of FFTs), faking near-zero stage times."""
    leaf = jax.tree_util.tree_leaves(y)[0]
    if jnp.iscomplexobj(leaf):
        return jnp.mean(jnp.real(leaf)) + jnp.mean(jnp.imag(leaf))
    return jnp.mean(leaf)


def _scan_seconds(body, x, reps=3):
    """Wall-clock of ONE dispatch of R scanned body(x) steps (the carry is
    perturbed each step so nothing hoists; the full output is reduced so
    nothing DCEs)."""
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


def scan_time(name, body, x, nbytes, calib_s):
    """Per-step stage seconds: scanned time minus the calibration scan
    (perturbation pass + consume reduction + scan overhead), divided by R.
    Stages cheaper than ~15% of the calibration scan are below the
    subtraction noise floor and reported as such."""
    total = _scan_seconds(body, x)
    dt = (total - calib_s) / R
    noise = 0.15 * calib_s / R
    if dt < noise:
        print(f"{name:24s} {'<'+format(noise*1e3, '.3f'):>9s} ms   "
              f"(below noise floor; {nbytes/1e6:8.1f} MB logical)",
              flush=True)
        return max(dt, 0.0)
    gbs = nbytes / dt / 1e9 if nbytes else 0.0
    print(f"{name:24s} {dt*1e3:8.3f} ms   {gbs:7.1f} GB/s "
          f"({nbytes/1e6:8.1f} MB logical)", flush=True)
    return dt


def calibration(x):
    """The scan with an identity body: measures the perturbation pass, the
    consume reduction and scan overhead."""
    return _scan_seconds(lambda xp: xp, x)


def copy_floor(n_elems_c64: int):
    """Device copy floor from the calibration scan itself: each step reads
    the carry, writes the perturbed carry, and reads it again for the mean
    (XLA fuses any extra elementwise multiply into the same pass, so a
    separate 'body' would measure nothing) — three array traversals of
    n elements per step."""
    x = jnp.ones((n_elems_c64, 2), jnp.float32)
    dt = calibration(x) / R
    return 3 * n_elems_c64 * C64 / dt / 1e9, dt


def profile(n: int):
    triplets = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single")
    p = plan.index_plan
    N, S, Z = p.num_values, p.num_sticks, p.dim_z
    SZ, G = S * Z, n * n * n
    print(f"\n== dim={n} values={N} sticks={S} "
          f"pallas={plan._pallas_active} (R={R} scanned steps/stage) ==",
          flush=True)
    floor_gbs, _ = copy_floor(G)
    print(f"{'copy floor (n^3 c64)':24s} {'':8s}      {floor_gbs:7.1f} GB/s",
          flush=True)

    rng = np.random.default_rng(0)
    values = (rng.uniform(-1, 1, N)
              + 1j * rng.uniform(-1, 1, N)).astype(np.complex64)
    # the plan's own coercion produces the correct boundary layout
    # (interleaved rows, or planar pair for >=16M-value plans)
    values_il = jax.device_put(plan._coerce_values(values))
    tables = plan._tables

    total_bytes = 0
    total_time = 0.0

    def stage(name, body, arg, nbytes, calib_s):
        nonlocal total_bytes, total_time
        dt = scan_time(name, body, arg, nbytes, calib_s)
        total_bytes += nbytes
        total_time += dt

    # calibration per carry shape (the perturbation pass scales with it)
    cal_values = calibration(values_il)
    sticks0 = jax.jit(lambda v: plan._decompress(v, tables))(values_il)
    cal_sticks = calibration(sticks0)
    grid0 = jax.jit(lambda s: stages.sticks_to_grid(
        s, tables["col_inv"], p.dim_y, p.dim_x_freq))(sticks0)
    cal_grid = calibration(grid0)

    stage("decompress", lambda v: plan._decompress(v, tables), values_il,
          (N + SZ) * C64, cal_values)
    stage("z_backward (ifft)", stages.z_backward, sticks0,
          2 * SZ * C64, cal_sticks)
    stage("sticks_to_grid", lambda s: stages.sticks_to_grid(
        s, tables["col_inv"], p.dim_y, p.dim_x_freq), sticks0,
        (SZ + G) * C64, cal_sticks)
    stage("xy_backward (ifft2)", stages.xy_backward_c2c, grid0,
          2 * G * C64, cal_grid)
    stage("xy_forward (fft2)", stages.xy_forward_c2c, grid0,
          2 * G * C64, cal_grid)
    stage("grid_to_sticks", lambda g: stages.grid_to_sticks(
        g, tables["scatter_cols"]), grid0, (G + SZ) * C64, cal_grid)
    stage("z_forward (fft)", stages.z_forward, sticks0,
          2 * SZ * C64, cal_sticks)
    stage("compress", lambda s: plan._compress(s, tables, None), sticks0,
          (SZ + N) * C64, cal_sticks)

    if total_time > 0:
        print(f"{'sum of stages':24s} {total_time*1e3:8.2f} ms   "
              f"{total_bytes/total_time/1e9:7.1f} GB/s", flush=True)
    else:
        print(f"{'sum of stages':24s} below noise floor at this size",
              flush=True)

    # the fused pair, scanned through iterate-style composition
    pair_t = scan_time(
        "FULL fused pair",
        lambda v: plan._forward_impl(plan._backward_impl(v, tables), tables,
                                     scaled=False),
        values_il, total_bytes, cal_values)
    print(f"{'vs stage sum':24s} {(total_time-pair_t)*1e3:8.2f} ms "
          f"({(1 - pair_t/max(total_time,1e-12))*100:.0f}% saved by fusion)",
          flush=True)


if __name__ == "__main__":
    dims = os.environ.get("DIMS") or os.environ.get("DIM", "256")
    print(f"devices: {jax.devices()}", flush=True)
    for d in dims.split():
        profile(int(d))
