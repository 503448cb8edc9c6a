#!/usr/bin/env python
"""Round-5 probe: time structure of the device's fast/slow modes.

Round-4 treated the ~1.3x bimodality as fixed per process; round-5
trials saw 8.6-9.5 ms mins INSIDE otherwise-12.5 ms sessions. This
prints every group's per-pair time over a long run to show dwell times
and transition structure, deciding how bench.py should catch the fast
mode (VERDICT r4 task 6).

Usage: DIM=256 GROUPS=40 python scripts/probe_r5_mode.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

from spfft_tpu import TransformType, make_local_plan
from spfft_tpu.utils.workloads import spherical_cutoff_triplets


def sync(a):
    return float(np.asarray(jax.numpy.real(a).ravel()[0]))


def main():
    n = int(os.environ.get("DIM", "256"))
    groups = int(os.environ.get("GROUPS", "40"))
    g = int(os.environ.get("G", "10"))
    print(f"devices: {jax.devices()}", flush=True)
    triplets = spherical_cutoff_triplets(n)
    rng = np.random.default_rng(42)
    N = len(triplets)
    values = (rng.uniform(-1, 1, N)
              + 1j * rng.uniform(-1, 1, N)).astype(np.complex64)
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single")
    vil = jax.device_put(plan._coerce_values(values))
    sync(plan.apply_pointwise(vil))

    # Per-group pipelined time, g pairs + 1 sync each. The sync constant
    # (the hard-sync readback) inflates all groups equally, so MODE
    # CONTRAST survives even though absolute values are biased by
    # sync/g. Also prints the rolling diff-pair estimate (g2-g1 pairs
    # of adjacent groups are the same, so adjacent-group differences
    # don't apply; use contrast only).
    ts = []
    for i in range(groups):
        t0 = time.perf_counter()
        o = None
        for _ in range(g):
            o = plan.apply_pointwise(vil)
        sync(o)
        dt = (time.perf_counter() - t0) / g
        ts.append(dt)
        print(f"group {i:3d}: {dt*1e3:7.3f} ms/pair (incl sync/g)",
              flush=True)
    arr = np.asarray(ts) * 1e3
    print(f"min {arr.min():.3f} med {np.median(arr):.3f} "
          f"max {arr.max():.3f}", flush=True)


if __name__ == "__main__":
    main()
