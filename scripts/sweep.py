#!/usr/bin/env python
"""Performance sweep on the current device: dims x path x kernel.

Produces the table recorded in BENCHMARKS.md. Uses the sync-cancelling
difference estimator (see bench.py), which cancels the per-sync readback
cost: each number is min over 3 trials of
(T(g2) - T(g1)) / (g2 - g1) with one hard sync per group.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

import spfft_tpu as sp
from spfft_tpu.utils import as_interleaved
from spfft_tpu.utils.workloads import (even_plane_split,
                                       round_robin_stick_partition,
                                       spherical_cutoff_triplets)

REPS = int(os.environ.get("SWEEP_REPS", "20"))
DIMS = [int(d) for d in os.environ.get("SWEEP_DIMS", "64,128,256").split(",")]

probe = jax.jit(lambda x: x.reshape(-1)[:8].sum())


def timeit(fn):
    """Shared sync-cancelling estimator (spfft_tpu.utils.benchtime) —
    identical methodology to bench.py so BENCHMARKS.md numbers from
    different scripts are comparable."""
    from spfft_tpu.utils.benchtime import diff_estimate_seconds

    float(np.asarray(probe(fn())))  # warm-up + compile

    def timed(g):
        t0 = time.perf_counter()
        for _ in range(g):
            out = fn()
        float(np.asarray(probe(out)))
        return time.perf_counter() - t0

    sec, _, fallback = diff_estimate_seconds(timed, reps=REPS, trials=3)
    if fallback:
        print("  (diff estimator below noise — pipelined median reported)",
              flush=True)
    return sec


def main():
    rows = []
    for n in DIMS:
        trip = spherical_cutoff_triplets(n)
        rng = np.random.default_rng(0)
        v = (rng.uniform(-1, 1, len(trip))
             + 1j * rng.uniform(-1, 1, len(trip))).astype(np.complex64)
        vil = jax.device_put(np.asarray(as_interleaved(v, "single")))
        for path in ("local", "dist1"):
            for pallas in (True, False):
                if path == "local":
                    plan = sp.make_local_plan(
                        sp.TransformType.C2C, n, n, n, trip,
                        precision="single", use_pallas=bool(pallas))
                    if pallas and not plan._pallas_active:
                        continue
                    fn = (lambda p=plan: p.apply_pointwise(
                        vil, scaling=sp.Scaling.FULL))
                else:
                    parts = round_robin_stick_partition(trip, (n, n, n), 1)
                    plan = sp.make_distributed_plan(
                        sp.TransformType.C2C, n, n, n, parts,
                        even_plane_split(n, 1), mesh=sp.make_mesh(1),
                        precision="single",
                        use_pallas=True if pallas else False)
                    if pallas and (plan._pallas_dist is None
                                   or plan._pallas_interpret):
                        continue  # no compiled kernel on this backend
                    vdev = plan.shard_values([v])
                    fn = (lambda p=plan, w=vdev: p.apply_pointwise(
                        w, scaling=sp.Scaling.FULL))
                ms = timeit(fn) * 1e3
                rows.append({"dim": n, "path": path, "pallas": pallas,
                             "pair_ms": round(ms, 2)})
                print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"device": str(jax.devices()[0]), "reps": REPS,
                      "rows": rows}))


if __name__ == "__main__":
    main()
