#!/usr/bin/env python
"""Canonical verify drive (see .claude/skills/verify/SKILL.md).

Runs on whatever platform jax selects (the TPU where one is attached;
set JAX_PLATFORMS=cpu to force CPU)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import jax

import spfft_tpu as sp
from spfft_tpu.utils import as_complex_np
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

print("platform:", jax.default_backend(), jax.devices())

# 1. dense 2x2x2 C2C round trip (reference example.cpp equivalent)
n = 2
triplets = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)])
plan = sp.make_local_plan(sp.TransformType.C2C, n, n, n, triplets,
                          precision="single")
rng = np.random.default_rng(0)
v = (rng.uniform(-1, 1, len(triplets))
     + 1j * rng.uniform(-1, 1, len(triplets))).astype(np.complex64)
space = plan.backward(v)
freq = as_complex_np(np.asarray(plan.forward(space)))
assert np.allclose(freq, v * n**3, atol=1e-4), "dense round trip failed"
print("1. dense 2^3 round trip: OK")

# 2. R2C vs numpy oracle: random real field, fftn coefficients at the
# non-redundant hermitian triplets; unnormalised backward returns field * N.
n = 8
herm = [(x, y, z) for x in range(n // 2 + 1) for y in range(n)
        for z in range(n)]
herm = np.asarray(herm)
field = rng.uniform(-1, 1, (n, n, n))
cube = np.fft.fftn(field)  # cube[z, y, x] with positive storage indexing
vals = np.array([cube[t[2], t[1], t[0]] for t in herm], np.complex64)
rplan = sp.make_local_plan(sp.TransformType.R2C, n, n, n, herm,
                           precision="single")
got = np.asarray(rplan.backward(vals))
err = np.abs(got - field * n**3).max()
assert err < 1e-2, f"r2c backward mismatch {err}"
print("2. R2C vs numpy oracle: OK")

# 3. error surface
try:
    sp.make_local_plan(sp.TransformType.C2C, 4, 4, 4, np.array([[9, 0, 0]]))
    raise SystemExit("expected InvalidIndicesError")
except sp.InvalidIndicesError:
    pass
try:
    plan.backward(v[:3])
    raise SystemExit("expected InvalidParameterError")
except sp.InvalidParameterError:
    pass
print("3. error surface: OK")

# 4. scale probe: 128^3 spherical cutoff
n = 128
t0 = time.perf_counter()
trip = spherical_cutoff_triplets(n)
plan = sp.make_local_plan(sp.TransformType.C2C, n, n, n, trip,
                          precision="single")
plan_s = time.perf_counter() - t0
vals = (rng.uniform(-1, 1, len(trip))
        + 1j * rng.uniform(-1, 1, len(trip))).astype(np.complex64)
jax.block_until_ready(plan.forward(plan.backward(vals), sp.Scaling.FULL))
t0 = time.perf_counter()
reps = 5
for _ in range(reps):
    out = plan.forward(plan.backward(vals), sp.Scaling.FULL)
jax.block_until_ready(out)
per = (time.perf_counter() - t0) / reps
got = as_complex_np(np.asarray(out))
err = np.abs(got - vals).max()
assert err < 1e-4, f"128^3 roundtrip err {err}"
print(f"4. 128^3 probe: OK — plan {plan_s:.2f}s, pair {per*1e3:.1f} ms/iter, "
      f"pallas={plan._pallas_active}, err={err:.2e}")

# 5. batched (vmapped) execution: drive the fused executable DIRECTLY
# (multi_transform_* may legitimately route shared-plan batches to the
# per-transform path when the Pallas kernel is active, so calling it would
# not cover the vmap lowering on TPU), then the multi_transform wrapper.
from spfft_tpu.grid import Transform
from spfft_tpu import multi_transform_backward, multi_transform_forward

vals_b = [(rng.uniform(-1, 1, len(trip))
           + 1j * rng.uniform(-1, 1, len(trip))).astype(np.complex64)
          for _ in range(3)]
t0 = time.perf_counter()
stacked = plan.backward_batched(vals_b)
jax.block_until_ready(stacked)
per_b = (time.perf_counter() - t0) / 3
ref1 = np.asarray(plan.backward(vals_b[1]))
err = np.abs(np.asarray(stacked[1]) - ref1).max()
assert err < 1e-4, f"batched backward mismatch {err}"
fw = plan.forward_batched(list(np.asarray(stacked)), sp.Scaling.FULL)
gotf = as_complex_np(np.asarray(fw[2]))
err = np.abs(gotf - vals_b[2]).max()
assert err < 1e-4, f"batched roundtrip mismatch {err}"
base = Transform(plan)
clones = [base.clone() for _ in range(3)]
outs = multi_transform_backward(clones, vals_b)
err = np.abs(np.asarray(outs[1]) - ref1).max()
assert err < 1e-4, f"multi_transform backward mismatch {err}"
fouts = multi_transform_forward(clones, [np.asarray(o) for o in outs],
                                [sp.Scaling.FULL] * 3)
err = np.abs(as_complex_np(np.asarray(fouts[2])) - vals_b[2]).max()
assert err < 1e-4, f"multi_transform roundtrip mismatch {err}"
print(f"5. batched vmapped executable (B=3, incl. compile "
      f"{per_b*1e3:.1f} ms/transform) + multi_transform wrapper: OK")

# 6. distributed shard_map path on the real chip: a 1-device mesh compiles
# and runs the same SPMD program (collectives included) as a pod slice.
from spfft_tpu.utils.workloads import (even_plane_split,
                                       round_robin_stick_partition)
n6 = 32
trip6 = spherical_cutoff_triplets(n6)
parts6 = round_robin_stick_partition(trip6, (n6, n6, n6), 1)
planes6 = even_plane_split(n6, 1)
dplan = sp.make_distributed_plan(sp.TransformType.C2C, n6, n6, n6, parts6,
                                 planes6, mesh=sp.make_mesh(1),
                                 precision="single")
vals6 = [(rng.uniform(-1, 1, len(p))
          + 1j * rng.uniform(-1, 1, len(p))).astype(np.complex64)
         for p in parts6]
out6 = dplan.apply_pointwise(vals6, scaling=sp.Scaling.FULL)
err = max(np.abs(g - v).max()
          for g, v in zip(dplan.unshard_values(out6), vals6))
assert err < 1e-3, f"distributed-on-TPU roundtrip err {err}"
print(f"6. distributed shard_map path on TPU (1-device mesh): OK "
      f"err={err:.2e}")

# 7. split xy path + Pallas kernel together on the compiled path: a
# narrow-x cutoff set above the Pallas auto threshold must match the
# dense-path result.
from spfft_tpu.benchmark import cutoff_stick_triplets
trip7 = cutoff_stick_triplets(128, 128, 128, 0.25, False)
plan7 = sp.make_local_plan(sp.TransformType.C2C, 128, 128, 128, trip7,
                           precision="single")
assert plan7._split_x is not None and plan7._pallas_active, \
    (plan7._split_x, plan7._pallas_active)
plan7d = sp.make_local_plan(sp.TransformType.C2C, 128, 128, 128, trip7,
                            precision="single")
plan7d._split_x = None
plan7d._pair_jits = {}
v7 = (rng.uniform(-1, 1, len(trip7))
      + 1j * rng.uniform(-1, 1, len(trip7))).astype(np.complex64)
a7 = np.asarray(plan7.apply_pointwise(v7, scaling=sp.Scaling.FULL))
b7 = np.asarray(plan7d.apply_pointwise(v7, scaling=sp.Scaling.FULL))
err = np.abs(a7 - b7).max()
assert err < 1e-4, f"split-vs-dense mismatch {err}"
print(f"7. split xy + Pallas on TPU (x width {plan7._split_x[1]}/128): OK "
      f"max diff vs dense {err:.2e}")
print("VERIFY DRIVE: ALL OK")
