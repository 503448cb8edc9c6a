#!/usr/bin/env python
"""Two-process `jax.distributed` smoke of the multihost plan build — the
real-wire analogue of the stub-world tests in tests/test_multihost.py (the
reference's equivalent is running its MPI tests under real ranks,
reference: tests/run_mpi_tests.cpp:14-20).

Parent mode (no args): spawns two worker processes on a localhost
coordinator and reports their combined verdict. Worker mode
(``--worker <pid>``): initialises the process group, builds the
distributed plan collectively (fingerprint allgather cross-check), runs
one backward+forward on this process's mesh slice, and prints
``worker <pid>: ok``.

Usage:  python scripts/multihost_smoke.py
Exit 0 = both workers completed the collective plan build and a transform.
Any failure prints the worker logs (this is a smoke harness, not a test —
the container may not support multi-process XLA groups; ROADMAP.md records
the observed result).
"""
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PORT = int(os.environ.get("SPFFT_SMOKE_PORT", "12355"))
NPROC = 2


def worker(pid: int) -> None:
    # Each worker must be CPU-intent BEFORE jax loads a backend; the
    # spawned interpreter inherits env from the parent below.
    from spfft_tpu.utils.platform import force_virtual_cpu_devices
    force_virtual_cpu_devices(1)

    import numpy as np
    import jax
    from spfft_tpu import (Scaling, TransformType, initialize_multihost,
                           make_mesh)
    from spfft_tpu.parallel.dist import DistributedTransformPlan
    from spfft_tpu.parallel.multihost import build_distributed_plan_multihost
    from spfft_tpu.utils.workloads import (even_plane_split,
                                           round_robin_stick_partition,
                                           spherical_cutoff_triplets)

    initialize_multihost(coordinator_address=f"127.0.0.1:{PORT}",
                         num_processes=NPROC, process_id=pid)
    assert jax.process_count() == NPROC, jax.process_count()
    n_dev = len(jax.devices())
    print(f"worker {pid}: process group up, {n_dev} global devices",
          flush=True)

    n = 8
    triplets = spherical_cutoff_triplets(n)
    parts = round_robin_stick_partition(triplets, (n, n, n), n_dev)
    planes = even_plane_split(n, n_dev)
    # Collective build: each process contributes ITS shards only (one
    # device per process here); the builder allgathers the stick lists and
    # validates the blake2b fingerprint across processes (the reference's
    # plan-time Allreduce mismatch check, grid_internal.cpp:148-167).
    local = slice(pid, pid + 1)
    dist = build_distributed_plan_multihost(
        TransformType.C2C, n, n, n, parts[local], planes[local])
    plan = DistributedTransformPlan(dist, mesh=make_mesh(n_dev),
                                    precision="single")
    rng = np.random.default_rng(0)
    values = [(rng.uniform(-1, 1, len(p))
               + 1j * rng.uniform(-1, 1, len(p))).astype(np.complex64)
              for p in parts]
    out = plan.forward(plan.backward(values), Scaling.FULL)
    out.block_until_ready()
    print(f"worker {pid}: ok", flush=True)


def main() -> int:
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    procs = []
    for pid in range(NPROC):
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             str(pid)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    deadline = time.time() + 300
    outs = [None] * NPROC
    for i, p in enumerate(procs):
        try:
            outs[i], _ = p.communicate(timeout=max(1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()
            outs[i], _ = p.communicate()
            outs[i] += "\n<timed out>"
    ok = all(p.returncode == 0 and f"worker {i}: ok" in (outs[i] or "")
             for i, p in enumerate(procs))
    for i, o in enumerate(outs):
        print(f"--- worker {i} (rc={procs[i].returncode}) ---")
        print(o)
    print("MULTIHOST SMOKE:", "OK" if ok else "FAILED")
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        worker(int(sys.argv[2]))
    else:
        sys.exit(main())
