#!/usr/bin/env python
"""Wide vs narrow windowed gather at one distributed shard's size, on the chip.

A shard of full z-sticks in stick-major order gathers through the identity
map in both compression directions (slot j <- value j, value j <- slot j),
so one identity gather of ``sticks * dim_z`` values stands for either. The
default is one shard of the 768^3 C2C cell: 73,728 sticks of 768 (SpFFT's
benchmark at sparsity 0.5 over 4 ranks). Builds both table kinds, checks
each output against its input bit for bit, and times ``REPS`` gathers
dispatched back to back and waited for once.

    python scripts/probe_shard_gather.py [sticks] [dim_z]

Prints one JSON line: table build seconds, chunks (grid steps), launches,
table bytes and milliseconds a gather for each kind.
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from spfft_tpu.ops import gather_kernel as gk  # noqa: E402

REPS = int(os.environ.get("REPS", 20))


def main(argv):
    sticks = int(argv[1]) if len(argv) > 1 else 73_728
    dim_z = int(argv[2]) if len(argv) > 2 else 768
    n = sticks * dim_z
    idx = np.arange(n, dtype=np.int64)
    valid = np.ones(n, bool)
    src = np.random.default_rng(1).standard_normal((2, n), np.float32)
    out = {"device": jax.devices()[0].device_kind, "values": n}
    for kind, build in (("wide", gk.build_wide_gather_tables),
                        ("narrow", gk.build_monotone_gather_tables)):
        t0 = time.perf_counter()
        t = build(idx, valid, n)
        build_s = time.perf_counter() - t0
        dev = gk.gather_device_tables(t)
        re, im = (jnp.pad(jnp.asarray(a), (0, t.src_rows * gk.TILE_LANE - n))
                  .reshape(t.src_rows, gk.TILE_LANE) for a in src)
        run = jax.jit(lambda a, b, d, t=t: gk.run_gather(
            a, b, d, t, interpret=jax.default_backend() != "tpu"))
        o_re, o_im = jax.block_until_ready(run(re, im, dev))
        exact = bool(np.array_equal(np.asarray(o_re).reshape(-1)[:n], src[0])
                     and np.array_equal(np.asarray(o_im).reshape(-1)[:n],
                                        src[1]))
        del o_re, o_im
        t0 = time.perf_counter()
        for _ in range(REPS):  # one stream: the last done, all done
            o = run(re, im, dev)
        jax.block_until_ready(o)
        ms = (time.perf_counter() - t0) / REPS * 1e3
        del o
        out[kind] = {
            "build_s": build_s, "chunks": int(t.row0.shape[0]),
            "launches": max(1, len(t.segs)), "exact": exact,
            "table_bytes": int(sum(a.nbytes
                                   for a in jax.tree_util.tree_leaves(dev))),
            "ms_per_gather": ms}
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv)
