"""Benchmark CLI: ``python -m spfft_tpu.benchmark``.

Rebuild of the reference benchmark program (reference:
tests/programs/benchmark.cpp) with the same knobs and output schema:

* workload: dense-within-cutoff stick set — all (x, y) sticks with
  ``x < dim_x_freq * sparsity``, full z sticks, split round-robin over
  shards when distributed (reference: benchmark.cpp:176-205);
* measurement: warm-up pass, then repeated backward+forward pairs
  (reference: benchmark.cpp:84-96), wall-clock with a hard device sync at
  the end of the timed loop;
* output: per-phase timing tree + JSON dump with ``timings`` and
  ``parameters`` sections (reference: benchmark.cpp:276-308).

Flags mirror reference benchmark.cpp:138-156: -d dims, -r repeats,
-s sparsity, -t c2c|r2c, -e exchange, -p host|device, -m num transforms,
-o json output; plus --shards to run distributed over a device mesh,
--precision for the float twin, and --fused/--no-fused to A/B the fused
compression+z-DFT Pallas path (docs/kernels.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def cutoff_stick_triplets(dim_x: int, dim_y: int, dim_z: int,
                          sparsity: float, hermitian: bool) -> np.ndarray:
    """Dense-within-cutoff stick set (reference: benchmark.cpp:176-205):
    every (x, y) stick with x below ``dim_x_freq * sparsity``, full z."""
    dim_x_freq = dim_x // 2 + 1 if hermitian else dim_x
    num_x = max(1, min(dim_x_freq, int(round(dim_x_freq * sparsity))))
    x = np.arange(num_x, dtype=np.int32)
    y = np.arange(dim_y, dtype=np.int32)
    z = np.arange(dim_z, dtype=np.int32)
    X, Y, Z = np.meshgrid(x, y, z, indexing="ij")
    return np.stack([X.ravel(), Y.ravel(), Z.ravel()], axis=1)


def _parse_args(argv):
    p = argparse.ArgumentParser(
        prog="python -m spfft_tpu.benchmark",
        description="spfft_tpu benchmark (reference: tests/programs/"
                    "benchmark.cpp)")
    p.add_argument("-d", "--dimensions", type=int, nargs="+", required=True,
                   metavar="N", help="grid dims: one value (cubic) or three")
    p.add_argument("-r", "--repeats", type=int, default=10)
    p.add_argument("-w", "--warmups", type=int, default=1)
    p.add_argument("-s", "--sparsity", type=float, default=1.0,
                   help="fraction of x range covered by sticks (default 1)")
    p.add_argument("-t", "--transform", choices=["c2c", "r2c"],
                   default="c2c")
    p.add_argument("-e", "--exchange",
                   choices=["default", "buffered", "bufferedFloat",
                            "compact", "compactFloat", "unbuffered", "all"],
                   default="default",
                   help="'all' sweeps every exchange mechanism on one "
                        "workload and prints a comparison table with HLO "
                        "wire bytes (reference: benchmark.cpp:138-156)")
    p.add_argument("-p", "--proc", choices=["host", "device"],
                   default="device",
                   help="host: numpy I/O every repeat; device: arrays stay "
                        "resident (reference -p cpu|gpu|gpu-gpu)")
    p.add_argument("-m", "--num-transforms", type=int, default=1)
    p.add_argument("-o", "--output", default=None, metavar="FILE.json")
    p.add_argument("--fused-pair", action="store_true",
                   help="time backward+forward as ONE fused executable "
                        "(apply_pointwise identity; requires -m 1)")
    p.add_argument("--fused", dest="fused", action="store_true",
                   default=None,
                   help="force the fused compression+z-DFT Pallas "
                        "kernels on (ops/fused_kernel.py; implies "
                        "use_pallas=True). Off-TPU this also forces the "
                        "matmul-DFT pipeline and interpret-mode kernel "
                        "execution, so CPU A/B numbers vs --no-fused "
                        "are honest overhead-only (docs/kernels.md)")
    p.add_argument("--no-fused", dest="fused", action="store_false",
                   help="disable the fused compression+z-DFT path (the "
                        "two-kernel pipeline; the A/B twin of --fused)")
    p.add_argument("--serve", action="store_true",
                   help="route the -m transforms through the serving "
                        "layer (spfft_tpu.serve: registry + batching "
                        "executor) instead of multi_transform_*; local "
                        "plans only (requires --shards 1)")
    p.add_argument("--shards", type=int, default=1,
                   help="distribute over an N-device mesh (default local)")
    p.add_argument("--overlap-chunks", type=int, default=None,
                   metavar="K",
                   help="split the distributed exchange into K "
                        "destination-balanced chunks so the z/xy FFT "
                        "stages pipeline with the collectives "
                        "(parallel/overlap.py; default 1 = monolithic, "
                        "or SPFFT_TPU_OVERLAP_CHUNKS)")
    p.add_argument("--cpu", action="store_true",
                   help="force a virtual CPU platform with --shards devices "
                        "(multi-chip simulation, like the test conftest)")
    p.add_argument("--precision", choices=["single", "double"],
                   default="single")
    p.add_argument("--store-dir", default=None, metavar="DIR",
                   help="cold/warm plan-resolution A/B through the "
                        "persistent plan-artifact store "
                        "(spfft_tpu.serve.store): resolve this "
                        "workload's plan through a store-backed "
                        "registry in-process (cold when DIR starts "
                        "empty: build + async spill), then re-resolve "
                        "it through a fresh registry in the same process "
                        "(warm: artifact load, zero builds; a failed warm "
                        "boot fails the run). Adds cold_start_ms/"
                        "warm_start_ms "
                        "to the JSON; use a fresh DIR per honest A/B "
                        "(docs/artifact_cache.md)")
    p.add_argument("--profile-dir", default=None, metavar="DIR",
                   help="capture a jax.profiler trace of the measured "
                        "window into DIR — the pipeline's "
                        "jax.named_scope phase names (decompress/z/"
                        "exchange/xy) become visible in the device "
                        "profile (open with TensorBoard/XProf)")
    args = p.parse_args(argv)
    if args.fused_pair and args.num_transforms != 1:
        p.error("--fused-pair requires -m 1")
    if args.serve and (args.shards > 1 or args.fused_pair):
        p.error("--serve requires --shards 1 and no --fused-pair")
    if args.store_dir and args.shards > 1:
        p.error("--store-dir measures local plan resolution "
                "(requires --shards 1)")
    return args


_EXCHANGE = {
    "default": "default", "buffered": "buffered",
    "bufferedFloat": "buffered_float", "compact": "compact_buffered",
    "compactFloat": "compact_buffered_float", "unbuffered": "unbuffered",
}


def _exchange_sweep(args, dims, ttype, triplets, rng, cdt) -> int:
    """-e all: one workload, every exchange mechanism (reference:
    benchmark.cpp:138-156 runs the benchmark once per exchange for
    'all'). Prints a comparison table — pair wall-clock plus the
    aggregate and busiest-link wire bytes of the LOWERED exchange HLO —
    and writes the same rows into the -o JSON."""
    import jax
    from .parallel import make_distributed_plan, make_mesh
    from .types import ExchangeType
    from .utils.workloads import (even_plane_split,
                                  round_robin_stick_partition)

    nx, ny, nz = dims
    parts = round_robin_stick_partition(triplets, dims, args.shards)
    planes = even_plane_split(nz, args.shards)
    values_np = [
        (rng.uniform(-1, 1, len(p)) + 1j * rng.uniform(-1, 1, len(p)))
        .astype(cdt) for p in parts]
    variants = ["buffered", "bufferedFloat", "compact", "compactFloat",
                "unbuffered"]
    rows = []
    for name in variants:
        plan = make_distributed_plan(
            ttype, nx, ny, nz, parts, planes, mesh=make_mesh(args.shards),
            precision=args.precision,
            exchange=ExchangeType(_EXCHANGE[name]),
            overlap_chunks=args.overlap_chunks)
        values = plan.shard_values(values_np)
        last = None
        for _ in range(max(args.warmups, 1)):
            last = plan.apply_pointwise(values)
        jax.block_until_ready(last)
        np.asarray(jax.tree_util.tree_leaves(last)[-1]).ravel()[:1]
        t0 = time.perf_counter()
        for _ in range(args.repeats):
            out = plan.apply_pointwise(values)
        jax.block_until_ready(out)
        np.asarray(jax.tree_util.tree_leaves(out)[-1]).ravel()[:1]
        pair_s = (time.perf_counter() - t0) / args.repeats
        # Hermitian trimming state, disclosed per row: an r2c plan's
        # exchange ships only the non-redundant stick set, so its wire
        # column is NOT comparable against a c2c (untrimmed) sweep of
        # the same sphere without this tag (docs/distributed.md).
        folded = sum(int(sp.value_conj.sum())
                     for sp in plan.dist_plan.shard_plans
                     if sp.value_conj is not None)
        rows.append({
            "exchange": name,
            "overlap_chunks": plan.overlap_chunks,
            "pair_seconds": round(pair_s, 6),
            "wire_total_bytes": int(plan.exchange_wire_bytes()),
            "busiest_link_bytes": int(plan.exchange_busiest_link_bytes()),
            "hermitian_trimmed": bool(plan.dist_plan.hermitian),
            "folded_mirror_values": folded,
        })
    hdr = (f"{'exchange':>14s} {'pair ms':>10s} {'wire total MB':>14s} "
           f"{'busiest link MB':>16s} {'stick set':>18s}")
    print(hdr)
    for r in rows:
        trim = ("r2c-trimmed" + (f"(+{r['folded_mirror_values']}f)"
                                 if r["folded_mirror_values"] else "")
                if r["hermitian_trimmed"] else "untrimmed")
        print(f"{r['exchange']:>14s} {r['pair_seconds'] * 1e3:10.3f} "
              f"{r['wire_total_bytes'] / 1e6:14.3f} "
              f"{r['busiest_link_bytes'] / 1e6:16.3f} {trim:>18s}")
    if args.output:
        payload = {
            "parameters": {
                "dim_x": nx, "dim_y": ny, "dim_z": nz,
                "shards": args.shards, "sparsity": args.sparsity,
                "transform_type": args.transform,
                "precision": args.precision, "repeats": args.repeats,
                "backend": jax.default_backend(),
                "num_values": int(len(triplets)),
            },
            "exchange_sweep": rows,
        }
        with open(args.output, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.output}")
    return 0


def _store_cold_warm(args, ttype, dims, triplets) -> dict:
    """The --store-dir A/B: resolve this workload's plan + first
    execution through a store-backed registry (a true COLD start when
    the store directory begins empty — build, spill), then the WARM boot
    from a fresh store handle and a fresh registry over the populated
    store (artifact load + first execution, builds == 0). Both halves
    run in this process: it holds the chip, so a child process could not
    reach it. The warm half shares this process's in-memory XLA caches,
    so its compile can be cheaper than a fresh process's. Returns the
    cold_start_ms/warm_start_ms pair scripts/bench_regress.py compares;
    a warm boot that builds, rejects or loads nothing raises."""
    from .serve.registry import PlanRegistry
    from .serve.store import PlanArtifactStore

    store = PlanArtifactStore(args.store_dir)
    reg = PlanRegistry(store=store)
    t0 = time.perf_counter()
    sig, plan = reg.get_or_build(ttype, *dims, triplets,
                                 precision=args.precision)
    n = plan.index_plan.num_values
    plan.backward(np.zeros((n, 2), np.float32)
                  if plan.precision == "single"
                  else np.zeros(n, np.complex128))
    cold_ms = (time.perf_counter() - t0) * 1e3
    store.drain()
    warm_store = PlanArtifactStore(args.store_dir)
    warm_reg = PlanRegistry(store=warm_store)
    t0 = time.perf_counter()
    loaded = warm_reg.warmup(warm_store.manifest()["entries"], compile=True,
                             strict=True)
    warm_ms = (time.perf_counter() - t0) * 1e3
    builds = warm_reg.stats()["builds"]
    if builds or warm_store.stats()["rejects"] or not loaded:
        raise RuntimeError(
            f"warm boot over {args.store_dir} failed: {len(loaded)} "
            f"loaded, {builds} built, store {warm_store.stats()}")
    return {
        "store_dir": args.store_dir,
        # a pre-populated DIR makes the cold number a warm one;
        # disclose rather than silently mislabel
        "store_was_cold": reg.stats()["builds"] == 1,
        "cold_start_ms": {"value": round(cold_ms, 3), "unit": "ms",
                          "metric": "plan resolve + first execute, "
                                    "empty store (build + spill)"},
        "warm_start_ms": {
            "value": round(warm_ms, 3), "unit": "ms",
            "metric": "plan resolve + first execute, fresh registry "
                      "over the populated store in the same process "
                      "(artifact load, builds==0)"},
        "warm_builds": builds,
        "warm_store": warm_store.stats(),
    }


def main(argv=None) -> int:
    args = _parse_args(argv if argv is not None else sys.argv[1:])
    if args.cpu:
        from .utils.platform import force_virtual_cpu_devices
        force_virtual_cpu_devices(max(args.shards, 1))
    restore = {}
    if args.fused is not None:
        import jax

        def _setenv(key, value):
            restore.setdefault(key, os.environ.get(key))
            os.environ[key] = value

        _setenv("SPFFT_TPU_FUSED_COMPRESS", "1" if args.fused else "0")
        if args.fused and jax.default_backend() != "tpu":
            # the fused seam only exists in the matmul-DFT pipeline and
            # off-TPU the kernels execute in interpret mode: the CPU A/B
            # lane measures honest orchestration overhead only
            # (docs/kernels.md)
            _setenv("SPFFT_TPU_FORCE_MATMUL_DFT", "1")
            _setenv("SPFFT_TPU_FUSED_INTERPRET", "1")
    try:
        return _run(args)
    finally:
        for key, value in restore.items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = value


def _run(args) -> int:
    dims = args.dimensions
    if len(dims) == 1:
        dims = dims * 3
    if len(dims) != 3:
        print("error: -d takes one or three values", file=sys.stderr)
        return 2
    if args.num_transforms < 1:
        print("error: -m must be >= 1", file=sys.stderr)
        return 2
    nx, ny, nz = dims

    import jax
    from . import timing
    from .grid import Transform
    from .plan import make_local_plan
    from .parallel import make_distributed_plan, make_mesh
    from .multi import multi_transform_backward, multi_transform_forward
    from .types import ExchangeType, Scaling, TransformType
    from .utils.dtypes import as_interleaved
    from .utils.workloads import (even_plane_split,
                                  round_robin_stick_partition)

    ttype = TransformType.C2C if args.transform == "c2c" else TransformType.R2C
    hermitian = ttype == TransformType.R2C
    triplets = cutoff_stick_triplets(nx, ny, nz, args.sparsity, hermitian)
    rng = np.random.default_rng(42)
    cdt = np.complex64 if args.precision == "single" else np.complex128

    if args.exchange == "all":
        if args.shards < 2:
            print("error: -e all compares exchange mechanisms and needs "
                  "--shards > 1", file=sys.stderr)
            return 2
        return _exchange_sweep(args, (nx, ny, nz), ttype, triplets, rng,
                               cdt)
    exchange = ExchangeType(_EXCHANGE[args.exchange])

    t0 = time.perf_counter()
    if args.shards > 1:
        if len(jax.devices()) < args.shards:
            print(f"error: {args.shards} shards but only "
                  f"{len(jax.devices())} devices", file=sys.stderr)
            return 2
        parts = round_robin_stick_partition(triplets, dims, args.shards)
        planes = even_plane_split(nz, args.shards)
        plan = make_distributed_plan(ttype, nx, ny, nz, parts, planes,
                                     mesh=make_mesh(args.shards),
                                     precision=args.precision,
                                     exchange=exchange,
                                     overlap_chunks=args.overlap_chunks,
                                     use_pallas=True if args.fused
                                     else None)
        values_np = [
            (rng.uniform(-1, 1, len(p)) + 1j * rng.uniform(-1, 1, len(p)))
            .astype(cdt) for p in parts]
        values = plan.shard_values(values_np)
    else:
        plan = make_local_plan(ttype, nx, ny, nz, triplets,
                               precision=args.precision,
                               use_pallas=True if args.fused else None)
        n = len(triplets)
        v = (rng.uniform(-1, 1, n) + 1j * rng.uniform(-1, 1, n)).astype(cdt)
        values_np = np.asarray(as_interleaved(v, args.precision))
        values = jax.device_put(values_np)
    plan_s = time.perf_counter() - t0

    transforms = [Transform(plan) for _ in range(args.num_transforms)]
    m = args.num_transforms

    serve_executor = None
    if args.serve:
        # the serving layer over the SAME plan: the registry is seeded
        # with the already-built plan and each repeat submits one
        # backward + one forward request per transform — the executor's
        # same-signature bucketing turns each phase into fused batches
        from .serve import PlanRegistry, PlanSignature, ServeExecutor
        registry = PlanRegistry()
        serve_sig = PlanSignature.of_plan(plan)
        registry.put(serve_sig, plan)
        serve_executor = ServeExecutor(registry)
        # every repeat submits exactly m same-signature requests, so the
        # adaptive observer pins the exact shape m after a few repeats —
        # prewarm it alongside the pow2 ladder to keep that compile out
        # of the measured loop
        serve_executor.prewarm(serve_sig, batch_sizes=(m,))

        def run_pair(vals):
            spaces = [f.result() for f in
                      [serve_executor.submit(serve_sig, vals)
                       for _ in range(m)]]
            outs = [f.result() for f in
                    [serve_executor.submit(serve_sig, s, "forward")
                     for s in spaces]]
            return outs
    elif args.fused_pair:
        def run_pair(vals):
            # one executable for backward+forward (apply_pointwise with
            # the identity operator) — the layout bench.py measures
            return plan.apply_pointwise(vals)
    else:
        def run_pair(vals):
            spaces = multi_transform_backward(transforms, [vals] * m)
            outs = multi_transform_forward(transforms, spaces,
                                           [Scaling.NONE] * m)
            return outs

    def sync(arrs):
        jax.block_until_ready(arrs)
        # Hard sync: a host readback of the last output (device programs
        # execute FIFO per core, so it waits for the whole queue).
        np.asarray(jax.tree_util.tree_leaves(arrs)[-1]).ravel()[:1]

    if args.repeats < 1 or args.warmups < 0:
        print("error: -r must be >= 1 and -w >= 0", file=sys.stderr)
        return 2
    host_io = args.proc == "host"
    feed = values_np if host_io else values

    def read_back(arrs):
        # host mode round-trips results to numpy inside the timed loop, so
        # both transfer directions are measured (reference -p cpu semantics)
        for a in jax.tree_util.tree_leaves(arrs):
            np.asarray(a)

    for _ in range(args.warmups):
        last = run_pair(feed)
    if args.warmups:
        sync(last)

    profiling = False
    if args.profile_dir:
        try:
            jax.profiler.start_trace(args.profile_dir)
            profiling = True
        except Exception as exc:
            print(f"warning: jax.profiler capture unavailable: {exc}",
                  file=sys.stderr)
    timing.enable()
    timing.GlobalTimer.reset()
    t0 = time.perf_counter()
    for _ in range(args.repeats):
        outs = run_pair(feed)
        if host_io:
            read_back(outs)
    sync(outs)
    total = time.perf_counter() - t0
    timing.disable()
    if profiling:
        try:
            jax.profiler.stop_trace()
            print(f"wrote jax.profiler trace to {args.profile_dir}",
                  file=sys.stderr)
        except Exception as exc:
            print(f"warning: jax.profiler stop failed: {exc}",
                  file=sys.stderr)

    pair_s = total / args.repeats
    result = timing.GlobalTimer.process()
    params = {
        "proc": args.proc, "shards": args.shards,
        "devices": len(jax.devices()), "backend": jax.default_backend(),
        "dim_x": nx, "dim_y": ny, "dim_z": nz,
        "exchange": args.exchange, "repeats": args.repeats,
        "overlap_chunks": int(getattr(plan, "overlap_chunks", 1)),
        "transform_type": args.transform, "num_transforms": m,
        "fused_pair": bool(args.fused_pair),
        "sparsity": args.sparsity, "precision": args.precision,
        "num_values": int(len(triplets)),
        "pallas": bool(getattr(plan, "_pallas_active", False)
                       or getattr(plan, "_pallas_dist", None) is not None),
        "fused": bool(getattr(plan, "fused_active", False)),
        "fused_fallback": dict(getattr(plan, "fused_fallback_reasons",
                                       None) or {}),
        # distributed fused twins (both directions), with the decline or
        # inactive:<why> reason disclosed per direction — the --fused
        # --overlap-chunks crossed A/B reads these to explain a seam
        # that did not engage
        "fused_dist": bool(getattr(plan, "fused_dist_active", False)),
        "fused_dist_fallback": {
            k: v for k, v in
            (("bwd", getattr(plan, "fused_dist_fallback_reason", None)),
             ("fwd", getattr(plan, "fused_dist_fwd_fallback_reason",
                             None)))
            if v is not None},
        "plan_seconds": round(plan_s, 4),
        "pair_seconds": round(pair_s, 6),
    }
    if serve_executor is not None:
        serve_executor.close()
        params["serve"] = serve_executor.metrics.snapshot(
            serve_executor.registry)
    if args.store_dir:
        params.update(_store_cold_warm(args, ttype, (nx, ny, nz),
                                       triplets))
    print(json.dumps(params, indent=2))
    result.print()
    if args.output:
        payload = json.loads(result.json())
        payload["parameters"] = params
        with open(args.output, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {args.output}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
