#!/usr/bin/env python
"""North-star benchmark: 256^3 spherical-cutoff C2C forward+backward pair.

Driver metric (BASELINE.json): wall-clock of a backward+forward pair on a
256^3 grid with a spherical-cutoff sparse frequency set, plus L2 error vs a
dense FFT oracle. Mirrors the reference benchmark workload
(reference: tests/programs/benchmark.cpp:176-205 builds a dense-within-cutoff
stick set; :84-96 times repeated backward+forward pairs).

Baseline: the reference publishes no numbers (BASELINE.md) and this container
has no FFTW/CUDA to build its benchmark, so the baseline is *generated* here:
the same sparse algorithm (stick z-FFTs -> scatter -> plane FFTs) run on CPU
via scipy's pocketfft with all available cores (workers=-1) — the moral
equivalent of the reference host path on this machine. ``vs_baseline`` is
baseline_seconds / tpu_seconds (>1 means faster than baseline).

Prints exactly one JSON line at the end:
  {"metric": ..., "value": ..., "unit": "s", "vs_baseline": ...}

Session handling: the estimator differences MEDIANS of several samples
per group size (utils/benchtime.py), which cancels any constant per-sync
cost. The measurement runs in SPFFT_BENCH_SESSIONS (default 4) fresh
backend sessions (compile/backend variance) and reports the best
session — disclosed in the metric string together with every session's
value. Each session is a child process, and a TPU belongs to one process
at a time, so the parent imports no JAX before or between its children
(:func:`run_child` enforces it); only CPU-pinned children may follow a
parent that holds the chip.

Env knobs: SPFFT_BENCH_DIM (default 256), SPFFT_BENCH_REPS (default 30),
SPFFT_BENCH_SESSIONS (default 4, set 1 to disable re-rolling),
SPFFT_BENCH_SKIP_BASELINE=1 to skip the CPU baseline (vs_baseline = 0).
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


#: A backend session is flagged as an outlier when its value exceeds
#: this factor times the MEDIAN of all sessions. Provenance: the r05
#: best-of-4 line carried a 274.74 ms session next to 10.6-11 ms ones —
#: a backend hiccup, not a compute mode. The best-of statistic
#: was already immune (min is never a high outlier), but the disclosed
#: per-session list distorted cross-round trajectory comparisons, so
#: hiccup sessions are split out and labelled instead of silently mixed
#: into the healthy list.
SESSION_OUTLIER_FACTOR = 2.5


def split_outlier_sessions(values):
    """Partition session values into (kept, outliers) around
    ``SESSION_OUTLIER_FACTOR x median``. The median includes every
    session, so one hiccup among >= 3 healthy sessions cannot shift the
    threshold onto healthy values; with k < 3 sessions nothing is ever
    flagged (too few samples to call anything an outlier)."""
    import statistics
    if len(values) < 3:
        return list(values), []
    cut = SESSION_OUTLIER_FACTOR * statistics.median(values)
    kept = [v for v in values if v <= cut]
    return kept, [v for v in values if v > cut]


def symmetry_rows() -> dict:
    """The hermitian-symmetry sub-rows, computed in a forced-CPU
    subprocess (fresh interpreter: the accounting is backend-independent
    and must not claim this process's backend):

    * ``wire_bytes_r2c`` — table-derived aggregate exchange wire bytes
      of the trimmed R2C distributed plan on the flagship spherical
      workload (deterministic accounting, no execution);
    * ``fused_r2c`` — how many of the two r2c fused seams (local
      backward kernel + distributed pre-exchange twin) are ACTIVE on
      the interpret lane (deterministic; 2 = the r2c decline stays
      lifted);
    * ``pod_routing`` — the round-18 pod frontend's skewed-trace
      imbalance reduction, rr completed-work skew over p2c skew
      (seeded discrete-event replay of the real ``load_score``;
      deterministic, so a drop means the routing policy regressed);
    * ``pod_wire`` / ``pod_wire_pooled`` — TCP-vs-loopback rpc_submit
      overhead through an in-process localhost HostAgent, on the
      connect-per-RPC wire and the kept-alive pooled wire;
    * ``spmd_coalesce`` — distributed requests per collective round
      for a concurrent same-signature burst through the pod SPMD
      coalescer (deterministic scheduler accounting);
    * ``recorder_overhead`` — per-request hot-path cost of the ARMED
      flight recorder (journal + tail retention) minus the disarmed
      path, from the deterministic ``obs.recorder.overhead_probe``
      micro A/B (the disarmed path itself is budgeted at <= 1% of a
      request in tests/test_recorder.py).

    A failed probe fails the run: a row that is silently omitted reads
    as a row that was never asked for.
    """
    env = dict(os.environ, SPFFT_BENCH_SYMMETRY_INNER="1",
               JAX_PLATFORMS="cpu",
               SPFFT_TPU_FORCE_MATMUL_DFT="1",
               SPFFT_TPU_FUSED_INTERPRET="1")
    proc = run_child(env)
    line = next((ln for ln in reversed(proc.stdout.splitlines())
                 if ln.startswith("{")), None)
    if proc.returncode != 0 or line is None:
        sys.stderr.write(proc.stdout[-1000:] + proc.stderr[-1000:])
        raise SystemExit("symmetry sub-row probe failed")
    return json.loads(line)


def run_child(env: dict) -> subprocess.CompletedProcess:
    """Run this script in a child process with ``env``. A TPU belongs to
    one process at a time: a parent that has imported JAX may hold the
    chip, and a child that needs it would then fail or hang. So a child
    that may use the chip requires a parent that has not imported JAX;
    a child pinned to the CPU (``JAX_PLATFORMS=cpu``) needs no chip."""
    if env.get("JAX_PLATFORMS") != "cpu" and "jax" in sys.modules:
        raise RuntimeError("bench.py imported JAX before starting a child "
                           "that needs the chip")
    return subprocess.run([sys.executable, os.path.abspath(__file__)],
                          capture_output=True, text=True, env=env)


def symmetry_inner() -> None:
    """SPFFT_BENCH_SYMMETRY_INNER=1: compute the symmetry sub-rows on a
    virtual-CPU backend and print them as one JSON line."""
    from spfft_tpu.utils.platform import force_virtual_cpu_devices
    force_virtual_cpu_devices(2)
    from spfft_tpu import TransformType, make_local_plan
    from spfft_tpu.parallel import make_distributed_plan, make_mesh
    from spfft_tpu.parallel.dist import build_distributed_plan
    from spfft_tpu.parallel.exchange import build_ragged_schedule
    from spfft_tpu.utils.workloads import (
        even_plane_split, round_robin_stick_partition,
        sort_triplets_stick_major, spherical_cutoff_triplets)

    # --- wire_bytes_r2c: host-side accounting only, no device work ---
    n = int(os.environ.get("SPFFT_BENCH_DIM", "256"))
    shards = 8
    full = spherical_cutoff_triplets(n)
    x, y, z = full[:, 0], full[:, 1], full[:, 2]
    # the non-redundant hermitian half: x > 0 plus the x = 0 plane's
    # canonical half-spectrum (docs/distributed.md "Hermitian symmetry")
    half = full[(x > 0) | ((x == 0) & ((y > 0) | ((y == 0) & (z >= 0))))]
    planes = even_plane_split(n, shards)
    dims = (n, n, n)
    elem = 8  # complex64 wire
    r2c_wire = build_ragged_schedule(build_distributed_plan(
        TransformType.R2C, n, n, n,
        round_robin_stick_partition(half, dims, shards),
        planes)).wire_elements() * elem
    c2c_dp = build_distributed_plan(
        TransformType.C2C, n, n, n,
        round_robin_stick_partition(full, dims, shards), planes)
    c2c_wire = build_ragged_schedule(c2c_dp).wire_elements() * elem

    # --- wire_bytes_int8: the compressed-wire ladder's bottom rung ---
    # Padded block layout (the only mechanism that carries the int8
    # rung: the scale sidecar rides each slot's row through the SAME
    # collective), same 256^3 spherical set and shard count. Backward
    # convention, scales included — one f32 absmax scale per
    # (slot, stick row). Compared against the f32 wire on the SAME
    # layout, so the ratio isolates the rung, not the layout.
    ms, mp = c2c_dp.max_sticks, c2c_dp.max_planes
    links = shards * (shards - 1)
    int8_wire = links * (ms * mp * 2 + ms * 4)
    f32_wire = links * ms * mp * 8

    # --- wire_error_int8: measured end-to-end rel-l2 of the rung ---
    # A real 2-shard int8-wire plan on the virtual-CPU mesh vs its
    # rung-0 twin: seeded spectrum with adversarial 10^+-4 per-value
    # dynamic range through the actual quantized collective.
    wn = 32
    wfull = spherical_cutoff_triplets(wn)
    wparts = round_robin_stick_partition(wfull, (wn, wn, wn), 2)
    wplanes = even_plane_split(wn, 2)
    w_ref = make_distributed_plan(
        TransformType.C2C, wn, wn, wn, wparts, wplanes,
        mesh=make_mesh(2), precision="single", wire_precision=0)
    w_int8 = make_distributed_plan(
        TransformType.C2C, wn, wn, wn, wparts, wplanes,
        mesh=make_mesh(2), precision="single", wire_precision=3)
    wrng = np.random.default_rng(0xA11)
    wv = [wrng.standard_normal(p.num_values)
          * 10.0 ** wrng.uniform(-4, 4, p.num_values)
          + 1j * wrng.standard_normal(p.num_values)
          for p in w_ref.dist_plan.shard_plans]
    ref_out = np.asarray(w_ref.backward(wv), np.float64)
    int8_out = np.asarray(w_int8.backward(wv), np.float64)
    wire_err = float(np.linalg.norm(int8_out - ref_out)
                     / np.linalg.norm(ref_out))

    # --- fused_r2c: the two r2c fused seams on the interpret lane ---
    fd = (8, 6, 128)  # dim_z % 128 == 0: fused eligibility floor
    xs, ys, zs = (np.arange(0, fd[0] // 2),
                  np.arange(-(fd[1] // 2 - 1), fd[1] // 2 + 1),
                  np.arange(-(fd[2] // 2 - 1), fd[2] // 2 + 1))
    X, Y, Z = np.meshgrid(xs, ys, zs, indexing="ij")
    t = np.stack([X.ravel(), Y.ravel(), Z.ravel()], axis=1)
    t = t[(t[:, 0] > 0) | ((t[:, 1] > 0) | ((t[:, 1] == 0)
                                            & (t[:, 2] >= 0)))]
    t = sort_triplets_stick_major(t, fd)
    local = make_local_plan(TransformType.R2C, *fd, t,
                            precision="single", use_pallas=True)
    fparts = [sort_triplets_stick_major(p, fd)
              for p in round_robin_stick_partition(t, fd, 2)]
    fplanes = even_plane_split(fd[2], 2)
    dist = make_distributed_plan(
        TransformType.R2C, *fd, fparts, fplanes, mesh=make_mesh(2),
        precision="single", use_pallas=True)
    # backward-twin activity, the seam this row has always counted
    # (the forward twin reports through the fused_dist row below)
    active = int(bool(local.fused_active)) + int(bool(
        dist.fused_dist_bwd_active))

    # --- fused_dist: both fused directions composed with overlap ---
    dist_ov = make_distributed_plan(
        TransformType.R2C, *fd, fparts, fplanes, mesh=make_mesh(2),
        precision="single", use_pallas=True, overlap_chunks=2)
    dist_active = (int(bool(dist_ov.fused_dist_bwd_active))
                   + int(bool(dist_ov.fused_dist_fwd_active)))

    # --- pod_routing: p2c-vs-rr skew on the recorded skewed trace ---
    from spfft_tpu.serve.cluster import simulate_routing
    rr = simulate_routing("rr")
    p2c = simulate_routing("p2c")

    # --- pod_wire: what the real TCP wire costs over loopback ---
    from spfft_tpu.net.transport import wire_overhead_probe
    wire = wire_overhead_probe(repeats=48)

    # --- spmd_coalesce: requests per collective round in a burst ---
    # 12 concurrent same-signature distributed requests against the
    # pod SPMD coalescer (default spmd_max_batch 8): the window drains
    # them in ceil(12/8) = 2 rounds, so a healthy scheduler scores
    # 6.0 req/round. Duck-typed plan — the row measures the SCHEDULER
    # (bit-exactness of the batched math is tier-1's job), so it is
    # deterministic on any backend.
    from spfft_tpu.control.config import global_config
    from spfft_tpu.serve.cluster import SPMDCoalescer
    from spfft_tpu.types import Scaling

    class _BurstPlan:
        def coalesce_backward(self, values_list):
            return list(values_list)

    cfg = global_config()
    old_knobs = (cfg.spmd_batch_window, cfg.max_queue)
    cfg.set("spmd_batch_window", 0.25, source="bench",
            reason="spmd_coalesce row burst window")
    cfg.set("max_queue", 64, source="bench",
            reason="spmd_coalesce row burst depth")
    lane = SPMDCoalescer(max_workers=1)
    burst = 12
    try:
        futs = [lane.submit("bench-spmd", _BurstPlan(), i, "backward",
                            Scaling.NONE, None) for i in range(burst)]
        for f in futs:
            f.result(timeout=60)
        spmd_sig = lane.signals()
    finally:
        cfg.set("spmd_batch_window", old_knobs[0], source="bench",
                reason="restore after spmd_coalesce row")
        cfg.set("max_queue", old_knobs[1], source="bench",
                reason="restore after spmd_coalesce row")
        lane.close()
    per_round = burst / max(spmd_sig["spmd_launches"], 1)

    # --- recorder_overhead: armed-vs-disarmed hot path micro A/B ---
    from spfft_tpu.obs.recorder import overhead_probe
    rec = overhead_probe()

    print(json.dumps({
        "wire_bytes_r2c": {
            "metric": f"{n}^3 spherical-cutoff R2C distributed exchange "
                      f"aggregate wire bytes ({shards} shards, compact "
                      f"schedule, table-derived accounting): hermitian-"
                      f"trimmed non-redundant stick set "
                      f"({len(half)} of {len(full)} values; untrimmed "
                      f"C2C wire {c2c_wire} B, ratio "
                      f"{r2c_wire / c2c_wire:.3f})",
            "value": int(r2c_wire),
            "unit": "bytes",
        },
        "fused_r2c": {
            "metric": "r2c fused seams ACTIVE on the interpret lane "
                      "(local decompress+z-DFT backward kernel + "
                      "distributed pre-exchange twin; 2 = the "
                      "hermitian_completion decline stays lifted, "
                      f"fallbacks: local={local.fused_fallback_reasons} "
                      f"dist={dist.fused_dist_fallback_reason})",
            "value": active,
            "unit": "seams",
        },
        "fused_dist": {
            "metric": "distributed fused directions ACTIVE under the "
                      "K=2 overlap pipeline (chunk-sliceable "
                      "decompress+z-DFT backward + post-exchange "
                      "z-DFT+compress forward twin; 2 = fusion and "
                      "overlap compose in both directions, reasons: "
                      f"bwd={dist_ov.fused_dist_fallback_reason} "
                      f"fwd={dist_ov.fused_dist_fwd_fallback_reason})",
            "value": dist_active,
            "unit": "directions",
        },
        "pod_routing": {
            "metric": "pod frontend skewed-trace imbalance reduction: "
                      "round-robin completed-work skew over p2c skew, "
                      "seeded discrete-event replay of the live "
                      "load_score (rr "
                      f"{rr['ratio']:.2f}x vs p2c {p2c['ratio']:.2f}x; "
                      "python -m spfft_tpu.serve.cluster --simulate)",
            "value": round(rr["ratio"] / p2c["ratio"], 3),
            "unit": "x",
        },
        "pod_wire": {
            "metric": "pod wire overhead: median rpc_submit round "
                      "trip through an in-process localhost-TCP "
                      "HostAgent minus the loopback lane's, same "
                      "executor + tiny C2C workload "
                      f"(loopback {wire['loopback_us']:.0f} us vs "
                      f"TCP {wire['tcp_us']:.0f} us, "
                      f"{wire['repeats']} warmed repeats; "
                      "net.transport.wire_overhead_probe)",
            "value": round(wire["overhead_us"], 1),
            "unit": "us",
        },
        "pod_wire_pooled": {
            "metric": "pod wire overhead with connection pooling: "
                      "median rpc_submit round trip over a KEPT-ALIVE "
                      "pooled TCP lane minus the loopback lane's, "
                      "same agent + workload as pod_wire "
                      f"(TCP pooled {wire['tcp_pooled_us']:.0f} us vs "
                      f"fresh-connect {wire['tcp_us']:.0f} us, pool "
                      f"hits {wire['pool_hits']}/"
                      f"{wire['pool_hits'] + wire['pool_misses']}; "
                      "net.transport.wire_overhead_probe)",
            "value": round(wire["overhead_pooled_us"], 1),
            "unit": "us",
        },
        "wire_bytes_int8": {
            "metric": f"{n}^3 spherical-cutoff C2C distributed exchange "
                      f"aggregate wire bytes on the int8 rung (padded "
                      f"block layout, {shards} shards, per-stick f32 "
                      f"scales INCLUDED: {links * ms * 4} B of scales "
                      f"on {links * ms * mp * 2} B payload; f32 wire "
                      f"on the same layout {f32_wire} B, ratio "
                      f"{int8_wire / f32_wire:.4f})",
            "value": int(int8_wire),
            "unit": "bytes",
        },
        "wire_error_int8": {
            "metric": "measured end-to-end rel-l2 of the int8 wire "
                      f"rung: {wn}^3 spherical C2C backward on 2 "
                      "virtual shards vs the rung-0 twin, seeded "
                      "spectrum with 10^+-4 per-value dynamic range "
                      f"(plan probe err {w_int8.wire_probe_error:.2e}, "
                      f"declared budget {w_int8.wire_error_budget:g}, "
                      f"resolved rung {w_int8.wire_rung_name})",
            "value": round(wire_err, 6),
            "unit": "rel-l2",
        },
        "spmd_coalesce": {
            "metric": "cross-request SPMD coalescing: distributed "
                      "requests per collective round for a 12-request "
                      "same-signature burst through the pod coalescer "
                      f"(spmd_max_batch 8 -> {spmd_sig['spmd_launches']}"
                      f" launches, batch hist "
                      f"{spmd_sig['spmd_batch_hist']}; a drop means "
                      "the window splinters rounds)",
            "value": round(per_round, 2),
            "unit": "req/round",
        },
        "recorder_overhead": {
            "metric": "flight-recorder armed hot-path cost per "
                      "request: journal events + trace tail retention "
                      "with the recorder ON minus the same path "
                      "disarmed, deterministic synthetic-request "
                      "micro A/B (obs.recorder.overhead_probe, "
                      f"{rec['requests']} requests x {rec['repeats']} "
                      f"repeats, best-of; disarmed path "
                      f"{rec['off_us']:.2f} us/req, armed "
                      f"{rec['on_us']:.2f} us/req)",
            "value": round(rec["delta_us"], 2),
            "unit": "us",
        },
    }))


def run_sessions(k: int) -> None:
    """Run the measurement in k fresh subprocesses (each gets its own
    backend session) and emit the best session's JSON with the per-session
    values disclosed (outlier sessions flagged separately)."""
    results = []
    for i in range(k):
        env = dict(os.environ, SPFFT_BENCH_INNER="1",
                   SPFFT_BENCH_SKIP_BASELINE="1")
        proc = run_child(env)
        line = next((ln for ln in reversed(proc.stdout.splitlines())
                     if ln.startswith("{")), None)
        if line is None:
            sys.stderr.write(proc.stdout[-2000:] + proc.stderr[-2000:])
            raise SystemExit(f"bench session {i} produced no JSON")
        results.append(json.loads(line))
    best = min(results, key=lambda r: r["value"])
    kept, outliers = split_outlier_sessions([r["value"] for r in results])
    sessions_ms = ", ".join(f"{v * 1e3:.2f}" for v in kept)
    outlier_note = ("" if not outliers else
                    f"; {len(outliers)} outlier session(s) dropped: "
                    + ", ".join(f"{v * 1e3:.2f}" for v in outliers)
                    + " ms")
    rows = symmetry_rows()  # CPU-pinned child, before this process imports JAX
    if os.environ.get("SPFFT_BENCH_SKIP_BASELINE") == "1":
        baseline_s = 0.0
    else:
        baseline_s = baseline_only()
    best["metric"] += (f" [best of {k} backend sessions: {sessions_ms} ms"
                       f"{outlier_note}]"
                       f" (baseline=pocketfft[{os.cpu_count()}cpu] "
                       f"{baseline_s:.3f}s)")
    best["vs_baseline"] = (round(baseline_s / best["value"], 3)
                           if baseline_s else 0.0)
    best.update(rows)
    print(json.dumps(best))


def baseline_only() -> float:
    """The CPU pocketfft baseline without touching the TPU backend."""
    from spfft_tpu.indexing import build_index_plan
    from spfft_tpu.types import TransformType
    from spfft_tpu.utils.workloads import spherical_cutoff_triplets
    n = int(os.environ.get("SPFFT_BENCH_DIM", "256"))
    triplets = spherical_cutoff_triplets(n)
    rng = np.random.default_rng(42)
    values = (rng.uniform(-1, 1, len(triplets))
              + 1j * rng.uniform(-1, 1, len(triplets))).astype(np.complex64)

    class _P:  # minimal plan view for cpu_baseline_pair_seconds
        index_plan = build_index_plan(TransformType.C2C, n, n, n,
                                      np.asarray(triplets))
    return cpu_baseline_pair_seconds(_P, values)


def cpu_baseline_pair_seconds(plan, values: np.ndarray, reps: int = 2) -> float:
    """The same sparse pipeline on CPU (pocketfft, workers=-1 i.e. all
    available cores), timed after one warm-up rep (first-touch allocation and
    pocketfft plan setup excluded, matching the warmed TPU measurement)."""
    from scipy import fft as sfft
    ip = plan.index_plan
    nz, ny, nxf = ip.dim_z, ip.dim_y, ip.dim_x_freq
    cols = ip.scatter_cols
    vi = ip.value_indices
    t0 = time.perf_counter()
    for rep in range(reps + 1):
        if rep == 1:
            t0 = time.perf_counter()  # discard the warm-up rep
        # backward: decompress -> z-IFFT -> scatter -> xy-IFFT
        sticks = np.zeros((ip.num_sticks * nz,), np.complex64)
        sticks[vi] = values
        sticks = sticks.reshape(ip.num_sticks, nz)
        sticks = sfft.ifft(sticks, axis=1, workers=-1) * nz
        grid = np.zeros((nz, ny * nxf), np.complex64)
        grid[:, cols] = sticks.T
        grid = grid.reshape(nz, ny, nxf)
        space = sfft.ifft2(grid, axes=(1, 2), workers=-1) * (ny * nxf)
        # forward: xy-FFT -> gather -> z-FFT -> compress
        grid = sfft.fft2(space, axes=(1, 2), workers=-1)
        sticks = grid.reshape(nz, ny * nxf)[:, cols].T
        sticks = np.ascontiguousarray(sticks)
        sticks = sfft.fft(sticks, axis=1, workers=-1)
        _ = sticks.reshape(-1)[vi]
    return (time.perf_counter() - t0) / reps


def main() -> None:
    if os.environ.get("SPFFT_BENCH_SYMMETRY_INNER") == "1":
        return symmetry_inner()
    k = int(os.environ.get("SPFFT_BENCH_SESSIONS", "4"))
    if "SPFFT_BENCH_INNER" not in os.environ and k > 1:
        return run_sessions(k)
    import jax
    from spfft_tpu import TransformType, make_local_plan
    from spfft_tpu.utils import as_interleaved
    from spfft_tpu.utils.workloads import spherical_cutoff_triplets

    n = int(os.environ.get("SPFFT_BENCH_DIM", "256"))
    reps = int(os.environ.get("SPFFT_BENCH_REPS", "30"))

    triplets = spherical_cutoff_triplets(n)
    rng = np.random.default_rng(42)
    values = (rng.uniform(-1, 1, len(triplets))
              + 1j * rng.uniform(-1, 1, len(triplets))).astype(np.complex64)

    jax.devices()  # backend bring-up is session cost, not plan cost —
    # keep it out of plan_s
    t_plan = time.perf_counter()
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single")
    t_plan = time.perf_counter() - t_plan

    values_il = jax.device_put(
        np.asarray(as_interleaved(values, "single")))

    def sync(arr):
        # A host readback of one element is a hard sync. Device programs
        # execute FIFO per core, so syncing the last enqueued output
        # syncs the whole queue. (On a locally attached v5e,
        # block_until_ready waits for the work too: chip_smoke.py.)
        return float(np.asarray(arr.ravel()[0]))

    # The benchmark pair through the public fused round-trip API
    # (plan.apply_pointwise with identity fn): one executable for
    # backward+forward — saves a dispatch round trip and lets XLA schedule
    # across the boundary (18.6 vs 25.6 ms at 256^3 on TPU v5e). The
    # separate backward call still produces the space field used for the
    # accuracy check.
    space = plan.backward(values_il)
    out = plan.apply_pointwise(values_il)  # warm-up / compile
    sync(out)

    # Variance-robust statistic: any "time N reps then sync" number
    # includes sync_cost/N of readback latency. The difference-of-group-
    # sizes estimator cancels that constant: pair = (medT(g2) - medT(g1))
    # / (g2 - g1), medians over several samples per size.
    from spfft_tpu.utils.benchtime import diff_estimate_seconds

    def timed(g):
        t0 = time.perf_counter()
        o = None
        for _ in range(g):
            o = plan.apply_pointwise(values_il)
        sync(o)
        return time.perf_counter() - t0

    est = diff_estimate_seconds(timed, reps=reps)
    pair_s, stat = est.seconds, est.label

    # accuracy: L2 error of the backward result vs a dense oracle
    st = triplets.copy()
    st = np.where(st < 0, st + n, st)
    cube = np.zeros((n, n, n), np.complex64)
    cube[st[:, 2], st[:, 1], st[:, 0]] = values
    from scipy import fft as sfft
    oracle = sfft.ifftn(cube, workers=-1) * cube.size
    got = np.asarray(space)
    got = got[..., 0] + 1j * got[..., 1]
    l2 = float(np.linalg.norm(got - oracle) / np.linalg.norm(oracle))

    if os.environ.get("SPFFT_BENCH_SKIP_BASELINE") == "1":
        baseline_s = 0.0
    else:
        baseline_s = cpu_baseline_pair_seconds(plan, values)

    # Effective bandwidth: logical bytes of the pair (each stage's elements
    # read + written once, c64 = 8 B; see scripts/profile_stages.py for the
    # per-stage model and the measured copy floor it compares against).
    ip = plan.index_plan
    sz = ip.num_sticks * ip.dim_z
    pair_bytes = (2 * ip.num_values + 8 * sz + 6 * n ** 3) * 8
    gbs = pair_bytes / pair_s / 1e9

    base_note = (f", baseline=pocketfft[{os.cpu_count()}cpu] "
                 f"{baseline_s:.3f}s" if baseline_s else "")
    result = {
        "metric": f"{n}^3 spherical-cutoff C2C fwd+bwd pair wall-clock, "
                  f"{stat} ("
                  f"l2_err_vs_dense={l2:.2e}, plan_s={t_plan:.2f}, "
                  f"n_values={len(triplets)}, "
                  f"effective_GBps={gbs:.0f}{base_note})",
        "value": round(pair_s, 6),
        "unit": "s",
        "vs_baseline": round(baseline_s / pair_s, 3) if baseline_s else 0.0,
    }
    if "SPFFT_BENCH_INNER" not in os.environ:
        result.update(symmetry_rows())  # single-session direct run
    print(json.dumps(result))


if __name__ == "__main__":
    main()
