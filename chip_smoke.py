#!/usr/bin/env python
"""Bring-up smoke of spfft_tpu on a locally attached TPU.

One process drives the public entry points at the flagship size: a
256^3 spherical-cutoff sparse transform (about 8.8 M values, single
precision), C2C and R2C, through ``make_local_plan``, then the serving
path (``PlanRegistry`` + ``ServeExecutor``) over the same C2C
signature. Every result is checked against a dense FFT oracle or the
local plan, and the script fails unless the Pallas gather kernel and
both fused compression+z-DFT kernels ran on the chip: no runtime
demotion, no plan-time decline, no AOT skip, ``tpu_custom_call`` in the
executed programs.

``--chips 4`` runs only the distributed phase: ``make_distributed_plan``
over a 4-device mesh at 256^3 C2C with the buffered ``all_to_all``
exchange and the compact ``ragged_all_to_all`` exchange, each against
the dense oracle, with the shards checked to sit on four devices.

No phase runs without a TPU: the script exits non-zero before any work
when the first JAX device is not a TPU. The last line of a passing run
is ``{"ok": true, "device": {"platform", "kind", "count"}}``; the
earlier lines carry the errors and informational timings (labelled with
the device kind; they are not benchmark numbers).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

#: Relative l2 bar of a single-precision result against the dense
#: oracle: the reference's 1e-6 contract (tests_tpu/test_tpu_ci.py TOL,
#: plan.predicted_rel_error).
REL_TOL = 1e-6

#: Counters a kernel that was not engaged increments (plan-time
#: declines, runtime demotions, AOT executables skipped).
FALLBACK_COUNTERS = ("spfft_plan_pallas_fallback_total",
                     "spfft_fused_demotions_total",
                     "spfft_store_aot_skipped_total")


class SmokeFailure(Exception):
    """A phase failed; the message names the reason."""


def say(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


def rel_err(got, want) -> float:
    return float(np.linalg.norm(got - want)
                 / max(float(np.linalg.norm(want)), 1e-30))


def check_close(label: str, got, want) -> float:
    """Relative l2 under REL_TOL and max-abs under the tests' single
    bar (tests/test_util.tolerance_for: 3e-5 x max(1, max|oracle|))."""
    rel = rel_err(got, want)
    abs_err = float(np.max(np.abs(got - want))) if want.size else 0.0
    abs_tol = 3e-5 * max(1.0, float(np.max(np.abs(want))))
    say(f"{label}: rel_l2={rel!r} max_abs={abs_err!r} "
        f"(bars: rel {REL_TOL}, abs {abs_tol!r})")
    require(rel < REL_TOL and abs_err <= abs_tol,
            f"{label} misses the single-precision bar")
    return rel


def device_check(want_count: int):
    """Print the device, versions and planner state; refuse anything
    but a TPU with at least ``want_count`` devices."""
    if os.environ.get("SPFFT_TPU_FUSED_INTERPRET"):
        raise SmokeFailure("SPFFT_TPU_FUSED_INTERPRET is set: the fused "
                           "kernels must compile for the chip")
    import jax
    from importlib import metadata

    from spfft_tpu import native
    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    say(f"devices: {devs}")
    say(f"platform={devs[0].platform} device_kind={devs[0].device_kind} "
        f"count={len(devs)} jax={jax.__version__} libtpu={libtpu}")
    say(f"native planner loaded: {native.available()}")
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"first device is {devs[0].platform!r}, not a "
                           "TPU: nothing runs without the chip")
    require(len(devs) >= want_count,
            f"{want_count} chips requested, {len(devs)} visible")
    z = np.array([1 + 2j, 3 - 4j], np.complex64)
    try:
        same = np.array_equal(np.asarray(jax.device_put(z)), z)
        say(f"complex64 host<->device round trip: "
            f"{'exact' if same else 'MISMATCH'}")
    except Exception as e:  # informational: the library moves reals
        say(f"complex64 host<->device round trip failed: {e!r}")
    from spfft_tpu.utils.platform import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()
    say(f"compilation cache: {jax.config.jax_compilation_cache_dir}")
    return devs


def fallback_counts() -> dict:
    """Non-zero samples of the fallback counters, by series."""
    from spfft_tpu import obs
    snap = obs.GLOBAL_COUNTERS.snapshot()
    out = {}
    for name in FALLBACK_COUNTERS:
        for labels, value in snap.get(name, {}).get("samples", {}).items():
            if value:
                out[f"{name}{dict(labels)}"] = value
    return out


def engagement_problems(plan, values_il, space) -> list:
    """Why the plan's kernels were not all engaged (empty when they
    were): gather kernel, both fused directions, no demotion, and
    ``tpu_custom_call`` in the lowered backward, forward and pair."""
    from spfft_tpu import Scaling
    problems = []
    if not plan._pallas_active:
        problems.append("Pallas gather kernel inactive")
    if not plan._fused_active_flag:
        problems.append("fused compression+z-DFT kernels inactive")
    for which in ("dec", "cmp"):
        if plan._fused.get(which) is None:
            problems.append(f"fused {which} kernel declined: "
                            f"{plan._fused_reasons.get(which)}")
    if plan.fused_demotions():
        problems.append(f"runtime demotions: {plan.fused_demotions()}")
    tabs = plan._tables_hot
    pair = plan._pair_jits.get((None, Scaling.NONE))
    lowered = {
        "backward": plan._backward_jit.lower(values_il, tabs),
        "forward": plan._forward_jit[Scaling.NONE].lower(space, tabs),
        "pair": None if pair is None else pair.lower(values_il, tabs),
    }
    for name, low in lowered.items():
        if low is None:
            problems.append(f"{name} was never executed")
            continue
        n = low.as_text().count("tpu_custom_call")
        say(f"  {name}: {n} tpu_custom_call in the lowered program")
        if n == 0:
            problems.append(f"no tpu_custom_call in the lowered {name}")
    return problems


def sphere_values(n: int, seed: int):
    from spfft_tpu.utils.workloads import spherical_cutoff_triplets
    triplets = spherical_cutoff_triplets(n)
    rng = np.random.default_rng(seed)
    values = (rng.uniform(-1, 1, len(triplets))
              + 1j * rng.uniform(-1, 1, len(triplets))).astype(np.complex64)
    return triplets, values


def dense_cube(triplets, values, n: int):
    idx = np.where(triplets < 0, triplets + n, triplets)
    cube = np.zeros((n, n, n), np.complex128)
    cube[idx[:, 2], idx[:, 1], idx[:, 0]] = values
    return cube


def c2c_oracle(triplets, values, n: int):
    from scipy import fft as sfft
    cube = dense_cube(triplets, values, n)
    return sfft.ifftn(cube, workers=-1) * cube.size


def time_pair(plan, values_il, kind: str) -> None:
    """Informational: one backward+forward pair through apply_pointwise
    with block_until_ready, and whether block_until_ready waits (a
    one-value readback after it should then cost next to nothing)."""
    import jax
    for _ in range(2):  # the first pass also compiles the readback slice
        t0 = time.perf_counter()
        out = plan.apply_pointwise(values_il)
        t_dispatch = time.perf_counter() - t0
        jax.block_until_ready(out)
        t_block = time.perf_counter() - t0
        t1 = time.perf_counter()
        np.asarray(out[:1])
        t_read = time.perf_counter() - t1
    say(f"info [{kind}]: one backward+forward pair: dispatch "
        f"{t_dispatch!r} s, block_until_ready {t_block!r} s, then a "
        f"one-value readback {t_read!r} s (not a benchmark number)")


def run_local(n: int = 256, seed: int = 42, require_kernels: bool = True,
              kind: str = "cpu"):
    """Local C2C and R2C at n^3. Returns the C2C plan and its triplets
    for the serving phase."""
    import jax

    from spfft_tpu import Scaling, TransformType, make_local_plan
    from spfft_tpu.utils import as_interleaved

    triplets, values = sphere_values(n, seed)
    say(f"C2C {n}^3: {len(triplets)} values")
    t0 = time.perf_counter()
    plan = make_local_plan(TransformType.C2C, n, n, n, triplets,
                           precision="single")
    plan._finalize()
    say(f"info [{kind}]: C2C plan built in {time.perf_counter() - t0!r} s")
    host_il = np.asarray(as_interleaved(values, "single"))
    t0 = time.perf_counter()
    values_il = jax.block_until_ready(jax.device_put(host_il))
    say(f"info [{kind}]: host->device copy of the {host_il.nbytes!r}-byte "
        f"value array {time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    space = jax.block_until_ready(plan.backward(values_il))
    say(f"info [{kind}]: first backward (compile + run) "
        f"{time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    fwd = jax.block_until_ready(plan.forward(space))
    say(f"info [{kind}]: first forward (compile + run) "
        f"{time.perf_counter() - t0!r} s")
    t0 = time.perf_counter()
    pair = jax.block_until_ready(plan.apply_pointwise(values_il))
    say(f"info [{kind}]: first apply_pointwise (compile + run) "
        f"{time.perf_counter() - t0!r} s")
    got = np.asarray(space)
    check_close("C2C backward vs dense ifftn",
                got[..., 0] + 1j * got[..., 1],
                c2c_oracle(triplets, values, n))
    nv = values.astype(np.complex128) * float(n ** 3)
    f = np.asarray(fwd)
    check_close("C2C forward(backward(v)) vs N*v",
                f[:, 0] + 1j * f[:, 1], nv)
    p = np.asarray(pair)
    check_close("C2C apply_pointwise(v) vs N*v", p[:, 0] + 1j * p[:, 1], nv)
    if require_kernels:
        problems = engagement_problems(plan, values_il, space)
        require(not problems, "C2C kernels not engaged: "
                + "; ".join(problems))
    time_pair(plan, values_il, kind)
    del space, fwd, pair, got, f, p

    # R2C: a sphere-limited real field; the plan sees the x >= 0 half
    rng = np.random.default_rng(seed + 1)
    field = rng.standard_normal((n, n, n))
    spec = np.fft.fftn(field)
    idx = np.where(triplets < 0, triplets + n, triplets)
    mask = np.zeros((n, n, n), bool)
    mask[idx[:, 2], idx[:, 1], idx[:, 0]] = True
    oracle = np.fft.ifftn(np.where(mask, spec, 0)).real * float(n ** 3)
    half = triplets[triplets[:, 0] >= 0]
    hidx = idx[triplets[:, 0] >= 0]
    hvals = spec[hidx[:, 2], hidx[:, 1], hidx[:, 0]].astype(np.complex64)
    del field, spec, mask
    say(f"R2C {n}^3: {len(half)} values")
    t0 = time.perf_counter()
    rplan = make_local_plan(TransformType.R2C, n, n, n, half,
                            precision="single")
    rplan._finalize()
    say(f"info [{kind}]: R2C plan built in {time.perf_counter() - t0!r} s")
    hvals_il = jax.device_put(np.asarray(as_interleaved(hvals, "single")))
    rspace = jax.block_until_ready(rplan.backward(hvals_il))
    rfwd = jax.block_until_ready(rplan.forward(rspace))
    rpair = jax.block_until_ready(rplan.apply_pointwise(hvals_il))
    check_close("R2C backward vs dense ifftn", np.asarray(rspace), oracle)
    hn = hvals.astype(np.complex128) * float(n ** 3)
    f = np.asarray(rfwd)
    check_close("R2C forward(backward(v)) vs N*v",
                f[:, 0] + 1j * f[:, 1], hn)
    p = np.asarray(rpair)
    check_close("R2C apply_pointwise(v) vs N*v", p[:, 0] + 1j * p[:, 1], hn)
    if require_kernels:
        problems = engagement_problems(rplan, hvals_il, rspace)
        require(not problems, "R2C kernels not engaged: "
                + "; ".join(problems))
    rplan.close()
    return plan, triplets


def run_serving(plan, triplets, n: int, requests: int = 8,
                require_kernels: bool = True, seed: int = 7) -> None:
    """PlanRegistry + ServeExecutor(devices="all") over the same C2C
    signature; every served backward must match the local plan's."""
    from spfft_tpu import TransformType
    from spfft_tpu.serve.executor import ServeExecutor
    from spfft_tpu.serve.registry import PlanRegistry

    reg = PlanRegistry()
    sig, served_plan = reg.get_or_build(TransformType.C2C, n, n, n,
                                        triplets, precision="single")
    rng = np.random.default_rng(seed)
    batch = [(rng.uniform(-1, 1, len(triplets))
              + 1j * rng.uniform(-1, 1, len(triplets))).astype(np.complex64)
             for _ in range(requests)]
    ex = ServeExecutor(reg, devices="all")
    try:
        futures = [ex.submit(sig, v) for v in batch]
        results = [np.asarray(fut.result(timeout=600)) for fut in futures]
    finally:
        ex.close()
    require(ex._thread is None or not ex._thread.is_alive(),
            "serving dispatcher still running after close()")
    snap = ex.metrics.snapshot()
    exact = 0
    for k, (v, got) in enumerate(zip(batch, results)):
        want = np.asarray(plan.backward(v))
        exact += int(np.array_equal(got, want))
        rel = rel_err(got, want)
        require(rel < REL_TOL, f"served request {k} differs from the "
                               f"local plan: rel_l2={rel!r}")
    say(f"serving: {len(results)} requests matched the local plan "
        f"({exact} bit-exact); completed={snap['completed']} "
        f"failed={snap['failed']} fused_batches={snap['fused_batches']} "
        f"serial_batches={snap['serial_batches']}; executor closed")
    require(snap["failed"] == 0 and snap["completed"] == requests,
            "serving metrics disagree with the served requests")
    if require_kernels:
        require(served_plan._pallas_active
                and served_plan._fused_active_flag
                and not served_plan.fused_demotions(),
                "the served plan's kernels were not engaged")
    served_plan.close()


def run_distributed(n: int = 256, shards: int = 4, seed: int = 3,
                    require_kernels: bool = True) -> None:
    """make_distributed_plan over make_mesh(shards), buffered
    all_to_all and compact ragged_all_to_all, each vs the dense oracle,
    with the shards checked to sit on distinct devices."""
    from spfft_tpu import ExchangeType, TransformType, make_mesh
    from spfft_tpu import make_distributed_plan
    from spfft_tpu.utils.workloads import (even_plane_split,
                                           round_robin_stick_partition,
                                           spherical_cutoff_triplets)
    triplets = spherical_cutoff_triplets(n)
    parts = round_robin_stick_partition(triplets, (n, n, n), shards)
    planes = even_plane_split(n, shards)
    rng = np.random.default_rng(seed)
    values = [(rng.uniform(-1, 1, len(p)) + 1j * rng.uniform(-1, 1, len(p)))
              .astype(np.complex64) for p in parts]
    oracle = c2c_oracle(np.concatenate(parts), np.concatenate(values), n)
    mesh = make_mesh(shards)
    for exchange in (ExchangeType.DEFAULT, ExchangeType.COMPACT_BUFFERED):
        name = exchange.name
        t0 = time.perf_counter()
        plan = make_distributed_plan(TransformType.C2C, n, n, n, parts,
                                     planes, mesh=mesh, precision="single",
                                     exchange=exchange)
        say(f"info: distributed {name} plan built in "
            f"{time.perf_counter() - t0!r} s")
        t0 = time.perf_counter()
        space = plan.backward(values)
        space.block_until_ready()
        say(f"info: distributed {name} first backward (compile + run) "
            f"{time.perf_counter() - t0!r} s")
        devices = {s.device for s in space.addressable_shards}
        say(f"{name}: shards on {len(devices)} devices: "
            f"{sorted(str(d) for d in devices)}")
        require(len(devices) == shards,
                f"{name}: shards on {len(devices)} devices, not {shards}")
        slabs = plan.unshard_space(space)
        got = np.concatenate(slabs, axis=0)
        check_close(f"distributed {name} backward vs dense ifftn",
                    got, oracle)
        fwd = plan.unshard_values(plan.forward(space))
        want = np.concatenate(values).astype(np.complex128) * float(n ** 3)
        check_close(f"distributed {name} forward(backward(v)) vs N*v",
                    np.concatenate(fwd), want)
        say(f"{name}: pallas={plan._pallas_dist is not None} "
            f"interpret={plan._pallas_interpret} "
            f"fused_bwd={plan.fused_dist_bwd_active} "
            f"fused_fwd={plan.fused_dist_fwd_active} "
            f"ragged_emulated={plan._ragged_emulate} "
            f"ragged={plan._ragged is not None}")
        if require_kernels:
            require(plan._pallas_dist is not None
                    and not plan._pallas_interpret,
                    f"{name}: distributed gather kernel inactive or "
                    "in interpret mode")
            for fd in (plan._fused_dist, plan._fused_dist_fwd):
                require(fd is None or not fd["interpret"],
                        f"{name}: fused kernel in interpret mode")
            require(not plan._ragged_emulate,
                    f"{name}: ragged_all_to_all emulated on the chip")
        if exchange is ExchangeType.COMPACT_BUFFERED:
            require(plan._ragged is not None,
                    "COMPACT_BUFFERED did not select ragged_all_to_all")
        del space, slabs, got, fwd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the distributed phase")
    args = ap.parse_args(argv)
    try:
        devs = device_check(args.chips)
        kind = devs[0].device_kind
        if args.chips == 4:
            run_distributed(shards=4)
        else:
            plan, triplets = run_local(kind=kind)
            run_serving(plan, triplets, n=256)
            plan.close()
        counts = fallback_counts()
        say(f"fallback counters: {counts or 'all zero'}")
        require(not counts, f"fallback counters incremented: {counts}")
    except SmokeFailure as e:
        say(f"FAIL: {e}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": kind, "count": len(devs)}}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
