"""On-TPU regression net (make ci-tpu): the exact code paths the
CPU-pinned suite cannot exercise, each against a dense numpy oracle.

Coverage (round-4 verdict item 3 + advisor finding 3):
  * oracle matrix 32^3/64^3, C2C + R2C, centered + positive indexing
  * Pallas compression kernel forced on (real Mosaic codegen + DMA)
  * the segmented aliased-carry accumulate path (input/output aliasing
    semantics only real hardware honors — the interpreter keeps the
    concat path, so this was previously validated by hand-run probes
    only)
  * split-x (occupied-window xy stage), pair-IO (2, N) boundary,
    two-stage Cooley-Tukey long axis, repeated-backward stability,
    fused iterate_pointwise
  * the fused compression+z-DFT kernels (ops/fused_kernel.py) on real
    Mosaic: bit-exact vs the dense oracle and the unfused two-kernel
    path, plus --profile-dir evidence that the dense stick
    intermediate is gone from the device profile (docs/kernels.md)
"""

import numpy as np
import pytest

import spfft_tpu.plan as plan_mod
from spfft_tpu import Scaling, TransformType, make_local_plan
from spfft_tpu.ops import gather_kernel as gk
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

TOL = 1e-6


def _values(n_values, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n_values)
            + 1j * rng.standard_normal(n_values)).astype(np.complex64)


def _dense_c2c_oracle(triplets, vals, dims):
    nx, ny, nz = dims
    st = triplets.copy()
    for a, n in enumerate(dims):
        st[:, a] = np.where(st[:, a] < 0, st[:, a] + n, st[:, a])
    cube = np.zeros((nz, ny, nx), np.complex64)
    cube[st[:, 2], st[:, 1], st[:, 0]] = vals
    return np.fft.ifftn(cube) * cube.size


def _rel(got, want):
    return np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30)


def _check_c2c(plan, triplets, n, seed=0):
    vals = _values(len(triplets), seed)
    space = np.asarray(plan.backward(vals))
    got = space[..., 0] + 1j * space[..., 1]
    oracle = _dense_c2c_oracle(triplets, vals, (n, n, n))
    assert _rel(got, oracle) < TOL
    out = np.asarray(plan.forward(space, Scaling.FULL))
    if plan.pair_values_io:
        out = out.T
    assert _rel(out[:, 0] + 1j * out[:, 1], vals) < TOL
    return space


@pytest.mark.parametrize("n", [32, 64])
@pytest.mark.parametrize("indexing", ["centered", "positive"])
def test_oracle_c2c(n, indexing):
    tr = spherical_cutoff_triplets(n, radius=n // 2 - 1)
    if indexing == "positive":
        tr = np.where(tr < 0, tr + n, tr)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    _check_c2c(plan, tr, n)


@pytest.mark.parametrize("n", [32, 64])
@pytest.mark.parametrize("indexing", ["centered", "positive"])
def test_oracle_r2c(n, indexing):
    rng = np.random.default_rng(1)
    field = rng.standard_normal((n, n, n)).astype(np.float32)
    freq = np.fft.fftn(field)
    half = []
    for x in range(n // 2 + 1):
        for y in range(n):
            for z in range(n):
                half.append((x, y, z))
    tr = np.asarray(half, np.int64)
    vals = freq[tr[:, 2], tr[:, 1], tr[:, 0]].astype(np.complex64)
    if indexing == "centered":
        tr = tr.copy()
        for a in (1, 2):
            tr[:, a] = np.where(tr[:, a] > n // 2, tr[:, a] - n, tr[:, a])
    plan = make_local_plan(TransformType.R2C, n, n, n, tr,
                           precision="single")
    space = np.asarray(plan.backward(vals))
    assert _rel(space, field * field.size) < TOL
    out = np.asarray(plan.forward(space, Scaling.FULL))
    assert _rel(out[:, 0] + 1j * out[:, 1], vals) < TOL


def test_pallas_kernel_forced():
    """The Mosaic windowed-gather kernel on real hardware (auto-gate
    would skip it below 200k values; forcing keeps this test fast)."""
    n = 64
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single", use_pallas=True)
    assert plan.pallas_active
    _check_c2c(plan, tr, n, seed=2)


def test_segmented_aliased_carry_accumulate(monkeypatch):
    """Segmented multi-launch gathers accumulate through pallas
    input/output aliasing on real hardware — semantics the interpreter
    does not honor, so only this lane can regression-test them
    (advisor r4 finding 3). Shrinking the launch limits forces many
    segments on a small plan; the result must still match both the
    dense oracle and the XLA-gather path."""
    # limit 2 at 32^3 segments BOTH table kinds (measured: decompress =
    # wide kernel, 2 segments; compress = narrow kernel, 14 segments)
    monkeypatch.setattr(gk, "SEG_CHUNK_LIMIT", 2)
    monkeypatch.setattr(gk, "SMEM_TABLE_WORDS", 2 * (3 + gk.WIDE_P // 4))
    n = 32
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single", use_pallas=True)
    assert plan.pallas_active
    box = plan._pallas
    assert all(t is not None and t.segs for t in box.values()), \
        "launch limits did not force segmentation on both directions"
    vals = _values(len(tr), 3)
    space = np.asarray(plan.backward(vals))
    got = space[..., 0] + 1j * space[..., 1]
    oracle = _dense_c2c_oracle(tr, vals, (n, n, n))
    assert _rel(got, oracle) < TOL
    # forward leg drives the segmented COMPRESS carry
    out = np.asarray(plan.forward(space, Scaling.FULL))
    assert _rel(out[:, 0] + 1j * out[:, 1], vals) < TOL
    # XLA-gather cross-check through the same plan tables
    import jax
    vil = plan._coerce_values(vals)
    xla = np.asarray(jax.jit(
        lambda v, t: plan._backward_impl(v, t, pallas=False))(
            vil, plan._tables))
    np.testing.assert_allclose(space, xla, atol=1e-5, rtol=1e-5)


def test_split_x_window():
    """Occupied-x-window xy stage (plan._split_x) on real hardware,
    wrapped window included (centered x in [-3, 3])."""
    n = 64
    tr = spherical_cutoff_triplets(n)
    tr = tr[np.abs(tr[:, 0]) <= 3]
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    assert plan._split_x is not None
    _check_c2c(plan, tr, n, seed=4)


def test_pair_io_boundary(monkeypatch):
    """The planar (2, N) value boundary (default only >= 16M values) on
    a small plan: layout flip must be observable and exact."""
    monkeypatch.setattr(plan_mod, "PAIR_IO_THRESHOLD", 1)
    n = 32
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    assert plan.pair_values_io
    vals = _values(len(tr), 5)
    out = plan.forward(plan.backward(vals), Scaling.FULL)
    assert out.shape == (2, len(tr))
    assert _rel(np.asarray(out)[0] + 1j * np.asarray(out)[1], vals) < TOL


def test_two_stage_long_axis():
    """768 = 24*32 z-axis through the two-stage Cooley-Tukey matmul
    path on real hardware."""
    nx, ny, nz = 16, 16, 768
    rng = np.random.default_rng(6)
    tr = np.stack([rng.integers(0, nx, 3000), rng.integers(0, ny, 3000),
                   rng.integers(0, nz, 3000)], axis=-1)
    tr = np.unique(tr, axis=0)
    plan = make_local_plan(TransformType.C2C, nx, ny, nz, tr,
                           precision="single")
    assert plan._use_mdft
    vals = _values(len(tr), 6)
    space = np.asarray(plan.backward(vals))
    got = space[..., 0] + 1j * space[..., 1]
    oracle = _dense_c2c_oracle(tr, vals, (nx, ny, nz))
    assert _rel(got, oracle) < TOL


def test_repeated_backward_is_stable():
    """Back-to-back backward executions must agree bit-for-bit (the
    reference's repeated-transform zeroing check, benchmark.cpp) —
    catches stale-buffer reuse on the device."""
    n = 32
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    vals = _values(len(tr), 7)
    a = np.asarray(plan.backward(vals))
    b = np.asarray(plan.backward(vals))
    np.testing.assert_array_equal(a, b)


def test_iterate_pointwise_fused_scan():
    """lax.scan-fused round trips == sequential apply_pointwise."""
    n = 32
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    vals = _values(len(tr), 8)
    it = np.asarray(plan.iterate_pointwise(vals, None, steps=2,
                                           scaling=Scaling.FULL))
    one = plan.apply_pointwise(vals, scaling=Scaling.FULL)
    two = np.asarray(plan.apply_pointwise(one, scaling=Scaling.FULL))
    np.testing.assert_allclose(it, two, atol=1e-6, rtol=1e-5)


def test_on_device_double():
    """The double-single (hi, lo) + exact-sliced-dot double mode on the
    real MXU (ops/dsdft.py): partial-dot exactness and TwoSum behavior
    are hardware properties the CPU run cannot certify. Round-5
    measured: 2.0e-14 (64^3) / 5.0e-14 (128^3) backward rel l2 vs the
    dense f64 oracle."""
    n = 32
    rng = np.random.default_rng(11)
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="double")
    assert plan._ds, "on-device double must engage on the TPU backend"
    vals = (rng.standard_normal(len(tr))
            + 1j * rng.standard_normal(len(tr)))
    space = plan.backward(vals)
    assert space.dtype == np.float64
    got = space[..., 0] + 1j * space[..., 1]
    st = np.where(tr < 0, tr + n, tr)
    cube = np.zeros((n, n, n), np.complex128)
    cube[st[:, 2], st[:, 1], st[:, 0]] = vals
    oracle = np.fft.ifftn(cube) * cube.size
    rel = np.linalg.norm(got - oracle) / np.linalg.norm(oracle)
    assert rel < 2e-12, rel   # contract envelope 2e-11; measured 1e-14
    out = plan.forward(space, Scaling.FULL)
    gv = out[:, 0] + 1j * out[:, 1]
    rel = np.linalg.norm(gv - vals) / np.linalg.norm(vals)
    assert rel < 2e-12, rel


def test_on_device_double_r2c():
    """R2C on-device double on the real MXU: half-spectrum real
    matrices through the same exact-sliced machinery, zero-stick and
    x=0-plane completions on double-single channels."""
    n = 16
    rng = np.random.default_rng(12)
    field = rng.standard_normal((n, n, n))
    freq = np.fft.fftn(field)
    tr = np.asarray([(x, y, z) for x in range(n // 2 + 1)
                     for y in range(n) for z in range(n)
                     if not (x == 0 and y == 0 and z > n // 2)],
                    np.int64)
    vals = freq[tr[:, 2], tr[:, 1], tr[:, 0]]
    plan = make_local_plan(TransformType.R2C, n, n, n, tr,
                           precision="double")
    assert plan._ds
    space = plan.backward(vals)
    assert space.dtype == np.float64
    rel = (np.linalg.norm(space - field * field.size)
           / np.linalg.norm(field * field.size))
    assert rel < 2e-12, rel
    out = plan.forward(space, Scaling.FULL)
    gv = out[:, 0] + 1j * out[:, 1]
    rel = np.linalg.norm(gv - vals) / np.linalg.norm(vals)
    assert rel < 2e-12, rel


def test_fused_stage_matches_xla(monkeypatch):
    """Fused Pallas DFT-stage kernels (real Mosaic codegen, in-VMEM
    transpose, HIGHEST-precision dots) vs the SPFFT_TPU_FUSED_STAGE=0
    XLA pipeline: same plan, same values. The two paths differ only in
    rounding order, so agreement is ~1e-7-class."""
    n = 64
    tr = spherical_cutoff_triplets(n)
    vals = _values(len(tr), 6)
    # dft_kernel.enabled() reads the env at TRACE time and plans trace
    # lazily at first execution — so each plan must EXECUTE while its
    # intended setting is live, or both trace the same path.
    import jax

    def hlo(plan):
        # lowered under the CURRENT env — the engagement proof below
        vil = plan._coerce_values(vals)
        return jax.jit(plan._backward_impl).lower(
            vil, plan._tables_hot).as_text()

    plan_f = make_local_plan(TransformType.C2C, n, n, n, tr,
                             precision="single")
    a = np.asarray(plan_f.backward(vals))
    fa = np.asarray(plan_f.forward(a, Scaling.FULL))
    hlo_f = hlo(plan_f)
    monkeypatch.setenv("SPFFT_TPU_FUSED_STAGE", "0")
    plan_x = make_local_plan(TransformType.C2C, n, n, n, tr,
                             precision="single")
    b = np.asarray(plan_x.backward(vals))
    fb = np.asarray(plan_x.forward(b, Scaling.FULL))
    hlo_x = hlo(plan_x)
    monkeypatch.delenv("SPFFT_TPU_FUSED_STAGE")
    # prove the A/B engaged: the fused plan lowers to Pallas custom
    # calls, the env=0 plan to plain dots (at 64^3 the two paths agree
    # BIT-FOR-BIT — same 6-pass dot algorithm either way — so result
    # inequality cannot serve as the engagement check)
    assert "tpu_custom_call" in hlo_f
    assert "tpu_custom_call" not in hlo_x
    assert _rel(a, b) < 5e-6
    assert _rel(fa, fb) < 5e-6


def test_batched_vmap_over_fused_kernels():
    """backward_batched/forward_batched vmap the pipeline over a batch
    axis; with the fused DFT-stage kernels active this exercises JAX's
    Pallas batching rule on real hardware (the CPU suite falls back to
    the XLA stages before reaching it). Each batch element must match
    the unbatched call exactly — same program modulo the vmap dimension."""
    n = 64
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    vals = [_values(len(tr), seed) for seed in (11, 12, 13)]
    batch = np.stack([np.asarray(plan._coerce_values(v)) for v in vals])
    out_b = np.asarray(plan.backward_batched(batch))
    for k, v in enumerate(vals):
        single = np.asarray(plan.backward(v))
        assert _rel(out_b[k], single) < 1e-6
    fwd_b = np.asarray(plan.forward_batched(out_b, Scaling.FULL))
    for k, v in enumerate(vals):
        got = fwd_b[k]
        assert _rel(got[:, 0] + 1j * got[:, 1], v) < TOL


def test_stage_kernel_compile_envelope():
    """The kernel tile chooser must pick configs that actually COMPILE
    on this chip: the formula-vs-Mosaic gap crashed 320^3/384^3 plans
    when the budget allowed 7-8 MB tiles (envelope regression, fixed by
    the 5.5 MB empirical ceiling). Compiles one stage at each larger
    axis class and the complex xy dispatcher on device."""
    import jax
    import jax.numpy as jnp
    from spfft_tpu.ops import dft, dft_kernel as dk

    rng = np.random.default_rng(30)
    for n in (384, 512):
        mats = dft.c2c_mats(n, dft.BACKWARD)
        xr = jnp.asarray(rng.standard_normal((1536, n)), jnp.float32)
        xi = jnp.asarray(rng.standard_normal((1536, n)), jnp.float32)
        yr, yi = jax.jit(
            lambda a, b, m=mats: dk.pdft_last(a, b, m))(xr, xi)
        got = np.asarray(yr, np.float64) + 1j * np.asarray(yi, np.float64)
        want = np.fft.ifft(np.asarray(xr, np.float64)
                           + 1j * np.asarray(xi, np.float64), axis=-1) * n
        assert _rel(got, want) < 1e-5

    # complex xy dispatcher (the distributed wrappers' fused path) at
    # n=64 (fast correctness) and at n=320 — the LARGEST eligible axis
    # class, where the swap_out variant's extra transposed buffers sit
    # closest to the Mosaic compile ceiling the VMEM formula does not
    # model. Complex cannot cross the host<->device boundary on this
    # backend, so the complex value is formed and split inside the jit.
    for n, p in ((64, 8), (320, 4)):
        xr = jnp.asarray(rng.standard_normal((p, n, n)), jnp.float32)
        xi = jnp.asarray(rng.standard_normal((p, n, n)), jnp.float32)
        m1 = dft.c2c_mats(n, dft.BACKWARD)
        m2 = dft.c2c_mats(n, dft.BACKWARD)

        def run(a, b, m1=m1, m2=m2):
            y = dft.cdft2_xy(a + 1j * b, m1, m2)
            return jnp.real(y), jnp.imag(y)

        gr, gi = jax.jit(run)(xr, xi)
        got = np.asarray(gr, np.float64) + 1j * np.asarray(gi, np.float64)
        want = np.fft.ifft2(np.asarray(xr, np.float64)
                            + 1j * np.asarray(xi, np.float64),
                            axes=(-2, -1)) * (n * n)
        assert _rel(got, want) < 1e-5
        hlo = jax.jit(run).lower(xr, xi).as_text()
        assert "tpu_custom_call" in hlo


def test_multi_transform_on_device():
    """multi_transform on the chip, both execution regimes: three
    clones of one plan (fused vmapped batch over the Pallas kernels —
    the path the CPU suite runs on XLA stages only) and two DISTINCT
    plans (per-transform async dispatch); each result must match the
    plan's own single execution."""
    from spfft_tpu import Transform
    from spfft_tpu.multi import (multi_transform_backward,
                                 multi_transform_forward)
    n = 48
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single")
    base = Transform(plan)
    clones = [base.clone() for _ in range(3)]
    vals = [_values(len(tr), s) for s in (21, 22, 23)]
    outs = multi_transform_backward(clones, vals)
    for o, v in zip(outs, vals):
        assert _rel(np.asarray(o), np.asarray(plan.backward(v))) < 1e-7
    fouts = multi_transform_forward(clones, [np.asarray(o) for o in outs])
    for f, o in zip(fouts, outs):
        want = np.asarray(plan.forward(np.asarray(o)))
        assert _rel(np.asarray(f), want) < 1e-7

    m = 40
    tr2 = spherical_cutoff_triplets(m)
    plan_b = make_local_plan(TransformType.C2C, m, m, m, tr2,
                             precision="single")
    pair = [Transform(plan), Transform(plan_b)]
    vals2 = [vals[0], _values(len(tr2), 24)]
    outs2 = multi_transform_backward(pair, vals2)
    assert _rel(np.asarray(outs2[0]),
                np.asarray(plan.backward(vals2[0]))) < 1e-7
    assert _rel(np.asarray(outs2[1]),
                np.asarray(plan_b.backward(vals2[1]))) < 1e-7


def test_prime_axis_direct_on_device():
    """617-point (prime > MATMUL_DFT_MAX) z-axis through the direct
    matmul fallback on real hardware — the round-5 coverage extension
    that keeps prime axes off the conv-lowered jnp.fft TPU path."""
    nx, ny, nz = 8, 8, 617
    rng = np.random.default_rng(31)
    tr = np.unique(np.stack([rng.integers(0, nx, 2000),
                             rng.integers(0, ny, 2000),
                             rng.integers(0, nz, 2000)], -1), axis=0)
    plan = make_local_plan(TransformType.C2C, nx, ny, nz, tr,
                           precision="single")
    assert plan._use_mdft
    vals = _values(len(tr), 32)
    space = np.asarray(plan.backward(vals))
    got = space[..., 0] + 1j * space[..., 1]
    oracle = _dense_c2c_oracle(tr, vals, (nx, ny, nz))
    assert _rel(got, oracle) < TOL
    out = np.asarray(plan.forward(space, Scaling.FULL))
    assert _rel(out[:, 0] + 1j * out[:, 1], vals) < TOL


def test_distributed_delegate_on_device():
    """A comm-size-1 distributed plan on the real chip: the S=1 mesh
    delegates to the local pipeline (reference grid_internal.cpp:182
    semantics), so the delegate glue — per-shard value slicing, plane
    accounting, the distributed API surface — runs over the fused
    kernels on hardware. CPU suites cover S>1 on the virtual mesh."""
    from spfft_tpu import make_distributed_plan

    n = 48
    tr = spherical_cutoff_triplets(n)
    plan = make_distributed_plan(TransformType.C2C, n, n, n, [tr], [n])
    vals = _values(len(tr), 41)
    space = np.asarray(plan.backward([vals])[0])
    got = space[..., 0] + 1j * space[..., 1]
    oracle = _dense_c2c_oracle(tr, vals, (n, n, n))
    assert _rel(got, oracle) < TOL
    out = np.asarray(plan.forward([space], Scaling.FULL)[0])
    assert _rel(out[:, 0] + 1j * out[:, 1], vals) < TOL


def test_serve_smoke_on_tpu():
    """The serving layer's deterministic pinning smoke ON THE CHIP: the
    adaptive exact-shape path (pinned batched executables, staged host
    buffers, zero pad rows) exercises real Mosaic/XLA:TPU executables
    here — the CPU tier-1 smoke covers the same logic but not the
    hardware dispatch. Also records a small on-chip serve trace so the
    TPU-regime serving numbers the ROADMAP calls for land in the CI log
    (window/max-batch retuning reads them from there)."""
    from spfft_tpu.serve.bench import main as serve_bench_main

    assert serve_bench_main(["--smoke"]) == 0
    # one small measured trace (printed JSON line lands in the CI log)
    assert serve_bench_main(["--dim", "24", "--requests", "64",
                             "--signatures", "2", "--threads", "4",
                             "--high-fraction", "0.25"]) == 0


def test_serve_fault_smoke_on_tpu():
    """The failure-semantics smoke ON THE CHIP: scripted faults drive
    bucket isolation, bounded retry, device quarantine/probation over
    the REAL device pool and the crash-proof dispatch supervisor
    against real Mosaic/XLA:TPU executables (the CPU tier-1 smoke
    covers the same logic but not hardware dispatch or a multi-chip
    pool). A short injected-fault trace is also measured so degraded
    TPU-regime serving numbers land in the CI log next to the clean
    trace from test_serve_smoke_on_tpu."""
    from spfft_tpu.serve.bench import main as serve_bench_main

    assert serve_bench_main(["--fault-smoke"]) == 0
    assert serve_bench_main(["--dim", "24", "--requests", "64",
                             "--signatures", "2", "--threads", "4",
                             "--fault-rate", "0.05"]) == 0


def test_obs_smoke_on_tpu(tmp_path):
    """Unified telemetry ON THE CHIP: the traced serving smoke must
    produce a Chrome trace covering all eight request stages with zero
    unclosed spans against real Mosaic/XLA:TPU executables (the CPU
    tier-1 smoke covers the same lifecycle logic but not hardware
    dispatch — on TPU the device_execute spans measure real async chip
    work and a multi-chip host exercises per-device tracks), plus
    Prometheus text that round-trips the exposition parser. The traced
    fault smoke then proves the zero-leak contract across bucket
    isolation / quarantine / crash recovery on the real device pool."""
    import json

    from spfft_tpu import obs
    from spfft_tpu.obs.__main__ import (REQUEST_STAGES,
                                        validate_trace_payload)
    from spfft_tpu.serve.bench import main as serve_bench_main

    trace_file = tmp_path / "tpu_trace.json"
    prom_file = tmp_path / "tpu_metrics.prom"
    try:
        assert serve_bench_main(["--smoke",
                                 "--trace-out", str(trace_file),
                                 "--prom-out", str(prom_file)]) == 0
        payload = json.loads(trace_file.read_text())
        assert validate_trace_payload(
            payload, require_names=REQUEST_STAGES) == []
        series = obs.parse_prometheus_text(prom_file.read_text())
        assert series[("spfft_trace_spans_open", ())] == 0
        assert serve_bench_main(
            ["--fault-smoke",
             "--trace-out", str(tmp_path / "tpu_fault_trace.json")]) == 0
    finally:
        obs.disable()
        obs.GLOBAL_TRACER.reset()


def test_overlap_exchange_on_tpu():
    """Compute/communication overlap ON REAL CHIPS (multi-chip hosts
    only — the chunked exchange needs a real mesh): overlap_chunks=K
    output must match the monolithic plan (rel <= 1e-6; the matmul-DFT
    z-stage may re-tile per chunk width, so bitwise equality is not
    guaranteed on TPU the way it is on the CPU suite), the compiled
    module must show the collective start/done split the chunk loop
    exists to enable (utils.hlo_inspect.collective_async_split), and a
    measured same-session A/B trace (monolithic vs K in {2,4}) lands in
    the CI log for BENCHMARKS.md's distributed-perf trajectory."""
    import json
    import time

    import jax

    from spfft_tpu import ExchangeType, make_distributed_plan
    from spfft_tpu.parallel import make_mesh
    from spfft_tpu.utils.hlo_inspect import (collective_async_split,
                                             count_collectives)
    from spfft_tpu.utils.workloads import (even_plane_split,
                                           round_robin_stick_partition)

    S = min(len(jax.devices()), 8)
    if S < 2:
        pytest.skip("overlap exchange A/B needs >= 2 TPU devices; "
                    f"this host exposes {len(jax.devices())}")
    n = 64
    tr = spherical_cutoff_triplets(n)
    parts = round_robin_stick_partition(tr, (n, n, n), S)
    planes = even_plane_split(n, S)
    mesh = make_mesh(S)
    rng = np.random.default_rng(0)
    vals = [(rng.uniform(-1, 1, len(p))
             + 1j * rng.uniform(-1, 1, len(p))).astype(np.complex64)
            for p in parts]
    rows = []
    ref_space = None
    for exchange in (ExchangeType.DEFAULT, ExchangeType.COMPACT_BUFFERED):
        for k in (1, 2, 4):
            plan = make_distributed_plan(
                TransformType.C2C, n, n, n, parts, planes, mesh=mesh,
                exchange=exchange, overlap_chunks=k)
            space = plan.backward(vals)
            got = np.asarray(space)
            if ref_space is None:
                ref_space = got
            else:  # bit-exact-or-1e-6 contract vs the monolithic result
                assert _rel(got[..., 0] + 1j * got[..., 1],
                            ref_space[..., 0] + 1j * ref_space[..., 1]) \
                    < TOL
            v = plan.shard_values(vals)
            lowered = plan._backward_jit.lower(v, *plan._device_tables)
            launches = sum(count_collectives(lowered.as_text()).values())
            split = collective_async_split(lowered.compile().as_text())
            if k > 1:
                assert launches >= k  # one collective per chunk
                # the latency-hiding scheduler split them: overlap is
                # structurally possible on this backend
                assert split["starts"] >= k
            # measured same-session A/B (pair wall-clock)
            out = plan.apply_pointwise(vals)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(10):
                out = plan.apply_pointwise(vals)
            jax.block_until_ready(out)
            rows.append({"exchange": exchange.value, "k": k,
                         "pair_ms": round(
                             (time.perf_counter() - t0) / 10 * 1e3, 3),
                         "collectives": launches,
                         "async_starts": split["starts"]})
    print("OVERLAP_AB " + json.dumps({"shards": S, "dim": n,
                                      "rows": rows}))


def test_symmetry_exchange_on_tpu():
    """Hermitian wire trimming ON REAL CHIPS, next to the overlap A/B:
    a folded full-sphere R2C plan must ship exactly the half-spectrum
    plan's bytes (table-derived accounting, conserved at every
    overlap_chunks=K), reproduce its backward grid on the real exchange
    (rel <= 1e-6 on chip; the CPU suite asserts bitwise), and land at
    <= 55% of the untrimmed C2C wire — the ISSUE r06 halving, measured
    where the bytes actually cross ICI links."""
    import json
    import jax

    from spfft_tpu import ExchangeType, make_distributed_plan
    from spfft_tpu.parallel import make_mesh
    from spfft_tpu.utils.workloads import (even_plane_split,
                                           round_robin_stick_partition)

    S = min(len(jax.devices()), 8)
    if S < 2:
        pytest.skip("symmetry exchange A/B needs >= 2 TPU devices; "
                    f"this host exposes {len(jax.devices())}")
    n = 64
    dims = (n, n, n)
    full = spherical_cutoff_triplets(n)
    x, y, z = full[:, 0], full[:, 1], full[:, 2]
    half = full[(x > 0) | ((x == 0) & ((y > 0)
                                       | ((y == 0) & (z >= 0))))]
    half_parts = round_robin_stick_partition(half, dims, S)
    # mirrors ride WITH their fold-target stick's shard
    full_parts = [np.concatenate([p, -p[p[:, 0] > 0]])
                  for p in half_parts]
    planes = even_plane_split(n, S)
    mesh = make_mesh(S)
    rng = np.random.default_rng(7)
    half_vals = [(rng.uniform(-1, 1, len(p))
                  + 1j * rng.uniform(-1, 1, len(p))).astype(np.complex64)
                 for p in half_parts]
    full_vals = [np.concatenate([v, np.conj(v[p[:, 0] > 0])])
                 for v, p in zip(half_vals, half_parts)]

    def build(ttype, parts, k):
        return make_distributed_plan(
            ttype, n, n, n, parts, planes, mesh=mesh,
            exchange=ExchangeType.COMPACT_BUFFERED, overlap_chunks=k)

    wires = []
    for k in (1, 2, 4):
        fp = build(TransformType.R2C, full_parts, k)
        hp = build(TransformType.R2C, half_parts, k)
        assert fp.exchange_wire_bytes() == hp.exchange_wire_bytes()
        wires.append(fp.exchange_wire_bytes())
        got = np.asarray(fp.backward(full_vals))
        ref = np.asarray(hp.backward(half_vals))
        assert _rel(got[..., 0] + 1j * got[..., 1],
                    ref[..., 0] + 1j * ref[..., 1]) < TOL
    assert wires[0] == wires[1] == wires[2]  # conserved across chunking
    # untrimmed baseline: the same sphere as C2C, storage coordinates
    # (the C2C centered bounds reject the hermitian-only -n/2 mirror)
    c2c = build(TransformType.C2C,
                [p % np.array(dims, np.int64) for p in full_parts], 1)
    ratio = wires[0] / c2c.exchange_wire_bytes()
    assert ratio <= 0.55, f"wire ratio {ratio:.3f} > 0.55"
    print("SYMMETRY_AB " + json.dumps({
        "shards": S, "dim": n, "r2c_wire_bytes": int(wires[0]),
        "c2c_wire_bytes": int(c2c.exchange_wire_bytes()),
        "ratio": round(ratio, 4)}))


def test_wire_precision_on_tpu():
    """The compressed exchange wire ON REAL CHIPS, next to the
    symmetry A/B: an int8-rung C2C plan must resolve its declared rung
    (budget honored by the build-time probe), ship <= 30% of the f32
    rung's wire bytes INCLUDING the per-stick scale sidecar (the ISSUE
    r06 acceptance, measured where the bytes actually cross ICI links),
    conserve that accounting at every overlap_chunks=K, and land its
    real-collective backward within the declared l2 budget of the
    rung-0 twin."""
    import json
    import jax

    from spfft_tpu import make_distributed_plan
    from spfft_tpu.parallel import make_mesh
    from spfft_tpu.utils.workloads import (even_plane_split,
                                           round_robin_stick_partition)

    S = min(len(jax.devices()), 8)
    if S < 2:
        pytest.skip("wire precision A/B needs >= 2 TPU devices; "
                    f"this host exposes {len(jax.devices())}")
    n = 64
    tr = spherical_cutoff_triplets(n)
    parts = round_robin_stick_partition(tr, (n, n, n), S)
    planes = even_plane_split(n, S)
    mesh = make_mesh(S)
    rng = np.random.default_rng(0x51F)
    # adversarial per-value dynamic range: the per-stick scales must
    # absorb it, not a global one
    mags = 10.0 ** rng.uniform(-4, 4, size=len(tr))
    off = 0
    vals = []
    for p in parts:
        m = mags[off:off + len(p)]
        off += len(p)
        vals.append(((rng.uniform(-1, 1, len(p))
                      + 1j * rng.uniform(-1, 1, len(p))) * m)
                    .astype(np.complex64))
    budget = 0.01

    def build(rung, k):
        return make_distributed_plan(
            TransformType.C2C, n, n, n, parts, planes, mesh=mesh,
            precision="single", overlap_chunks=k,
            wire_precision=rung, wire_error_budget=budget)

    wires, errs = [], []
    for k in (1, 2, 4):
        ip = build(3, k)
        fp = build(1, k)
        assert ip.wire_rung_name == "int8", ip.wire_declines
        assert ip.wire_probe_error <= budget
        wires.append(ip.exchange_wire_bytes())
        got = np.asarray(ip.backward(vals))
        ref = np.asarray(fp.backward(vals))
        err = _rel(got[..., 0] + 1j * got[..., 1],
                   ref[..., 0] + 1j * ref[..., 1])
        assert err <= budget, f"k={k}: int8 wire err {err:.2e} > budget"
        errs.append(err)
    assert wires[0] == wires[1] == wires[2]  # conserved across chunking
    f32_wire = build(1, 1).exchange_wire_bytes()
    ratio = wires[0] / f32_wire
    assert ratio <= 0.30, f"int8 wire ratio {ratio:.3f} > 0.30"
    print("WIRE_AB " + json.dumps({
        "shards": S, "dim": n, "int8_wire_bytes": int(wires[0]),
        "f32_wire_bytes": int(f32_wire), "ratio": round(ratio, 4),
        "budget": budget, "rel_l2": [round(float(e), 6) for e in errs]}))


def test_control_retune_on_tpu(tmp_path):
    """The round-11 closed loop on the real chip: the deterministic
    control smoke (scripted queue buildup -> recorded, bounds-clamped
    batch_window decision; SLO watchdog clean on the healthy trace)
    plus a measured replay with the live controller on — on-chip
    queue-wait vs device-execute ratios differ from the CPU lane, so
    this is where the controller's rules meet real dispatch latencies.
    Record the printed decisions when retuning defaults per the
    ROADMAP's on-chip backlog."""
    import json as _json

    from spfft_tpu.serve.bench import main as serve_bench_main

    assert serve_bench_main([
        "--smoke", "--control",
        "--trace-out", str(tmp_path / "control_tpu_trace.json"),
        "--prom-out", str(tmp_path / "control_tpu.prom")]) == 0
    prom = (tmp_path / "control_tpu.prom").read_text()
    assert "spfft_control_decisions_total" in prom
    assert "spfft_slo_burn_rate" in prom
    out = tmp_path / "control_tpu_replay.json"
    assert serve_bench_main([
        "--dim", "24", "--requests", "96", "--signatures", "3",
        "--threads", "4", "--control",
        "--slo", "p99_ms=60000,error_rate=0.1,max_quarantines=0",
        "-o", str(out)]) == 0
    payload = _json.loads(out.read_text())
    assert payload["failed_requests"] == 0
    assert payload["slo"]["violations"] == []
    from spfft_tpu.control import ServeConfig
    for knob, value in payload["control"]["knobs"].items():
        lo, hi = ServeConfig.bounds(knob)
        assert lo <= value <= hi


def test_fused_compression_dft_on_tpu(tmp_path, monkeypatch):
    """The fused compression+z-DFT kernels (ops/fused_kernel.py) on
    real Mosaic: both directions must pass the gate at 128^3 (dim_z a
    multiple of 128, under the axis cap), stay bit-exact vs the dense
    oracle AND the unfused two-kernel plan, and the --profile-dir
    device capture must no longer contain the dense stick-array
    intermediate the fusion exists to remove (the tier-1 twin asserts
    the same on lowered HLO; here it is checked against the real device
    profile). Record pair timings printed as FUSED_AB when retuning
    BENCHMARKS.md "Round-12" with chip numbers."""
    import glob
    import json
    import time

    import jax

    n = 128
    tr = spherical_cutoff_triplets(n)
    plan = make_local_plan(TransformType.C2C, n, n, n, tr,
                           precision="single", use_pallas=True)
    assert plan.pallas_active
    assert plan.fused_active, plan.fused_fallback_reasons
    assert plan.fused_fallback_reasons == {}
    space = _check_c2c(plan, tr, n, seed=11)  # dense-oracle bit-exact

    # A/B twin: same workload, fused path off -> the two-kernel plan
    monkeypatch.setenv("SPFFT_TPU_FUSED_COMPRESS", "0")
    plan_off = make_local_plan(TransformType.C2C, n, n, n, tr,
                               precision="single", use_pallas=True)
    assert not plan_off.fused_active
    vals = _values(len(tr), 11)
    np.testing.assert_allclose(space, np.asarray(plan_off.backward(vals)),
                               rtol=2e-6, atol=2e-6)

    def timed(p, v):
        out = p.backward(v)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(10):
            out = p.backward(v)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / 10

    ab = {"fused_s": timed(plan, vals), "unfused_s": timed(plan_off, vals)}
    print("FUSED_AB " + json.dumps(ab))

    # profile evidence: the unfused path materialises the dense gather
    # output (num_tiles, 8, 128) between the kernels; the fused capture
    # must not mention that buffer anywhere in the device profile
    dec = plan._pallas["dec"]
    n_tiles = (dec.num_super * dec.p_tiles
               if isinstance(dec, gk.WideGatherTables) else dec.num_tiles)
    token = ("%dx8x128" % n_tiles).encode()

    def capture(p, sub):
        d = tmp_path / sub
        jax.profiler.start_trace(str(d))
        jax.block_until_ready(p.backward(vals))
        jax.profiler.stop_trace()
        blob = b""
        for f in glob.glob(str(d / "**" / "*"), recursive=True):
            try:
                with open(f, "rb") as fh:
                    blob += fh.read()
            except (IsADirectoryError, OSError):
                pass
        return blob

    unfused_blob = capture(plan_off, "unfused")
    fused_blob = capture(plan, "fused")
    assert len(fused_blob) > 0
    if token in unfused_blob:  # the capture format names buffer shapes
        assert token not in fused_blob, \
            "dense stick intermediate still present in the fused profile"
    else:
        # profile format carries no shape strings on this runtime: the
        # HLO-level assertion is the backstop (tier-1 twin + here)
        text = jax.jit(
            lambda v: plan._backward_impl(v, plan._tables_hot)).lower(
                plan._coerce_values(vals)).as_text()
        assert ("%dx8x128xf32" % n_tiles) not in text


def test_plan_store_on_tpu(tmp_path):
    """The round-13 persistent plan-artifact store on the real chip:
    a warm load must (1) resolve with ZERO builds and restore the
    Pallas/fused kernel tables ACTIVE (the table cover build — seconds
    at this size — is the biggest cold-start line item the artifact
    exists to persist), (2) stay bit-exact vs the cold-built plan, and
    (3) first-execute FASTER through the jax.export AOT deserialize
    than a fresh trace+compile of the identical plan. Record the
    printed STORE_AB line into BENCHMARKS.md "Round-13" chip rows."""
    import time

    from spfft_tpu.serve.registry import PlanRegistry
    from spfft_tpu.serve.store import PlanArtifactStore

    n = 128
    tr = spherical_cutoff_triplets(n)
    store = PlanArtifactStore(str(tmp_path / "store"))
    reg = PlanRegistry(store=store)
    t0 = time.perf_counter()
    sig, plan = reg.get_or_build(TransformType.C2C, n, n, n, tr)
    plan._finalize()            # cold pays the whole table build
    cold_s = time.perf_counter() - t0
    store.drain()
    vals = _values(len(tr), 13)
    want = np.asarray(plan.backward(vals))

    # warm boot: fresh registry over the populated store
    reg2 = PlanRegistry(store=PlanArtifactStore(store.root))
    t0 = time.perf_counter()
    sig2, plan2 = reg2.get_or_build(TransformType.C2C, n, n, n, tr)
    load_s = time.perf_counter() - t0
    assert sig2 == sig
    assert reg2.stats()["builds"] == 0
    assert reg2.stats()["store_hits"] == 1
    assert plan2._build_thread is None
    assert plan2.pallas_active == plan.pallas_active
    assert plan2.fused_active == plan.fused_active
    assert plan2._aot is not None and "backward" in plan2._aot
    t0 = time.perf_counter()
    got = np.asarray(plan2.backward(vals))
    aot_first_s = time.perf_counter() - t0
    assert np.array_equal(got, want)    # bit-exact vs the cold build

    # fresh-compile twin: the SAME artifact with the AOT executables
    # stripped — identical restore cost, only trace+compile differs
    loaded = PlanArtifactStore(store.root).load_signature(sig)
    assert loaded is not None
    _, plan3 = loaded
    plan3._aot = None
    t0 = time.perf_counter()
    out3 = np.asarray(plan3.backward(vals))
    fresh_first_s = time.perf_counter() - t0
    assert np.array_equal(out3, want)
    print(f"STORE_AB n={n} cold_resolve={cold_s * 1e3:.1f}ms "
          f"warm_load={load_s * 1e3:.1f}ms "
          f"aot_first_execute={aot_first_s * 1e3:.1f}ms "
          f"fresh_first_execute={fresh_first_s * 1e3:.1f}ms")
    assert load_s < cold_s, "warm load failed to beat the cold build"
    assert aot_first_s < fresh_first_s, \
        "AOT deserialize failed to beat the fresh trace+compile"


def test_fused_overlap_on_tpu(monkeypatch):
    """Fusion x overlap ON REAL CHIPS (multi-chip hosts only): a plan
    with overlap_chunks=K>1 and use_pallas=True must run BOTH fused
    distributed twins (chunk-sliceable decompress+z-DFT backward,
    post-exchange z-DFT+compress forward) while keeping the per-chunk
    collective structure — K collectives split into async start/done
    pairs by the latency-hiding scheduler — and match the monolithic
    UNFUSED oracle (rel <= 1e-6; the Mosaic matmul accumulation order
    differs from the XLA z-stage, so bitwise equality is the CPU
    interpret suite's contract, tests/test_fused_dist.py). The
    measured same-session A/B (unfused-monolithic vs fused xK) prints
    as FUSED_OVERLAP_AB for BENCHMARKS.md's chip trajectory."""
    import json
    import time

    import jax

    from spfft_tpu import ExchangeType, make_distributed_plan
    from spfft_tpu.parallel import make_mesh
    from spfft_tpu.utils.hlo_inspect import (collective_async_split,
                                             count_collectives)
    from spfft_tpu.utils.workloads import (even_plane_split,
                                           round_robin_stick_partition,
                                           sort_triplets_stick_major)

    S = min(len(jax.devices()), 8)
    if S < 2:
        pytest.skip("fused overlap A/B needs >= 2 TPU devices; "
                    f"this host exposes {len(jax.devices())}")
    # the random spherical workload's window-overlap recompute can trip
    # the default forward cost gate at toy densities — widen it with
    # the declared knob (control/config.py fused_recompute_limit)
    monkeypatch.setenv("SPFFT_TPU_FUSED_RECOMPUTE_LIMIT", "16")
    nx = ny = 64
    nz = 128  # dim_z % 128 == 0: the fused eligibility floor
    tr = spherical_cutoff_triplets(nx, radius=nx // 2 - 1)
    tr = np.stack([tr[:, 0], tr[:, 1], tr[:, 2] * 2], axis=1)
    dims = (nx, ny, nz)
    parts = [sort_triplets_stick_major(p, dims)
             for p in round_robin_stick_partition(tr, dims, S)]
    planes = even_plane_split(nz, S)
    mesh = make_mesh(S)
    rng = np.random.default_rng(5)
    vals = [(rng.uniform(-1, 1, len(p))
             + 1j * rng.uniform(-1, 1, len(p))).astype(np.complex64)
            for p in parts]

    def build(use_pallas, k):
        return make_distributed_plan(
            TransformType.C2C, nx, ny, nz, parts, planes, mesh=mesh,
            exchange=ExchangeType.BUFFERED, overlap_chunks=k,
            precision="single", use_pallas=use_pallas)

    ref = build(False, 1)                 # monolithic unfused oracle
    assert not ref.fused_dist_active
    ref_space = np.asarray(ref.backward(vals))
    ref_fwd = np.asarray(ref.forward(ref.backward(vals)))

    def timed_pair(p):
        out = p.apply_pointwise(vals)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(10):
            out = p.apply_pointwise(vals)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / 10 * 1e3, 3)

    rows = [{"fused": False, "k": 1, "pair_ms": timed_pair(ref)}]
    for k in (1, 2, 4):
        plan = build(True, k)
        # the composition this round exists for: fusion AND overlap,
        # both directions, no decline
        assert plan.fused_dist_active, (
            plan.fused_dist_fallback_reason,
            plan.fused_dist_fwd_fallback_reason)
        assert plan.fused_dist_fallback_reason is None
        assert plan.fused_dist_fwd_fallback_reason is None
        # fusion and chunking move no extra bytes over the wire
        assert plan.exchange_wire_bytes() == ref.exchange_wire_bytes()
        space = plan.backward(vals)
        got = np.asarray(space)
        assert _rel(got[..., 0] + 1j * got[..., 1],
                    ref_space[..., 0] + 1j * ref_space[..., 1]) < TOL
        fwd = np.asarray(plan.forward(space))
        assert _rel(fwd[..., 0] + 1j * fwd[..., 1],
                    ref_fwd[..., 0] + 1j * ref_fwd[..., 1]) < TOL
        v = plan.shard_values(vals)
        lowered = plan._backward_jit.lower(v, *plan._device_tables)
        launches = sum(count_collectives(lowered.as_text()).values())
        split = collective_async_split(lowered.compile().as_text())
        if k > 1:
            assert launches >= k  # one collective per fused chunk
            # start/done evidence WITH fusion active: the scheduler can
            # still hide chunk i-1's exchange behind chunk i's launch
            assert split["starts"] >= k
        rows.append({"fused": True, "k": k, "pair_ms": timed_pair(plan),
                     "collectives": launches,
                     "async_starts": split["starts"]})
    print("FUSED_OVERLAP_AB " + json.dumps({"shards": S, "dims": dims,
                                            "rows": rows}))
