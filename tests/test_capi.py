"""C API tests: build libspfft_tpu.so, drive it from C and from ctypes.

The reference exercises its C API through compiled examples and the test
binaries (reference: examples/example.c, tests built on the C++ API); here a
real C program is compiled with g++ and run against the library, and the
same ABI is additionally driven in-process via ctypes for the error-surface
cases.
"""

import ctypes
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "lib", "libspfft_tpu.so")

pytestmark = pytest.mark.skipif(shutil.which("g++") is None,
                                reason="no C++ compiler")


@pytest.fixture(scope="module")
def capi_lib():
    subprocess.run(["make", "-s", "capi"], cwd=REPO, check=True,
                   capture_output=True, text=True)
    assert os.path.exists(LIB)
    return LIB


def test_abi_version_matches_header(capi_lib):
    """spfft_tpu_abi_version() equals the header's SPFFT_TPU_ABI_VERSION
    (the runtime probe old callers use to detect signature skew)."""
    hdr = open(os.path.join(REPO, "include", "spfft_tpu.h")).read()
    import re
    macro = int(re.search(r"#define SPFFT_TPU_ABI_VERSION (\d+)",
                          hdr).group(1))
    lib = ctypes.CDLL(capi_lib)
    lib.spfft_tpu_abi_version.restype = ctypes.c_int
    assert lib.spfft_tpu_abi_version() == macro


def test_c_example_round_trip(capi_lib):
    """Compile and run the shipped C example end-to-end (subprocess: the
    example embeds its own interpreter)."""
    build = os.path.join(REPO, "build")
    os.makedirs(build, exist_ok=True)
    exe = os.path.join(build, "example_c_test")
    subprocess.run(
        ["g++", "-O2", "-I" + os.path.join(REPO, "include"),
         os.path.join(REPO, "examples", "example.c"), "-o", exe,
         "-L" + os.path.join(REPO, "lib"), "-lspfft_tpu",
         "-Wl,-rpath," + os.path.join(REPO, "lib")],
        check=True, capture_output=True, text=True)
    env = dict(os.environ, SPFFT_TPU_PACKAGE_PATH=REPO,
               JAX_PLATFORMS="cpu")
    out = subprocess.run([exe], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


@pytest.fixture(scope="module")
def lib(capi_lib):
    """The C ABI loaded into this process. The embedded-interpreter branch
    is exercised by test_c_example_round_trip; loaded from Python, the shim
    detects the already-running interpreter and shares it."""
    lib = ctypes.CDLL(capi_lib)
    lib.spfft_tpu_error_string.restype = ctypes.c_char_p
    lib.spfft_tpu_init.argtypes = [ctypes.c_char_p]
    lib.spfft_tpu_plan_create.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_longlong, ctypes.c_void_p,
        ctypes.c_int, ctypes.c_int]
    lib.spfft_tpu_plan_destroy.argtypes = [ctypes.c_void_p]
    lib.spfft_tpu_backward.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                       ctypes.c_void_p]
    lib.spfft_tpu_forward.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                      ctypes.c_int, ctypes.c_void_p]
    lib.spfft_tpu_plan_num_values.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong)]
    lib.spfft_tpu_plan_create_distributed.argtypes = [
        ctypes.POINTER(ctypes.c_void_p), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_int,
        ctypes.c_int]
    code = lib.spfft_tpu_init(None)
    assert code == 0
    return lib


def test_ctypes_round_trip(lib):
    n = 4
    trip = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)], np.int32)
    values = np.random.default_rng(0).standard_normal(
        (len(trip), 2)).astype(np.float32)
    space = np.empty((n, n, n, 2), np.float32)
    out = np.empty_like(values)
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create(
        ctypes.byref(plan), 0, n, n, n,
        ctypes.c_longlong(len(trip)), trip.ctypes.data,
        0, -1) == 0
    nv = ctypes.c_longlong()
    assert lib.spfft_tpu_plan_num_values(plan, ctypes.byref(nv)) == 0
    assert nv.value == len(trip)
    assert lib.spfft_tpu_backward(plan, values.ctypes.data,
                                  space.ctypes.data) == 0
    assert lib.spfft_tpu_forward(plan, space.ctypes.data, 1,
                                 out.ctypes.data) == 0
    np.testing.assert_allclose(out, values, atol=1e-5)
    assert lib.spfft_tpu_plan_destroy(plan) == 0


def test_ctypes_execute_pair(lib):
    """The fused pair entry point matches separate backward+forward and
    supports in-place operation."""
    lib.spfft_tpu_execute_pair.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p]
    n = 4
    trip = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)], np.int32)
    values = np.random.default_rng(2).standard_normal(
        (len(trip), 2)).astype(np.float32)
    space = np.empty((n, n, n, 2), np.float32)
    seq = np.empty_like(values)
    fused = np.empty_like(values)
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create(
        ctypes.byref(plan), 0, n, n, n, ctypes.c_longlong(len(trip)),
        trip.ctypes.data, 0, -1) == 0
    assert lib.spfft_tpu_backward(plan, values.ctypes.data,
                                  space.ctypes.data) == 0
    assert lib.spfft_tpu_forward(plan, space.ctypes.data, 1,
                                 seq.ctypes.data) == 0
    assert lib.spfft_tpu_execute_pair(plan, values.ctypes.data, 1,
                                      fused.ctypes.data) == 0
    np.testing.assert_allclose(fused, seq, atol=1e-5)
    # in-place: out == in
    inplace = values.copy()
    assert lib.spfft_tpu_execute_pair(plan, inplace.ctypes.data, 1,
                                      inplace.ctypes.data) == 0
    np.testing.assert_allclose(inplace, seq, atol=1e-5)
    # NONE scaling == N * values
    assert lib.spfft_tpu_execute_pair(plan, values.ctypes.data, 0,
                                      fused.ctypes.data) == 0
    np.testing.assert_allclose(fused, values * len(trip), atol=1e-3)
    # bad scaling -> invalid parameter
    assert lib.spfft_tpu_execute_pair(plan, values.ctypes.data, 7,
                                      fused.ctypes.data) == 5
    assert lib.spfft_tpu_execute_pair(plan, None, 1, None) == 5
    assert lib.spfft_tpu_plan_destroy(plan) == 0


def test_ctypes_execute_pair_distributed(lib):
    """Fused pair on a distributed C plan (concatenated per-shard values)."""
    lib.spfft_tpu_execute_pair.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p]
    n, shards = 8, 4
    trip_all = np.array([[x, y, z] for x in range(n) for y in range(n)
                         for z in range(n)], np.int32)
    order = np.argsort((trip_all[:, 0] * n + trip_all[:, 1]) % shards,
                       kind="stable")
    trip = np.ascontiguousarray(trip_all[order])
    vps = np.array([(((trip_all[:, 0] * n + trip_all[:, 1]) % shards) == r)
                    .sum() for r in range(shards)], np.int64)
    pps = np.full(shards, n // shards, np.int32)
    values = np.random.default_rng(3).standard_normal(
        (len(trip), 2)).astype(np.float32)
    fused = np.empty_like(values)
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create_distributed(
        ctypes.byref(plan), 0, n, n, n, shards, vps.ctypes.data,
        trip.ctypes.data, pps.ctypes.data, 0, 0, -1) == 0
    assert lib.spfft_tpu_execute_pair(plan, values.ctypes.data, 1,
                                      fused.ctypes.data) == 0
    np.testing.assert_allclose(fused, values, atol=1e-5)
    assert lib.spfft_tpu_plan_destroy(plan) == 0


def test_invalid_indices_code(lib):
    trip = np.array([[99, 0, 0]], np.int32)
    plan = ctypes.c_void_p()
    code = lib.spfft_tpu_plan_create(ctypes.byref(plan), 0, 4, 4, 4,
                                     ctypes.c_longlong(1),
                                     trip.ctypes.data, 0, -1)
    assert code == 7  # SPFFT_TPU_INVALID_INDICES_ERROR
    assert b"out of bounds" in lib.spfft_tpu_error_string(code)


def test_overflow_code(lib):
    """Dimension products past the 64-bit size range return the overflow
    code at the ABI (reference: grid_internal.cpp:122-134 ->
    SPFFT_OVERFLOW_ERROR)."""
    trip = np.array([[0, 0, 0]], np.int32)
    plan = ctypes.c_void_p()
    n = 1 << 21
    code = lib.spfft_tpu_plan_create(ctypes.byref(plan), 0, n, n, n,
                                     ctypes.c_longlong(1),
                                     trip.ctypes.data, 0, -1)
    assert code == 3  # SPFFT_TPU_OVERFLOW_ERROR
    assert lib.spfft_tpu_error_string(code)


def test_invalid_handle_code(lib):
    assert lib.spfft_tpu_plan_destroy(ctypes.c_void_p(12345)) == 2


def test_null_arguments(lib):
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create(None, 0, 4, 4, 4,
                                     ctypes.c_longlong(0), None, 0, -1) == 5
    trip = np.zeros((1, 3), np.int32)
    assert lib.spfft_tpu_plan_create(ctypes.byref(plan), 0, 4, 4, 4,
                                     ctypes.c_longlong(1),
                                     trip.ctypes.data, 0, -1) == 0
    assert lib.spfft_tpu_backward(plan, None, None) == 5
    assert lib.spfft_tpu_plan_destroy(plan) == 0


def test_error_strings(lib):
    assert lib.spfft_tpu_error_string(0) == b"success"
    assert b"unrecognised" in lib.spfft_tpu_error_string(9999)


def test_ctypes_distributed_round_trip(lib):
    """Distributed C plan over the forced 8-device CPU mesh: concatenated
    per-shard values <-> full cube, against the local-plan result."""
    lib.spfft_tpu_plan_num_shards.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
    n, shards = 8, 4
    # split sticks round-robin: shard r gets sticks with (x*n+y) % shards == r
    trip_all = np.array([[x, y, z] for x in range(n) for y in range(n)
                         for z in range(n)], np.int32)
    order = np.argsort((trip_all[:, 0] * n + trip_all[:, 1]) % shards,
                       kind="stable")
    trip = np.ascontiguousarray(trip_all[order])
    vps = np.array([(((trip_all[:, 0] * n + trip_all[:, 1]) % shards) == r)
                    .sum() for r in range(shards)], np.int64)
    pps = np.full(shards, n // shards, np.int32)
    values = np.random.default_rng(1).standard_normal(
        (len(trip), 2)).astype(np.float32)
    space = np.empty((n, n, n, 2), np.float32)
    out = np.empty_like(values)
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create_distributed(
        ctypes.byref(plan), 0, n, n, n, shards, vps.ctypes.data,
        trip.ctypes.data, pps.ctypes.data, 0, 0, -1) == 0
    ns = ctypes.c_int()
    assert lib.spfft_tpu_plan_num_shards(plan, ctypes.byref(ns)) == 0
    assert ns.value == shards
    assert lib.spfft_tpu_backward(plan, values.ctypes.data,
                                  space.ctypes.data) == 0
    # oracle: the same transform through a local plan
    lplan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create(
        ctypes.byref(lplan), 0, n, n, n, ctypes.c_longlong(len(trip)),
        trip.ctypes.data, 0, -1) == 0
    lspace = np.empty((n, n, n, 2), np.float32)
    assert lib.spfft_tpu_backward(lplan, values.ctypes.data,
                                  lspace.ctypes.data) == 0
    np.testing.assert_allclose(space, lspace, atol=1e-4)
    assert lib.spfft_tpu_forward(plan, space.ctypes.data, 1,
                                 out.ctypes.data) == 0
    np.testing.assert_allclose(out, values, atol=1e-5)
    assert lib.spfft_tpu_plan_destroy(plan) == 0
    assert lib.spfft_tpu_plan_destroy(lplan) == 0


def test_distributed_too_many_shards_code(lib):
    """Requesting more shards than devices surfaces as a clean error code
    (InvalidParameterError -> 5), not a crash."""
    shards = 64  # more than the 8 virtual devices
    trip = np.array([[0, 0, 0]], np.int32)
    vps = np.zeros(shards, np.int64)
    vps[0] = 1
    pps = np.zeros(shards, np.int32)
    pps[0] = 4
    plan = ctypes.c_void_p()
    code = lib.spfft_tpu_plan_create_distributed(
        ctypes.byref(plan), 0, 4, 4, 4, shards, vps.ctypes.data,
        trip.ctypes.data, pps.ctypes.data, 0, 0, -1)
    assert code == 5


def test_ctypes_pair_layout_plan(lib, monkeypatch):
    """C ABI buffers stay interleaved rows even when the plan internally
    uses the planar-pair (2, N) boundary (regression: forward once wrote
    the transposed layout straight into the caller's buffer)."""
    from spfft_tpu import plan as plan_mod
    monkeypatch.setattr(plan_mod, "PAIR_IO_THRESHOLD", 1)
    lib.spfft_tpu_execute_pair.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p]
    n = 4
    trip = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)], np.int32)
    values = np.random.default_rng(5).standard_normal(
        (len(trip), 2)).astype(np.float32)
    space = np.empty((n, n, n, 2), np.float32)
    out = np.empty_like(values)
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create(
        ctypes.byref(plan), 0, n, n, n, ctypes.c_longlong(len(trip)),
        trip.ctypes.data, 0, -1) == 0
    import spfft_tpu.capi_bridge as bridge
    pid = max(bridge._plans)
    assert bridge._plans[pid].pair_values_io
    assert lib.spfft_tpu_backward(plan, values.ctypes.data,
                                  space.ctypes.data) == 0
    assert lib.spfft_tpu_forward(plan, space.ctypes.data, 1,
                                 out.ctypes.data) == 0
    np.testing.assert_allclose(out, values, atol=1e-5)
    fused = np.empty_like(values)
    assert lib.spfft_tpu_execute_pair(plan, values.ctypes.data, 1,
                                      fused.ctypes.data) == 0
    np.testing.assert_allclose(fused, values, atol=1e-5)
    assert lib.spfft_tpu_plan_destroy(plan) == 0


def test_c_feature_drive(capi_lib):
    """Compiled-C drive of the round-3 parity additions: COMPACT_BUFFERED
    distributed create, the extended getter surface, and a B=3 batched
    multi_backward/forward through one plan handle (subprocess: own
    embedded interpreter and 8-device virtual CPU platform)."""
    build = os.path.join(REPO, "build")
    os.makedirs(build, exist_ok=True)
    exe = os.path.join(build, "capi_feature_test")
    subprocess.run(
        ["g++", "-O2", "-I" + os.path.join(REPO, "include"),
         os.path.join(REPO, "tests", "capi_feature_test.c"), "-o", exe,
         "-L" + os.path.join(REPO, "lib"), "-lspfft_tpu", "-lm",
         "-Wl,-rpath," + os.path.join(REPO, "lib")],
        check=True, capture_output=True, text=True)
    env = dict(os.environ, SPFFT_TPU_PACKAGE_PATH=REPO,
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run([exe], env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr
    assert "OK" in out.stdout


def test_ctypes_exchange_knob_and_getters(lib):
    """ctypes drive of the exchange selector + per-shard getters."""
    lib.spfft_tpu_plan_exchange_type.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
    lib.spfft_tpu_plan_local_z_offset.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_int)]
    lib.spfft_tpu_plan_num_local_elements.argtypes = [
        ctypes.c_void_p, ctypes.c_int, ctypes.POINTER(ctypes.c_longlong)]
    lib.spfft_tpu_plan_pallas_active.argtypes = [
        ctypes.c_void_p, ctypes.POINTER(ctypes.c_int)]
    n, shards = 8, 4
    trip_all = np.array([[x, y, z] for x in range(n) for y in range(n)
                         for z in range(n)], np.int32)
    order = np.argsort((trip_all[:, 0] * n + trip_all[:, 1]) % shards,
                       kind="stable")
    trip = np.ascontiguousarray(trip_all[order])
    vps = np.array([(((trip_all[:, 0] * n + trip_all[:, 1]) % shards) == r)
                    .sum() for r in range(shards)], np.int64)
    pps = np.full(shards, n // shards, np.int32)
    plan = ctypes.c_void_p()
    # UNBUFFERED (ring) exchange via the C knob
    assert lib.spfft_tpu_plan_create_distributed(
        ctypes.byref(plan), 0, n, n, n, shards, vps.ctypes.data,
        trip.ctypes.data, pps.ctypes.data, 0, 5, -1) == 0
    exch = ctypes.c_int(-1)
    assert lib.spfft_tpu_plan_exchange_type(plan, ctypes.byref(exch)) == 0
    assert exch.value == 5
    off = ctypes.c_int(-1)
    for r in range(shards):
        assert lib.spfft_tpu_plan_local_z_offset(
            plan, r, ctypes.byref(off)) == 0
        assert off.value == r * (n // shards)
    ne = ctypes.c_longlong()
    assert lib.spfft_tpu_plan_num_local_elements(
        plan, 2, ctypes.byref(ne)) == 0
    assert ne.value == vps[2]
    # shard out of range -> invalid parameter
    assert lib.spfft_tpu_plan_local_z_offset(
        plan, shards, ctypes.byref(off)) == 5
    # bad exchange enum -> invalid parameter
    p2 = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create_distributed(
        ctypes.byref(p2), 0, n, n, n, shards, vps.ctypes.data,
        trip.ctypes.data, pps.ctypes.data, 0, 42, -1) == 5
    # forced-off pallas routing reports inactive
    lplan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create(
        ctypes.byref(lplan), 0, n, n, n, ctypes.c_longlong(len(trip)),
        trip.ctypes.data, 0, 0) == 0
    act = ctypes.c_int(-1)
    assert lib.spfft_tpu_plan_pallas_active(lplan, ctypes.byref(act)) == 0
    assert act.value == 0
    assert lib.spfft_tpu_plan_destroy(plan) == 0
    assert lib.spfft_tpu_plan_destroy(lplan) == 0


def test_ctypes_multi_entries(lib):
    """multi_backward/forward with MIXED plan handles (two distinct local
    plans) — the dispatch-all-then-sync path."""
    lib.spfft_tpu_multi_backward.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.spfft_tpu_multi_forward.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.c_void_p]
    n = 4
    trip = np.array([[x, y, z] for x in range(n) for y in range(n)
                     for z in range(n)], np.int32)
    rng = np.random.default_rng(9)
    p1, p2 = ctypes.c_void_p(), ctypes.c_void_p()
    for p in (p1, p2):
        assert lib.spfft_tpu_plan_create(
            ctypes.byref(p), 0, n, n, n, ctypes.c_longlong(len(trip)),
            trip.ctypes.data, 0, -1) == 0
    vals = [rng.standard_normal((len(trip), 2)).astype(np.float32)
            for _ in range(2)]
    spaces = [np.empty((n, n, n, 2), np.float32) for _ in range(2)]
    outs = [np.empty_like(vals[0]) for _ in range(2)]
    plans_arr = (ctypes.c_void_p * 2)(p1, p2)
    vptr = (ctypes.c_void_p * 2)(*[v.ctypes.data for v in vals])
    sptr = (ctypes.c_void_p * 2)(*[s.ctypes.data for s in spaces])
    optr = (ctypes.c_void_p * 2)(*[o.ctypes.data for o in outs])
    assert lib.spfft_tpu_multi_backward(2, plans_arr, vptr, sptr) == 0
    assert lib.spfft_tpu_multi_forward(2, plans_arr, sptr, 1, optr) == 0
    for v, o in zip(vals, outs):
        np.testing.assert_allclose(o, v, atol=1e-5)
    # null entry -> invalid parameter
    bad = (ctypes.c_void_p * 2)(None, vals[1].ctypes.data)
    assert lib.spfft_tpu_multi_backward(2, plans_arr, bad, sptr) == 5
    assert lib.spfft_tpu_plan_destroy(p1) == 0
    assert lib.spfft_tpu_plan_destroy(p2) == 0


def test_ctypes_multi_distributed_fused(lib):
    """Same-handle DISTRIBUTED multi entries run the fused per-shard-batch
    SPMD program (header contract: one fused device program)."""
    lib.spfft_tpu_multi_backward.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p]
    lib.spfft_tpu_multi_forward.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
        ctypes.c_void_p]
    n, shards, B = 8, 4, 3
    trip_all = np.array([[x, y, z] for x in range(n) for y in range(n)
                         for z in range(n)], np.int32)
    order = np.argsort((trip_all[:, 0] * n + trip_all[:, 1]) % shards,
                       kind="stable")
    trip = np.ascontiguousarray(trip_all[order])
    vps = np.array([(((trip_all[:, 0] * n + trip_all[:, 1]) % shards) == r)
                    .sum() for r in range(shards)], np.int64)
    pps = np.full(shards, n // shards, np.int32)
    plan = ctypes.c_void_p()
    assert lib.spfft_tpu_plan_create_distributed(
        ctypes.byref(plan), 0, n, n, n, shards, vps.ctypes.data,
        trip.ctypes.data, pps.ctypes.data, 0, 0, -1) == 0
    rng = np.random.default_rng(11)
    vals = [rng.standard_normal((len(trip), 2)).astype(np.float32)
            for _ in range(B)]
    spaces = [np.empty((n, n, n, 2), np.float32) for _ in range(B)]
    outs = [np.empty_like(vals[0]) for _ in range(B)]
    plans_arr = (ctypes.c_void_p * B)(plan, plan, plan)
    vptr = (ctypes.c_void_p * B)(*[v.ctypes.data for v in vals])
    sptr = (ctypes.c_void_p * B)(*[s.ctypes.data for s in spaces])
    optr = (ctypes.c_void_p * B)(*[o.ctypes.data for o in outs])
    assert lib.spfft_tpu_multi_backward(B, plans_arr, vptr, sptr) == 0
    assert lib.spfft_tpu_multi_forward(B, plans_arr, sptr, 1, optr) == 0
    for v, o in zip(vals, outs):
        np.testing.assert_allclose(o, v, atol=1e-5)
    # and the batched result matches the single-transform path
    single_space = np.empty((n, n, n, 2), np.float32)
    assert lib.spfft_tpu_backward(plan, vals[1].ctypes.data,
                                  single_space.ctypes.data) == 0
    np.testing.assert_allclose(spaces[1], single_space, atol=1e-5)
    assert lib.spfft_tpu_plan_destroy(plan) == 0
