"""Distributed fused local stages (parallel/dist.py ``_init_fused_dist``
and ``_init_fused_dist_fwd``): the backward's decompress + r2c
(0,0)-stick hermitian completion + z-IFFT as ONE Pallas launch per
overlap chunk, and the forward's post-exchange z-FFT + compress gather
as one launch — A/B'd bit-exact against the monolithic unfused oracle
in interpret mode on the virtual CPU mesh (the same lane as
test_fused_kernel.py's local A/B), across all three overlap exchange
kinds and chunk counts."""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from spfft_tpu import ExchangeType, Scaling, TransformType
from spfft_tpu.parallel import make_distributed_plan, make_mesh
from spfft_tpu.utils.workloads import sort_triplets_stick_major

from test_distributed import split_by_sticks, split_planes
from test_util import dense_forward, hermitian_triplets, sample_cube

DIMS = (8, 6, 128)  # dim_z % 128 == 0: the fused eligibility floor
BATCH = 2

# overlap kind -> (ExchangeType, extra env) per dist.py's selection
KINDS = {
    "block": (ExchangeType.BUFFERED, {}),
    "ragged": (ExchangeType.COMPACT_BUFFERED, {}),
    "compact": (ExchangeType.COMPACT_BUFFERED,
                {"SPFFT_TPU_COMPACT_PPERMUTE": "1"}),
}


@pytest.fixture
def fused_env(monkeypatch):
    """The CPU fused lane: mdft T pipeline forced on (the fused seam
    only exists there), the fused kernels in interpret mode, and the
    forward cost gate widened — the random fuzz workloads at these toy
    dims can trip the default RECOMPUTE_LIMIT (covered separately in
    test_dist_fused_fwd_recompute_gate)."""
    monkeypatch.setenv("SPFFT_TPU_FORCE_MATMUL_DFT", "1")
    monkeypatch.setenv("SPFFT_TPU_FUSED_INTERPRET", "1")
    monkeypatch.setenv("SPFFT_TPU_FUSED_RECOMPUTE_LIMIT", "16")


def _parts_planes(ttype, seed=11):
    rng = np.random.default_rng(seed)
    nx, ny, nz = DIMS
    if ttype is TransformType.R2C:
        trips = hermitian_triplets(rng, DIMS)
    else:
        pts = np.stack([rng.integers(0, nx, 300), rng.integers(0, ny, 300),
                        rng.integers(0, nz, 300)], 1)
        trips = np.unique(pts, axis=0)
    parts = [sort_triplets_stick_major(p, DIMS)
             for p in split_by_sticks(trips, DIMS, [2, 1])]
    return parts, split_planes(nz, [1, 1])


def _build(ttype, parts, planes, fused, **kw):
    old = os.environ.get("SPFFT_TPU_FUSED_COMPRESS")
    os.environ["SPFFT_TPU_FUSED_COMPRESS"] = "1" if fused else "0"
    try:
        return make_distributed_plan(
            ttype, *DIMS, parts, planes, mesh=make_mesh(2),
            precision=kw.pop("precision", "single"),
            use_pallas=kw.pop("use_pallas", True),
            overlap_chunks=kw.pop("overlap_chunks", 1), **kw)
    finally:
        if old is None:
            os.environ.pop("SPFFT_TPU_FUSED_COMPRESS", None)
        else:
            os.environ["SPFFT_TPU_FUSED_COMPRESS"] = old


def _sample_vals(ttype, parts, seed=3):
    rng = np.random.default_rng(seed)
    nz, ny, nx = DIMS[2], DIMS[1], DIMS[0]
    freq = dense_forward(rng.uniform(-1, 1, (nz, ny, nx)))
    return [sample_cube(freq, p, DIMS).astype(np.complex64) for p in parts]


# Monolithic unfused oracle outputs, computed once per transform type
# (every matrix row compares against the SAME reference — bit-exactness
# across K and kinds is transitive through it).
_ORACLE: dict = {}

# Monolithic per-kind wire-byte reference (the kinds move different
# byte counts — ragged/compact trim padding the block exchange ships).
_WIRE: dict = {}


def _kind_wire(ttype, kind, parts, planes):
    if (ttype, kind) not in _WIRE:
        exchange, _ = KINDS[kind]
        ref = _build(ttype, parts, planes, fused=False, exchange=exchange)
        _WIRE[(ttype, kind)] = ref.exchange_wire_bytes()
    return _WIRE[(ttype, kind)]


def _oracle(ttype):
    if ttype not in _ORACLE:
        parts, planes = _parts_planes(ttype)
        vals = _sample_vals(ttype, parts)
        ref = _build(ttype, parts, planes, fused=False)
        assert not ref.fused_dist_active
        space = ref.backward(vals)
        batch = [[(v * (b + 1)).astype(np.complex64) for v in vals]
                 for b in range(BATCH)]
        space_b = ref.backward_batched(ref.shard_values_batch(batch))
        _ORACLE[ttype] = {
            "vals": vals, "batch": batch,
            "space": np.asarray(space),
            "fwd": np.asarray(ref.forward(space)),
            "fwd_full": np.asarray(ref.forward(space, Scaling.FULL)),
            "space_b": np.asarray(space_b),
            "fwd_b": np.asarray(ref.forward_batched(space_b)),
        }
    return _ORACLE[ttype]


# Three representative rows run in the timed tier-1 lane (one per
# overlap kind, K in {1,2}, the r2c-trimmed flagship; the K=1 block row
# also pays the shared oracle build); the remaining 15 rows of the
# exhaustive matrix are marked slow and run in `make ci` (plain
# `pytest tests/`, no marker filter).
_FAST_ROWS = {(1, "block", TransformType.R2C),
              (2, "ragged", TransformType.R2C),
              (2, "compact", TransformType.R2C)}
_MATRIX = [
    pytest.param(chunks, kind, ttype,
                 marks=() if (chunks, kind, ttype) in _FAST_ROWS
                 else pytest.mark.slow)
    for chunks in (1, 2, 4)
    for kind in ("block", "ragged", "compact")
    for ttype in (TransformType.R2C, TransformType.C2C)
]


@pytest.mark.parametrize("chunks,kind,ttype", _MATRIX)
def test_dist_fused_overlap_matrix(fused_env, monkeypatch, kind, chunks,
                                   ttype):
    """The fused x overlap composition, bit-exact vs the monolithic
    unfused oracle: every overlap kind x K in {1,2,4} x {c2c,
    r2c-trimmed} x {single, batched}, with both fused directions active
    and `exchange_wire_bytes()` conserved at every K."""
    exchange, extra = KINDS[kind]
    for k, v in extra.items():
        monkeypatch.setenv(k, v)
    parts, planes = _parts_planes(ttype)
    ora = _oracle(ttype)

    plan = _build(ttype, parts, planes, fused=True, exchange=exchange,
                  overlap_chunks=chunks)
    assert plan.fused_dist_bwd_active, plan.fused_dist_fallback_reason
    assert plan.fused_dist_fwd_active, plan.fused_dist_fwd_fallback_reason
    assert plan.fused_dist_active
    assert plan.fused_dist_fallback_reason is None
    assert plan.fused_dist_fwd_fallback_reason is None
    if chunks > 1:
        assert plan.overlap_chunks == chunks
    # chunking and fusion move no extra bytes over this kind's wire
    assert plan.exchange_wire_bytes() == _kind_wire(ttype, kind, parts,
                                                    planes)

    got_space = plan.backward(ora["vals"])
    np.testing.assert_array_equal(np.asarray(got_space), ora["space"])
    np.testing.assert_array_equal(np.asarray(plan.forward(got_space)),
                                  ora["fwd"])
    got_sb = plan.backward_batched(plan.shard_values_batch(ora["batch"]))
    np.testing.assert_array_equal(np.asarray(got_sb), ora["space_b"])
    np.testing.assert_array_equal(
        np.asarray(plan.forward_batched(got_sb)), ora["fwd_b"])


def test_dist_fused_scaled_forward_bit_exact(fused_env):
    """Scaling.FULL through the fused forward == unfused gather + scale,
    to the bit: the twin keeps UNSCALED DFT matrices and applies the
    same post-gather multiply (folding 1/N into the matrix values would
    not be bit-identical)."""
    parts, planes = _parts_planes(TransformType.R2C)
    ora = _oracle(TransformType.R2C)
    plan = _build(TransformType.R2C, parts, planes, fused=True,
                  overlap_chunks=2)
    assert plan.fused_dist_active, (plan.fused_dist_fallback_reason,
                                    plan.fused_dist_fwd_fallback_reason)
    got = np.asarray(plan.forward(jnp.asarray(ora["space"]), Scaling.FULL))
    np.testing.assert_array_equal(got, ora["fwd_full"])


def test_dist_fused_pair_bit_exact(fused_env):
    """The fused pointwise pair body (which slices both directions'
    ftables past ptables+ctables) routes through both twins."""
    parts, planes = _parts_planes(TransformType.R2C)
    ora = _oracle(TransformType.R2C)
    ref_plan = _build(TransformType.R2C, parts, planes, fused=False)
    plan = _build(TransformType.R2C, parts, planes, fused=True,
                  overlap_chunks=2)
    assert plan.fused_dist_active
    got = np.asarray(plan.apply_pointwise(plan.shard_values(ora["vals"])))
    ref = np.asarray(
        ref_plan.apply_pointwise(ref_plan.shard_values(ora["vals"])))
    np.testing.assert_array_equal(got, ref)


def test_dist_fused_k1_hlo_identical_to_monolithic(fused_env):
    """overlap_chunks=1 lowers the EXACT monolithic program: the chunked
    build's single-chunk case must add no ops in either direction."""
    parts, planes = _parts_planes(TransformType.R2C)
    mono = _build(TransformType.R2C, parts, planes, fused=True)
    k1 = _build(TransformType.R2C, parts, planes, fused=True,
                overlap_chunks=1)
    assert mono.fused_dist_active and k1.fused_dist_active
    vals = mono.shard_values(_sample_vals(TransformType.R2C, parts))
    space = np.asarray(_oracle(TransformType.R2C)["space"])
    assert (mono._backward_jit.lower(vals, *mono._device_tables).as_text()
            == k1._backward_jit.lower(vals, *k1._device_tables).as_text())
    assert (mono._forward_jit[Scaling.NONE].lower(
                space, *mono._device_tables).as_text()
            == k1._forward_jit[Scaling.NONE].lower(
                space, *k1._device_tables).as_text())


def test_dist_fused_overlap_lowers_k_collectives(fused_env):
    """With fusion active the block overlap pipeline still lowers
    exactly K collectives per direction — one per chunk, the structure
    the latency-hiding scheduler splits into async start/done pairs."""
    parts, planes = _parts_planes(TransformType.R2C)
    for chunks in (2, 4):
        plan = _build(TransformType.R2C, parts, planes, fused=True,
                      exchange=ExchangeType.BUFFERED,
                      overlap_chunks=chunks)
        assert plan.fused_dist_active
        vals = plan.shard_values(_sample_vals(TransformType.R2C, parts))
        bwd = plan._backward_jit.lower(
            vals, *plan._device_tables).as_text()
        space = np.asarray(_oracle(TransformType.R2C)["space"])
        fwd = plan._forward_jit[Scaling.NONE].lower(
            space, *plan._device_tables).as_text()
        for text in (bwd, fwd):
            n = text.count("all_to_all") + text.count("collective_permute")
            assert n == chunks, (chunks, n)


def test_dist_fused_overlap_composes(fused_env):
    """The retired gate row: overlap_chunks > 1 no longer declines the
    fused twin — per-chunk table sets keep one launch per chunk, and
    "overlap_chunks" is gone from the reason vocabulary."""
    parts, planes = _parts_planes(TransformType.R2C)
    plan = _build(TransformType.R2C, parts, planes, fused=True,
                  overlap_chunks=2)
    assert plan.fused_dist_active
    assert plan.fused_dist_fallback_reason is None
    assert plan.fused_dist_fwd_fallback_reason is None


def test_dist_fused_fwd_recompute_gate(fused_env, monkeypatch):
    """At the tightest RECOMPUTE_LIMIT this workload's stick-window DFT
    recompute blows the forward cost model: the forward twin declines
    with a recorded reason while the backward stays active, and the
    SPFFT_TPU_FUSED_RECOMPUTE_LIMIT knob lifts it (the fused_env
    fixture's widened gate is what every other test here rides)."""
    monkeypatch.setenv("SPFFT_TPU_FUSED_RECOMPUTE_LIMIT", "1")
    parts, planes = _parts_planes(TransformType.R2C)
    plan = _build(TransformType.R2C, parts, planes, fused=True)
    assert plan.fused_dist_bwd_active
    assert not plan.fused_dist_fwd_active
    assert not plan.fused_dist_active
    assert plan.fused_dist_fwd_fallback_reason == "recompute_blowup"
    monkeypatch.setenv("SPFFT_TPU_FUSED_RECOMPUTE_LIMIT", "16")
    lifted = _build(TransformType.R2C, parts, planes, fused=True)
    assert lifted.fused_dist_active


def test_dist_fused_inactive_reasons(fused_env):
    """By-design inactivity is introspectable (not a counted fallback):
    the properties report a distinct inactive:<why> instead of the old
    indistinguishable None."""
    parts, planes = _parts_planes(TransformType.R2C)
    plan = _build(TransformType.R2C, parts, planes, fused=False)
    assert not plan.fused_dist_active
    assert plan.fused_dist_fallback_reason == "inactive:env_disabled"
    assert plan.fused_dist_fwd_fallback_reason == "inactive:env_disabled"
    plan = _build(TransformType.R2C, parts, planes, fused=True,
                  use_pallas=False)
    assert plan.fused_dist_fallback_reason == "inactive:use_pallas_false"
    plan = _build(TransformType.R2C, parts, planes, fused=True,
                  precision="double", use_pallas=None)
    assert plan.fused_dist_fallback_reason == "inactive:precision"
    assert plan.fused_dist_fwd_fallback_reason == "inactive:precision"
