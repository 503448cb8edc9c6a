"""The benchmark's 4-chip cell at a size a test run holds: the
distributed plan under the sharded closed loop against the slab-wise
reference, on 4 of the suite's 8 virtual CPU devices.

The program passes; the control (the reference one precision step down,
sharded) fails; and so does the program broken underneath the timed
path: one shard's slab left stale, two shards' values swapped, one
field's forward output left out. The slab-wise reference equals the
dense one, the benchmark's stick partition equals the program's, every
operation of the distributed programs carries a ``spfft.*`` stage scope
(the collective ``spfft.exchange``), and the exchange metrics read what
a trace and the program's counters give.
"""

import time

import jax
import numpy as np
import pytest

from benchmark import run, sharding, trace_reduce
from benchmark.references import sparse_fft, sparse_fft_slabs
from benchmark.sticks.cutoff import cutoff_triplets
from benchmark.systems import dist_plan
from benchmark.systems.control import System as ControlSUT
from spfft_tpu import (ExchangeType, Scaling, TransformType, obs,
                       make_distributed_plan)
from spfft_tpu.obs import counters as obs_counters
from spfft_tpu.utils.workloads import round_robin_stick_partition

WORKLOAD = "c2c-768-s0.5-4chip.spair"
N = 32


def small_spec(n=N):
    spec = run.load_spec(WORKLOAD)
    spec["config"] = dict(spec["config"], grid=[n, n, n],
                          planes=[n // 4] * 4)
    return spec


def run_small(sut_factory, seed=2147483659):
    return run.run_cell(small_spec(), seed, 0.3, trace=False,
                        device={"platform": "cpu", "kind": "cpu",
                                "count": 8},
                        peak={}, sut_factory=sut_factory,
                        t_start=time.perf_counter(), log=lambda *a: None)


class StaleSlab(dist_plan.System):
    """Shard 1's slab of every backward is the one of the first call."""

    _first = None

    def backward(self, values):
        out = super().backward(values)
        if self._first is None:
            self._first = out.copy()
        return out.at[1].set(self._first[1])


class SwappedValues(dist_plan.System):
    """Shards 0 and 1 exchange their forward values."""

    def forward(self, space):
        out = super().forward(space)
        return out.at[0].set(out[1]).at[1].set(out[0])


class FieldLeftOut(dist_plan.System):
    """Every second forward (the second field's) returns the output of
    the forward before it instead of its own."""

    _calls, _last = 0, None

    def forward(self, space):
        self._calls += 1
        if self._calls % 2 == 0:
            return self._last.copy()
        self._last = super().forward(space)
        return self._last


def test_program_is_correct():
    res = run_small(None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for c in res["checks"].values():
        assert c["value"] < c["limit"] / 5


@pytest.mark.parametrize("fault", [ControlSUT, StaleSlab, SwappedValues,
                                   FieldLeftOut],
                         ids=["control", "stale_slab", "swapped_values",
                              "field_left_out"])
def test_fault_is_not_correct(fault):
    res = run_small(fault)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > 3 * c["limit"] for c in res["checks"].values())


def _sets():
    rng = np.random.default_rng(7)
    n = 24
    c = np.arange(n)
    c = np.where(c > n // 2, c - n, c)
    X, Y, Z = np.meshgrid(c, c, c, indexing="ij")
    sphere = np.stack([X, Y, Z], -1).reshape(-1, 3)
    sphere = sphere[(sphere ** 2).sum(1) <= (n // 3) ** 2]
    half = sphere[sphere[:, 0] >= 0]
    return {
        "c2c_cutoff": (cutoff_triplets((16, 16, 16), 0.5, False),
                       (16, 16, 16), False),
        "c2c_sphere_shuffled": (rng.permutation(sphere), (n, n, n), False),
        "r2c_cutoff": (cutoff_triplets((16, 12, 20), 0.3, True),
                       (16, 12, 20), True),
        "r2c_half_sphere": (rng.permutation(half), (n, n, n), True),
    }


@pytest.mark.parametrize("name", sorted(_sets()))
def test_slab_reference_equals_dense(name):
    triplets, dims, r2c = _sets()[name]
    rng = np.random.default_rng(11)
    m = len(triplets)
    values = rng.standard_normal(m) + 1j * rng.standard_normal(m)
    dense = sparse_fft.SparseIndex(triplets, dims, r2c)
    slabs = sparse_fft_slabs.SparseIndex(triplets, dims, r2c)
    assert np.array_equal(slabs.ix, dense.ix)
    assert np.array_equal(slabs.iz, dense.iz)
    want = sparse_fft.backward(values, dense)
    got = sparse_fft_slabs.backward(values, slabs)
    assert sparse_fft.rel_l2(got, want) < 1e-12
    nx, ny, nz = dims
    shape = (nz, ny, nx)
    space = rng.standard_normal(shape)
    if not r2c:
        space = space + 1j * rng.standard_normal(shape)
    scale = 1.0 / (nx * ny * nz)
    assert sparse_fft.rel_l2(sparse_fft_slabs.forward(space, slabs, scale),
                             sparse_fft.forward(space, dense, scale)) < 1e-12


@pytest.mark.parametrize("noise", [1e-3, 3e-7])
@pytest.mark.parametrize("name", ["c2c_cutoff", "c2c_sphere_shuffled"])
def test_stages_check_equals_dense(name, noise):
    """The stages check read from one forward transform equals the two
    comparisons made on dense grids, down to the program's own error."""
    triplets, dims, r2c = _sets()[name]
    rng = np.random.default_rng(13)
    m = len(triplets)
    values = rng.standard_normal(m) + 1j * rng.standard_normal(m)
    dense = sparse_fft.SparseIndex(triplets, dims, r2c)
    slabs = sparse_fft_slabs.SparseIndex(triplets, dims, r2c)
    nx, ny, nz = dims
    scale = 1.0 / (nx * ny * nz)
    want = sparse_fft.backward(values, dense)
    shape = want.shape
    space = want + noise * np.abs(want).mean() * (
        rng.standard_normal(shape) + 1j * rng.standard_normal(shape))
    fwd = sparse_fft.forward(space, dense, scale)
    out = fwd + noise * np.abs(fwd).mean() * (
        rng.standard_normal(m) + 1j * rng.standard_normal(m))
    bwd_rel, fwd_rel = sparse_fft_slabs.stages_rel_l2(values, space, out,
                                                      slabs, scale)
    assert bwd_rel == pytest.approx(sparse_fft.rel_l2(space, want),
                                    rel=1e-6)
    assert fwd_rel == pytest.approx(sparse_fft.rel_l2(out, fwd), rel=1e-6)
    # the same values in another order, with the set's row of each
    order = rng.permutation(m)
    assert sparse_fft_slabs.stages_rel_l2(
        values[order], space, out[order], slabs, scale, order) == \
        pytest.approx((bwd_rel, fwd_rel), rel=1e-12)
    # an error of one frequency shows, off the set or at it: an x column
    # no stick lies in, a (y, x) column of an occupied x, a z of a stick
    z, y, x = np.meshgrid(np.arange(nz), np.arange(ny), np.arange(nx),
                          indexing="ij")
    for wave in (x * (nx // 2) / nx, y * (ny // 2) / ny,
                 z * (nz // 2) / nz):
        bad = want + noise * np.abs(want).mean() * np.exp(2j * np.pi * wave)
        got, _ = sparse_fft_slabs.stages_rel_l2(values, bad, fwd, slabs,
                                                scale)
        assert got == pytest.approx(sparse_fft.rel_l2(bad, want), rel=1e-6)


def _old_round_robin(triplets, dims, num_shards):
    """The program's partition as it was written first: one dict lookup
    a value."""
    _, ny, _ = dims
    storage = np.where(triplets < 0,
                       triplets + np.asarray(dims, triplets.dtype), triplets)
    keys = storage[:, 0].astype(np.int64) * ny + storage[:, 1]
    owner = {int(k): i % num_shards
             for i, k in enumerate(np.unique(keys).tolist())}
    owners = np.array([owner[int(k)] for k in keys])
    return [triplets[owners == r] for r in range(num_shards)]


@pytest.mark.parametrize("name", sorted(_sets()))
@pytest.mark.parametrize("shards", [3, 4])
def test_partition_equals_program(name, shards):
    triplets, dims, _ = _sets()[name]
    program = round_robin_stick_partition(triplets, dims, shards)
    for got, want in zip(program, _old_round_robin(triplets, dims, shards)):
        assert np.array_equal(got, want)
    nz = dims[2]
    lay = sharding.Layout(triplets[:, 0] % dims[0], triplets[:, 1] % dims[1],
                           dims, shards, [nz // shards] * (shards - 1)
                           + [nz - (shards - 1) * (nz // shards)])
    assert len(lay.rows) == shards
    for rows, want in zip(lay.rows, program):
        assert np.array_equal(triplets[rows], want)


# -- every operation of the distributed programs is in a stage ----------

_COLLECTIVES = ("all-to-all", "all-gather", "collective-permute",
                "ragged-all-to-all")


def _programs(plan):
    """(name, jitted, args) of the backward and forward programs, single
    and batched, with shapes for their arguments."""
    dp = plan.dist_plan
    tabs = plan._device_tables

    def sds(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                    sharding=plan._sharded)

    v = sds(np.zeros((4, dp.max_values, 2), np.float32))
    space = sds(jax.eval_shape(plan._backward_jit, v, *tabs))
    batched = plan._batched_jits()
    vb = sds(np.zeros((4, 2, dp.max_values, 2), np.float32))
    sb = sds(jax.eval_shape(batched["backward"], vb, *tabs))
    return [("backward", plan._backward_jit, (v,) + tabs),
            ("forward", plan._forward_jit[Scaling.FULL], (space,) + tabs),
            ("backward_batched", batched["backward"], (vb,) + tabs),
            ("forward_batched", batched[Scaling.FULL], (sb,) + tabs)]


@pytest.mark.parametrize("kind", ["c2c", "r2c"])
@pytest.mark.parametrize("exchange,chunks,pallas", [
    ("default", 1, None), ("default", 1, True), ("default", 2, None),
    ("compact_buffered", 1, None), ("compact_buffered", 2, None),
    ("unbuffered", 1, None), ("buffered_float", 1, None)])
def test_distributed_programs_carry_stage_scopes(kind, exchange, chunks,
                                                 pallas):
    _, hlo_pb2 = trace_reduce.protos()
    rules = trace_reduce.load_stage_rules()
    n = 16
    triplets = cutoff_triplets((n, n, n), 0.5, kind == "r2c")
    parts = round_robin_stick_partition(triplets, (n, n, n), 4)
    plan = make_distributed_plan(
        TransformType(kind), n, n, n, parts, [n // 4] * 4,
        exchange=ExchangeType(exchange), overlap_chunks=chunks,
        use_pallas=pallas)
    for name, jitted, args in _programs(plan):
        exe = jitted.lower(*args).compile().runtime_executable()
        module = hlo_pb2.HloModuleProto.FromString(
            exe.hlo_modules()[0].as_serialized_hlo_module_proto())
        assert module.name.startswith("jit__")
        entry = [c for c in module.computations
                 if c.id == module.entry_computation_id]
        unscoped, collectives = [], set()
        for inst in entry[0].instructions:
            scopes = rules["scope"].findall(inst.metadata.op_name)
            if inst.opcode in _COLLECTIVES:
                collectives.add(scopes[-1] if scopes else None)
            # a constant is data, not an operation the device runs; an
            # op_name without "/" names an argument
            if ("/" in inst.metadata.op_name and inst.opcode != "constant"
                    and not scopes):
                unscoped.append((inst.name, inst.metadata.op_name))
        assert not unscoped, (name, unscoped)
        assert collectives == {"spfft.exchange"}, (name, collectives)


# -- the exchange metrics ----------------------------------------------------

@pytest.fixture
def counters(monkeypatch):
    fresh = obs_counters.Counters()
    monkeypatch.setattr(obs, "GLOBAL_COUNTERS", fresh)
    return fresh


def _record(stage_s, transforms=40, built=True):
    record = {"trace": {"stage_s": stage_s, "transforms": transforms}}
    if built:
        record["plan_build_s"] = 30.0
    return record


def test_exchange_metrics_read_trace_and_counters(counters):
    record = _record({"spfft.exchange": 0.2, "spfft.exchange_layout": 0.08,
                      "spfft.xy": 1.0})
    assert run.read_metric("exchange_ms_per_transform", record) == \
        pytest.approx(5.0)
    assert run.read_metric("exchange_layout_ms_per_transform", record) == \
        pytest.approx(2.0)
    # no counter yet: no rate
    assert run.read_metric("exchange_egress_gb_per_s", record) is None
    counters.set("spfft_exchange_busiest_link_bytes", 85_000_000,
                 exchange="default", shards="4", chunks="1")
    # 85 MB in 5 ms
    assert run.read_metric("exchange_egress_gb_per_s", record) == \
        pytest.approx(17.0)


@pytest.mark.parametrize("name", ["exchange_ms_per_transform",
                                  "exchange_layout_ms_per_transform",
                                  "exchange_egress_gb_per_s"])
def test_exchange_metrics_none_without_their_parts(counters, name):
    counters.set("spfft_exchange_busiest_link_bytes", 85_000_000,
                 exchange="default", shards="4", chunks="1")
    assert run.read_metric(name, {}) is None
    assert run.read_metric(name, _record({"spfft.xy": 1.0})) is None
    assert run.read_metric(name, _record({"spfft.exchange": 0.2,
                                          "spfft.exchange_layout": 0.1},
                                         transforms=0)) is None
    if name == "exchange_egress_gb_per_s":
        assert run.read_metric(name, _record(
            {"spfft.exchange": 0.2}, built=False)) is None


def _plan_counters(counters, index=4.0, tables=(5.0, 37.0, 0.0), plans=1):
    for kind, seconds in zip(("index_plan", "exchange_tables",
                              "gather_tables", "fused_tables"),
                             (index,) + tables):
        counters.inc("spfft_compile_seconds_total", seconds, kind=kind)
    counters.inc("spfft_plan_builds_total", plans, kind="distributed")


def test_dist_plan_metrics_read_counters(counters):
    record = {"plan_build_s": 47.0}
    # a local plan's build counts under kind="local": not these
    counters.inc("spfft_compile_seconds_total", 3.0, kind="index_plan")
    counters.inc("spfft_plan_builds_total", 1, kind="local")
    assert run.read_metric("dist_plan_index_s", record) is None
    _plan_counters(counters, plans=2)
    assert run.read_metric("dist_plan_index_s", record) == \
        pytest.approx((3.0 + 4.0) / 2)
    assert run.read_metric("dist_plan_tables_s", record) == \
        pytest.approx(42.0 / 2)


@pytest.mark.parametrize("name", ["dist_plan_index_s", "dist_plan_tables_s"])
def test_dist_plan_metrics_none_without_their_parts(counters, name):
    # no plan built by the run's system, or no counter at all
    assert run.read_metric(name, {}) is None
    assert run.read_metric(name, {"plan_build_s": 47.0}) is None
    _plan_counters(counters, index=0.0, tables=(0.0, 0.0, 0.0))
    assert run.read_metric(name, {"plan_build_s": 47.0}) is None
    _plan_counters(counters)
    assert run.read_metric(name, {}) is None
    assert run.read_metric(name, {"plan_build_s": 47.0}) is not None
