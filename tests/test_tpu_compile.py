"""Compile-only checks of the main path's Pallas kernels for a TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached, so these tests catch what interpret mode cannot
(a slice not aligned to the tiling, more VMEM or SMEM than a kernel may
use) at no chip time. Nothing runs: a pass says the installed Mosaic
compiler accepts each kernel at the 256^3 plan's real shapes, never that
results or times are right. ``tpu_custom_call`` in the compiled text
proves the kernel is in the program.

Everything built from the topology is built in fixtures (never at
import), so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler. The plans are
built with ``jax.default_backend`` reporting ``"tpu"``, so their
trace-time gates take the branches the chip takes.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spfft_tpu import Scaling, TransformType, make_local_plan
from spfft_tpu.ops import dft
from spfft_tpu.ops import dft_kernel as dk
from spfft_tpu.ops import fused_kernel as fkm
from spfft_tpu.ops import gather_kernel as gk
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

N = 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compile_for(one_chip):
    """``compile_for(fn, *args)``: compile ``fn`` for the described chip
    with every array argument turned into a shape on it; returns the
    compiled text. The persistent cache is off around these compiles (an
    entry written for a described chip cannot be read back here), and so
    is the suite's x64 mode: the chip runs without it, and Mosaic
    refuses the 64-bit block indices it would give the kernels."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    was_x64 = jax.config.jax_enable_x64
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()

    def run(fn, *args):
        return _compile(jax.jit(fn), one_chip, args).as_text()

    yield run
    jax.config.update("jax_enable_compilation_cache", was)
    jax.config.update("jax_enable_x64", was_x64)
    compilation_cache.reset_cache()


def _compile(jitted, sharding, args):
    """``jitted`` compiled with every array argument turned into a shape
    on ``sharding``."""
    def shape(x):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype
                                    if not hasattr(x, "dtype") else x.dtype,
                                    sharding=sharding)

    args = jax.tree_util.tree_map(shape, args)
    return jitted.lower(*args).compile()


@pytest.fixture(scope="module")
def compile_module(one_chip, compile_for):
    """``compile_module(jitted, *args)``: the compiled HLO module proto
    of an already jitted function (under ``compile_for``'s settings)."""
    from benchmark import trace_reduce
    _, hlo_pb2 = trace_reduce.protos()

    def run(jitted, *args):
        exe = _compile(jitted, one_chip, args).runtime_executable()
        return hlo_pb2.HloModuleProto.FromString(
            exe.hlo_modules()[0].as_serialized_hlo_module_proto())

    return run


@pytest.fixture(scope="module")
def as_tpu():
    """Report a TPU backend for the module's plan builds and traces."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield


@pytest.fixture(scope="module")
def plans(as_tpu, compile_for):
    tr = spherical_cutoff_triplets(N)
    out = {
        "c2c": make_local_plan(TransformType.C2C, N, N, N, tr,
                               precision="single"),
        "r2c": make_local_plan(TransformType.R2C, N, N, N,
                               tr[tr[:, 0] >= 0], precision="single"),
    }
    yield out
    for p in out.values():
        p.close()


def _pipeline(plan, which):
    """(jitted, args) of one public executable of ``plan`` as the plan
    builds it, args as host arrays of the shapes the chip would see."""
    tabs = plan._tables_hot
    v = np.zeros((plan.index_plan.num_values, 2), np.float32)
    if which == "backward":
        return plan._backward_jit, (v, tabs)
    space = jax.eval_shape(plan._backward_jit, v, tabs)
    if which == "forward":
        return plan._forward_jit[Scaling.NONE], (space, tabs)
    if which == "pair":
        # what apply_pointwise(values) jits and caches
        return jax.jit(plan._pair_entry(None, Scaling.NONE)), (v, tabs)
    jits = plan._batched_jits()
    vb = np.zeros((2,) + v.shape, np.float32)
    if which == "batched_backward":
        return jits["backward"], (vb, tabs)
    sb = jax.eval_shape(jits["backward"], vb, tabs)
    return jits[Scaling.NONE], (sb, tabs)


@pytest.fixture(scope="module")
def compiled(plans, compile_module):
    """``compiled(kind, which)``: the compiled HLO module proto of one
    public executable, compiled once for the module's tests."""
    cache = {}

    def get(kind, which):
        if (kind, which) not in cache:
            plan = plans[kind]
            assert plan._pallas_active
            assert (plan._fused["dec"] is not None
                    and plan._fused["cmp"] is not None)
            fn, args = _pipeline(plan, which)
            cache[kind, which] = compile_module(fn, *args)
        return cache[kind, which]

    return get


PIPELINES = pytest.mark.parametrize(
    "which", ["backward", "forward", "pair", "batched_backward",
              "batched_forward"])


@PIPELINES
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_pipeline_256_compiles(compiled, kind, which):
    """The whole executable, gather + fused kernels included, as the
    chip runs it: both fused directions must be active, and the
    installed compiler must accept the program."""
    module = compiled(kind, which)
    assert any(inst.custom_call_target == "tpu_custom_call"
               for comp in module.computations
               for inst in comp.instructions)


@PIPELINES
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_pipeline_256_stage_scopes(compiled, kind, which):
    """Every operation of the executable carries the ``spfft.*`` stage
    scope that the benchmark's trace reduction reads: the program has a
    name of its own, every instruction whose ``op_name`` comes from a
    traced operation holds a scope (an ``op_name`` without ``/`` names
    an argument), and where the instruction's stack frames pass through
    a stage entry point of ``benchmark/stages.json``, the scope gives
    the stage those frames give."""
    from benchmark import trace_reduce
    rules = trace_reduce.load_stage_rules()
    module = compiled(kind, which)
    assert module.name != "jit__unknown"
    insts = trace_reduce.hlo_instructions(module)
    unscoped, differ, framed = [], [], 0
    for name, (op_name, frames) in insts.items():
        if "/" not in op_name:
            continue
        scopes = rules["scope"].findall(op_name)
        if not scopes:
            unscoped.append((name, op_name))
            continue
        by_frames = trace_reduce.stage_of("", frames, rules)
        if by_frames is not None:
            framed += 1
            if by_frames != scopes[-1]:
                differ.append((name, op_name, by_frames))
    assert not unscoped
    assert not differ
    assert framed  # the compiled proto keeps the stack frames


def _gather_inputs(plan):
    ip = plan.index_plan
    num_slots = plan._s_pad * ip.dim_z
    return num_slots, gk.compression_gather_inputs(
        ip.value_indices.astype(np.int64), num_slots)


@pytest.mark.parametrize("kind", ["narrow", "wide"])
@pytest.mark.parametrize("direction", ["dec", "cmp"])
def test_gather_kernel_256_compiles(plans, compile_for, kind, direction):
    """``run_gather`` on the 256^3 C2C plan's real compression tables,
    each direction one launch (``gk.wide_chunk_limit`` holds them)."""
    plan = plans["c2c"]
    num_slots, ((dec_idx, occ), (cmp_idx, cmp_valid)) = \
        _gather_inputs(plan)
    idx, valid, num_src = ((dec_idx, occ, plan.index_plan.num_values)
                           if direction == "dec"
                           else (cmp_idx, cmp_valid, num_slots))
    build = (gk.build_wide_gather_tables if kind == "wide"
             else gk.build_monotone_gather_tables)
    t = build(idx, valid, num_src)
    assert t is not None and not t.segs
    src = np.zeros((t.src_rows, gk.TILE_LANE), np.float32)
    dev = jax.tree_util.tree_map(np.asarray, gk.gather_device_tables(t))
    text = compile_for(lambda re, im, d: gk.run_gather(re, im, d, t),
                       src, src, dev)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [0, 2])
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_fused_decompress_256_compiles(plans, compile_for, kind, batch):
    plan = plans[kind]
    t = plan._fused["dec"]
    assert (t.zinfo is not None) == (kind == "r2c")
    lead = (batch,) if batch else ()
    src = np.zeros(lead + (t.src_rows, gk.TILE_LANE), np.float32)
    dev = tuple(np.asarray(a) for a in fkm.decompress_device_tables(t))
    mats = tuple(np.asarray(m, np.float32)
                 for m in dft.c2c_mats(N, dft.BACKWARD))
    text = compile_for(
        lambda re, im, d, m: fkm.run_decompress_zdft(re, im, d, m, t),
        src, src, dev, mats)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [0, 2])
@pytest.mark.parametrize("kind", ["c2c", "r2c"])
def test_fused_compress_256_compiles(plans, compile_for, kind, batch):
    plan = plans[kind]
    t = plan._fused["cmp"]
    lead = (batch,) if batch else ()
    sticks = np.zeros(lead + (t.src_sticks, N), np.float32)
    dev = tuple(np.asarray(a) for a in fkm.compress_device_tables(t))
    mats = tuple(np.asarray(m, np.float32)
                 for m in dft.c2c_mats(N, dft.FORWARD))
    text = compile_for(
        lambda sr, si, d, m: fkm.run_zdft_compress(sr, si, d, m, t),
        sticks, sticks, dev, mats)
    assert "tpu_custom_call" in text


def _mats(n):
    return tuple(np.asarray(m, np.float32)
                 for m in dft.c2c_mats(n, dft.BACKWARD))


@pytest.mark.parametrize("n,rows", [(N, 51456), (384, 1536), (512, 1536)])
def test_stage_kernel_compiles(compile_for, n, rows):
    """``pdft_last``: the 256^3 z-stage shape, and the larger axis
    classes where the 5.5 MB VMEM budget picks smaller row tiles (the
    next tile up, tm=1024 at 384 and tm=512 at 512, runs out of VMEM
    in this compiler)."""
    x = np.zeros((rows, n), np.float32)
    text = compile_for(lambda a, b, m: dk.pdft_last(a, b, m), x, x,
                       _mats(n))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n,planes", [(N, N), (320, 4)])
def test_xy_kernel_compiles(as_tpu, compile_for, n, planes):
    """The two-stage xy kernels: ``pdft2`` on the 256^3 local grid and
    ``cdft2_xy`` (the distributed xy dispatcher) at the largest eligible
    axis class, 320."""
    x = np.zeros((planes, n, n), np.float32)
    m = _mats(n)
    text = compile_for(lambda a, b, m1, m2: dk.pdft2(a, b, m1, m2),
                       x, x, m, m)
    assert "tpu_custom_call" in text

    def xy(a, b, m1, m2):
        y = dft.cdft2_xy(a + 1j * b, m1, m2)
        return jnp.real(y), jnp.imag(y)

    assert "tpu_custom_call" in compile_for(xy, x, x, m, m)


@pytest.mark.parametrize("chunks", ["limit", 6_913])
def test_wide_launch_at_chunk_limit(one_chip, compile_for, chunks):
    """One wide launch compiles at ``wide_chunk_limit()`` chunks, and at
    6,913: the 768^3 C2C shard's 6,912 super-tiles (73,728 full sticks a
    chip) and the padding one. Its scalar-prefetch tables are 1-D and
    take ``3 + P // 4`` words a chunk of ``SMEM_TABLE_WORDS``, 768 KiB of
    the 1 MiB SMEM (70,000 chunks overflow it); a 2-D ``sub`` table
    padded each row to 512 bytes and held a launch to 1,536 chunks."""
    P = gk.WIDE_P
    C = gk.wide_chunk_limit(P) if chunks == "limit" else chunks
    assert C * (3 + P // 4) <= gk.SMEM_TABLE_WORDS

    def shape(*dims, dtype=np.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    src = shape(70000, gk.TILE_LANE, dtype=np.float32)
    tabs = (shape(C), shape(C * P // 4), shape(C), shape(C),
            shape(C, P * 8, gk.TILE_LANE, dtype=np.int16))
    text = compile_for(
        lambda re, im, *t: gk._wide_gather_call(
            re, im, *t, span_rows=64, kp_rows=16, p_tiles=P, num_super=C,
            interpret=False),
        src, src, *tabs)
    assert "tpu_custom_call" in text


def test_distributed_wide_gather_one_launch(topo, as_tpu, compile_for,
                                            monkeypatch):
    """The distributed plan's compression gathers, compiled for four
    described chips: each direction is one ``wide_gather`` launch a chip
    (the shard-uniform wide tables, 1-D ``sub`` included, in one SPMD
    program), and no narrow ``monotone_gather``. SpFFT's C2C cutoff set
    at 64^3 and sparsity 0.5, round-robin over 4 shards: 4 super-tiles a
    shard. The plan's tables are shapes, never arrays on a chip."""
    import re
    from jax.sharding import Mesh
    from benchmark.sticks.cutoff import cutoff_triplets
    from spfft_tpu.parallel import make_distributed_plan
    from spfft_tpu.parallel.mesh import SHARD_AXIS
    from spfft_tpu.utils.workloads import round_robin_stick_partition

    def put(x, sharding=None, *args, **kw):
        return jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                    sharding=sharding)

    n = 64
    triplets = cutoff_triplets((n, n, n), 0.5, False)
    parts = round_robin_stick_partition(triplets, (n, n, n), 4)
    with monkeypatch.context() as m:
        m.setattr(jax, "device_put", put)
        plan = make_distributed_plan(
            TransformType.C2C, n, n, n, parts, [n // 4] * 4,
            mesh=Mesh(np.asarray(topo.devices[:4]), (SHARD_AXIS,)),
            precision="single", use_pallas=True)
    assert plan.gather_geometry == {"decompress": "wide", "compress": "wide"}
    assert plan._pallas_dist["dec"]["super_p1"] == 4 + 1
    dp, tabs = plan.dist_plan, plan._device_tables
    v = jax.ShapeDtypeStruct((4, dp.max_values, 2), np.float32,
                             sharding=plan._sharded)
    space = jax.eval_shape(plan._backward_jit, v, *tabs)
    space = jax.ShapeDtypeStruct(space.shape, space.dtype,
                                 sharding=plan._sharded)
    for jitted, arg in ((plan._backward_jit, v),
                        (plan._forward_jit[Scaling.FULL], space)):
        text = jitted.lower(arg, *tabs).compile().as_text()
        kernels = [re.search(r'op_name="([^"]*)"', line).group(1)
                   for line in text.splitlines()
                   if 'custom_call_target="tpu_custom_call"' in line]
        gathers = [k for k in kernels if "spfft.gather" in k]
        assert len(gathers) == 1 and "jit(wide_gather)" in gathers[0]
        assert "monotone_gather" not in text


@pytest.fixture(scope="module")
def cell_tables():
    """Super-tile fused tables of the benchmark cells' stick sets, built
    as the plan builds them: SpFFT's cutoff sets at 256^3, C2C at
    sparsity 0.5 (32768 full sticks) and R2C at 0.1 (3328)."""
    from spfft_tpu.benchmark import cutoff_stick_triplets
    from spfft_tpu.indexing import build_index_plan
    out = {}
    for kind, tt, sparsity in (("c2c", TransformType.C2C, 0.5),
                               ("r2c", TransformType.R2C, 0.1)):
        tr = cutoff_stick_triplets(N, N, N, sparsity, kind == "r2c")
        p = build_index_plan(tt, N, N, N, tr)
        s_pad = -(-(p.num_sticks + 1) // 32) * 32
        num_slots = s_pad * N
        (dec_idx, occ), (cmp_idx, cmp_valid) = gk.compression_gather_inputs(
            p.value_indices.astype(np.int64), num_slots)
        zid = p.zero_stick_id if kind == "r2c" else None
        out[kind] = (
            fkm.build_super_decompress_tables(
                dec_idx, occ, p.num_values, N, p.num_sticks,
                zero_stick_id=zid),
            fkm.build_super_compress_tables(cmp_idx, cmp_valid, num_slots,
                                            N, s_pad))
    return out


@pytest.fixture
def grids(monkeypatch):
    """The grids of the ``pallas_call``s traced while the test runs."""
    seen = []
    real = fkm.pl.pallas_call

    def record(*args, **kw):
        seen.append(tuple(kw["grid_spec"].grid))
        return real(*args, **kw)

    monkeypatch.setattr(fkm.pl, "pallas_call", record)
    return seen


CELLS = pytest.mark.parametrize("kind,steps,batch",
                                [("c2c", 512, 0), ("r2c", 52, 0),
                                 ("r2c", 52, 32)])


@CELLS
def test_super_tile_decompress_cells_compile(cell_tables, compile_for,
                                             grids, kind, steps, batch):
    """The backward fused kernel at the cells' super-tile geometry: one
    grid step per 64-stick super-tile (512 for C2C, 52 a field for R2C,
    against 8200 and 840 one-tile steps before), within the VMEM budget,
    accepted by the installed compiler."""
    t = cell_tables[kind][0]
    assert t.geometry == "super_tile" and t.grid_steps == steps
    assert (t.r_sticks, t.p_tiles) == (64, 16)
    assert fkm._fits_backward(N, t.p_tiles, t.span_rows,
                              t.zinfo is not None, t.p_tiles)
    lead = (batch,) if batch else ()
    src = np.zeros(lead + (t.src_rows, gk.TILE_LANE), np.float32)
    dev = tuple(np.asarray(a) for a in fkm.decompress_device_tables(t))
    mats = tuple(np.asarray(m, np.float32)
                 for m in dft.c2c_mats(N, dft.BACKWARD))
    text = compile_for(
        lambda re, im, d, m: fkm.run_decompress_zdft(re, im, d, m, t),
        src, src, dev, mats)
    assert "tpu_custom_call" in text
    assert grids == [lead + (steps,)]


@CELLS
def test_super_tile_compress_cells_compile(cell_tables, compile_for, grids,
                                           kind, steps, batch):
    """The forward fused kernel at the cells' geometry: one grid step per
    16 output tiles, each opening one 64-stick window (no stick is
    transformed twice), within the VMEM budget."""
    t = cell_tables[kind][1]
    assert t.geometry == "super_tile" and t.grid_steps == steps
    assert t.win_sticks == 64 and int(t.wnew.sum()) == steps
    assert fkm._fits_forward(N, t.win_sticks, t.p_tiles)
    lead = (batch,) if batch else ()
    sticks = np.zeros(lead + (t.src_sticks, N), np.float32)
    dev = tuple(np.asarray(a) for a in fkm.compress_device_tables(t))
    mats = tuple(np.asarray(m, np.float32)
                 for m in dft.c2c_mats(N, dft.FORWARD))
    text = compile_for(
        lambda sr, si, d, m: fkm.run_zdft_compress(sr, si, d, m, t),
        sticks, sticks, dev, mats)
    assert "tpu_custom_call" in text
    assert grids == [lead + (steps,)]


def test_super_tile_launch_at_smem_limit(compile_for):
    """The super-tile forward kernel compiles with as many chunks as
    ``SMEM_TABLE_WORDS`` admits at ``dim_z`` 256 (16 tiles a chunk): its
    1-D scalar-prefetch tables stay in the 1 MiB SMEM, which 16,000
    chunks overflow."""
    P = 16
    C = fkm.SMEM_TABLE_WORDS // (5 + P)
    i32 = functools.partial(np.zeros, dtype=np.int32)
    t = fkm.FusedCompressTables(
        s0=i32(C), out_tile=np.arange(C, dtype=np.int32),
        first=np.ones(C, np.int32), wnew=np.ones(C, np.int32),
        packed=np.zeros((C, P * 8, gk.TILE_LANE), np.int16), dim_z=N,
        win_sticks=64, num_tiles=C * P, num_out=C * P * gk.TILE,
        src_sticks=C * 64, span_rows=128, toff=i32(C * P), p_tiles=P,
        kp_rows=8)
    sticks = np.zeros((t.src_sticks, N), np.float32)
    dev = (t.s0, t.toff, t.out_tile, t.first, t.wnew, t.packed)
    mats = tuple(np.asarray(m, np.float32)
                 for m in dft.c2c_mats(N, dft.FORWARD))
    text = compile_for(
        lambda sr, si, d, m: fkm.run_zdft_compress(sr, si, d, m, t),
        sticks, sticks, dev, mats)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [0, 2])
@pytest.mark.parametrize("direction", ["dec", "cmp"])
def test_narrow_fused_256_compiles(plans, compile_for, direction, batch):
    """The narrow geometry, which the distributed plan and any tables
    past the super-tile budget keep, on the 256^3 C2C sphere's real
    tables."""
    plan = plans["c2c"]
    num_slots, ((dec_idx, occ), (cmp_idx, cmp_valid)) = \
        _gather_inputs(plan)
    lead = (batch,) if batch else ()
    s_pad = plan._s_pad
    if direction == "dec":
        nt = gk.build_monotone_gather_tables(dec_idx, occ,
                                             plan.index_plan.num_values)
        t = fkm.build_fused_decompress_tables(nt, N, s_pad)
        src = np.zeros(lead + (t.src_rows, gk.TILE_LANE), np.float32)
        dev = tuple(np.asarray(a) for a in fkm.decompress_device_tables(t))
        run = fkm.run_decompress_zdft
        mats = dft.c2c_mats(N, dft.BACKWARD)
    else:
        nt = gk.build_monotone_gather_tables(cmp_idx, cmp_valid, num_slots)
        t = fkm.build_fused_compress_tables(nt, N, s_pad)
        src = np.zeros(lead + (t.src_sticks, N), np.float32)
        dev = tuple(np.asarray(a) for a in fkm.compress_device_tables(t))
        run = fkm.run_zdft_compress
        mats = dft.c2c_mats(N, dft.FORWARD)
    assert t.geometry == "narrow"
    mats = tuple(np.asarray(m, np.float32) for m in mats)
    text = compile_for(lambda a, b, d, m: run(a, b, d, m, t),
                       src, src, dev, mats)
    assert "tpu_custom_call" in text
