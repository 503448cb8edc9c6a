"""Distributed sparse 3D FFT plans over a 1-D device mesh.

The reference's distributed layout (README.md:8, SURVEY.md §5.7): space domain
split into z-plane *slabs* per shard, frequency domain into z-stick *pencils*;
a collective exchange re-localises z between the two (reference:
src/parameters/parameters.cpp:43-140 builds the per-rank distribution plan,
src/execution/execution_host.cpp:249-352 runs the phases around the MPI
alltoall).

TPU-native realisation: one ``shard_map`` over a 1-D mesh whose body is the
whole per-shard pipeline —

  backward:  decompress -> [stick symmetry] -> z-IFFT -> pack ->
             all_to_all -> unpack -> [plane symmetry] -> xy-IFFT
  forward:   xy-FFT -> pack -> all_to_all -> unpack -> z-FFT -> compress

with all per-shard index tables padded to common maxima and passed as sharded
arrays (an SPMD body is traced once, so shard-varying data must be data, not
Python branches). Plan-time validation reproduces the reference's collective
consistency checks centrally: sum-of-planes == dim_z and sum-of-sticks bounds
(parameters.cpp:103-109), global duplicate-stick detection
(indices.hpp:105-117).

Caller-visible array layouts (per shard r, stacked over the shard axis and
sharded with ``PartitionSpec('shards')``):

* frequency values: ``(num_shards, max_values, 2)`` interleaved, shard r's
  values first, zero-padded;
* space domain: ``(num_shards, max_planes, dim_y, dim_x[, 2])`` — shard r's
  slab is rows ``[0, num_planes(r))`` of its block (zero-padded after), the
  global z order being ``plane_offsets(r) + p``.

Helpers convert between these padded device layouts and per-shard numpy lists.
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import faults as _faults
from ..errors import InvalidParameterError, ParameterMismatchError
from ..indexing import (build_index_plan, check_stick_duplicates,
                        occupied_x_window, window_sub_cols)
from ..ops import stages
from ..plan import _entry
from ..timing import timed_transform
from ..types import ExchangeType, Scaling, TransformType
from ..utils.dtypes import (as_interleaved, complex_dtype,
                            complex_to_interleaved, interleaved_to_complex,
                            real_dtype)
from .exchange import (all_to_all_blocks, build_compact_schedule,
                       build_ragged_schedule, compact_exchange,
                       ragged_exchange, pack_freq_to_blocks,
                       pack_space_to_blocks, ring_exchange_blocks,
                       unpack_blocks_to_grid, unpack_blocks_to_sticks)
from .mesh import make_mesh
from .overlap import build_overlap_schedule

#: Environment default for the plan's ``overlap_chunks`` knob: split the
#: distributed exchange into K destination-balanced chunks so the z/xy
#: FFT stages software-pipeline with the collectives (parallel/overlap.py).
#: K=1 (the default) is today's monolithic single-collective path.
OVERLAP_CHUNKS_ENV = "SPFFT_TPU_OVERLAP_CHUNKS"

#: The wire-compression ladder (docs/distributed.md "Compressed wire"):
#: rung index == ``wire_precision`` knob value. Rung 0 ships the payload
#: at transform precision; 1/2 are the typed float downcasts the legacy
#: ``*_FLOAT`` exchange variants hard-coded; 3 quantizes to int8 with
#: per-stick absmax scales packed alongside the payload.
WIRE_RUNGS = ("full", "f32", "bf16", "int8")
WIRE_PRECISION_ENV = "SPFFT_TPU_WIRE_PRECISION"
WIRE_ERROR_BUDGET_ENV = "SPFFT_TPU_WIRE_ERROR_BUDGET"

logger = logging.getLogger("spfft_tpu")


def _in_parallel(fn, items) -> list:
    """``[fn(item) for item in items]`` with one thread an item: the
    per-shard plan builds (and the two directions' gather tables) are
    independent, and their NumPy and native planner work runs outside
    the GIL."""
    items = list(items)
    if len(items) < 2:
        return [fn(item) for item in items]
    workers = min(len(items), os.cpu_count() or 1)
    with ThreadPoolExecutor(workers) as pool:
        return list(pool.map(fn, items))


@dataclasses.dataclass(frozen=True)
class DistributedIndexPlan:
    """The global distribution plan: per-shard stick sets + slab split.

    Equivalent of the reference ``Parameters`` in distributed mode
    (reference: parameters.cpp:43-140): per-rank stick counts and xy indices,
    per-rank plane counts and offsets, with the same validation.
    """

    transform_type: TransformType
    dim_x: int
    dim_y: int
    dim_z: int
    shard_plans: tuple
    num_planes: tuple
    plane_offsets: tuple

    @property
    def num_shards(self) -> int:
        return len(self.shard_plans)

    @property
    def max_sticks(self) -> int:
        return max(p.num_sticks for p in self.shard_plans)

    @property
    def max_planes(self) -> int:
        return max(self.num_planes)

    @property
    def max_values(self) -> int:
        return max(p.num_values for p in self.shard_plans)

    @property
    def dim_x_freq(self) -> int:
        return self.shard_plans[0].dim_x_freq

    @property
    def hermitian(self) -> bool:
        return self.transform_type == TransformType.R2C

    @property
    def num_global_elements(self) -> int:
        """Total sparse values across shards (reference:
        transform.hpp:145 num_global_elements)."""
        return sum(p.num_values for p in self.shard_plans)


def build_distributed_plan(transform_type: TransformType,
                           dim_x: int, dim_y: int, dim_z: int,
                           triplets_per_shard: Sequence[np.ndarray],
                           planes_per_shard: Sequence[int],
                           ) -> DistributedIndexPlan:
    """Build and validate the global distribution plan.

    ``triplets_per_shard[r]`` is shard r's sparse triplet list (a z-stick must
    live wholly on one shard — enforced by the duplicate check);
    ``planes_per_shard[r]`` its slab height. The distribution is caller-chosen
    and may be arbitrary/non-uniform including empty shards, exactly like the
    reference (tests/mpi_tests/test_transform.cpp:110-165).
    """
    transform_type = TransformType(transform_type)
    if len(triplets_per_shard) != len(planes_per_shard):
        raise InvalidParameterError(
            "triplets_per_shard and planes_per_shard length mismatch")
    if len(triplets_per_shard) == 0:
        raise InvalidParameterError("need at least one shard")
    planes = tuple(int(p) for p in planes_per_shard)
    if any(p < 0 for p in planes):
        raise InvalidParameterError("negative plane count")
    if sum(planes) != dim_z:
        # reference: parameters.cpp:107-109 (MPIParameterMismatchError)
        raise ParameterMismatchError(
            f"sum of planes per shard ({sum(planes)}) != dim_z ({dim_z})")
    from .. import obs as _obs
    with _obs.compile_span("index_plan"):
        shard_plans = tuple(_in_parallel(
            lambda t: build_index_plan(transform_type, dim_x, dim_y, dim_z,
                                       np.asarray(t).reshape(-1, 3)),
            triplets_per_shard))
        check_stick_duplicates([p.stick_keys for p in shard_plans])
    total_sticks = sum(p.num_sticks for p in shard_plans)
    if total_sticks > dim_x * dim_y:
        # reference: parameters.cpp:103-106
        raise ParameterMismatchError(
            f"total sticks ({total_sticks}) exceed xy plane size")
    offsets = tuple(int(o) for o in np.concatenate(
        [[0], np.cumsum(planes)[:-1]]))
    return DistributedIndexPlan(
        transform_type=transform_type, dim_x=dim_x, dim_y=dim_y, dim_z=dim_z,
        shard_plans=shard_plans, num_planes=planes, plane_offsets=offsets)


class DistributedTransformPlan:
    """A compiled distributed sparse 3D FFT over a device mesh.

    Equivalent of a distributed reference ``Transform``
    (reference: transform.hpp:56-227 with an MPI communicator).
    """

    def __init__(self, dist_plan: DistributedIndexPlan,
                 mesh: Optional[Mesh] = None, precision: str = "single",
                 exchange: ExchangeType = ExchangeType.DEFAULT,
                 use_pallas: Optional[bool] = None,
                 overlap_chunks: Optional[int] = None,
                 wire_precision: Optional[int] = None,
                 wire_error_budget: Optional[float] = None):
        from .. import obs as _obs
        from ..utils.platform import enable_persistent_compilation_cache
        enable_persistent_compilation_cache()
        _t0_build = time.perf_counter()
        self.dist_plan = dist_plan
        self.precision = precision
        self.exchange = ExchangeType(exchange)
        self.mesh = mesh if mesh is not None else make_mesh(
            dist_plan.num_shards)
        (self.axis_name,) = self.mesh.axis_names
        if self.mesh.devices.size != dist_plan.num_shards:
            raise InvalidParameterError(
                f"mesh has {self.mesh.devices.size} devices but plan has "
                f"{dist_plan.num_shards} shards")
        self._rdt = real_dtype(precision)
        self._cdt = complex_dtype(precision)
        # Reduced wire precision (reference *_FLOAT exchanges, types.h:43-57):
        if precision == "double" and (jax.default_backend() == "tpu"
                                      or not jax.config.jax_enable_x64):
            logger.warning(
                "spfft_tpu: distributed precision='double' without jax "
                "x64 runs at FLOAT32 device precision (x64 is "
                "unavailable on TPU, and off by default on CPU; the "
                "on-device double-single mode covers local plans only) "
                "— use the CPU backend with JAX_ENABLE_X64=1 for true "
                "f64 (docs/precision.md)")
        # Wire rung resolution (``self._wire_dtype``) is deferred to
        # _resolve_wire_rung below: int8 eligibility depends on the
        # exchange mechanism selected next, and the legacy *_FLOAT
        # variants map onto the ladder there (one rung down).
        self._wire_dtype = None
        self._init_split_x()
        # UNBUFFERED selects the ppermute-ring mechanism; COMPACT_BUFFERED
        # the exact-count exchange — ONE ragged_all_to_all per direction
        # (exchange.RaggedSchedule, the true Alltoallv; launch count is
        # shard-count-invariant, replacing the round-4 ppermute schedule
        # that paid up to 416 collectives at S=32). Off-TPU the ragged
        # collective is EMULATED (all_gather + plan-time gather — XLA:CPU
        # has no ragged-all-to-all kernel), so the CPU suite and the
        # virtual-device dryrun execute the same tables end-to-end.
        # SPFFT_TPU_COMPACT_PPERMUTE=1 restores the ppermute schedule
        # (also used at S=1, where no collective exists to batch). Every
        # other variant runs the single fused all_to_all (exchange.py).
        import os as _os
        self._compact = None
        self._ragged = None
        # Compute/communication overlap (parallel/overlap.py): split the
        # exchange into K destination-balanced chunks so chunk i's z/xy
        # FFT stage runs while chunk i-1's collective is in flight
        # (issue early, unpack late). K=1 keeps the monolithic path —
        # bit-identical to a plan built without the knob. The knob
        # composes with EVERY exchange mechanism: ragged/compact get
        # chunked exact-count sub-schedules, the padded block layouts
        # (buffered/ring, float-wire included) chunk by static row
        # slices with no extra tables.
        if overlap_chunks is None:
            env = _os.environ.get(OVERLAP_CHUNKS_ENV)
            if env:
                overlap_chunks = int(env)
            else:
                # round 11: the knob's default lives in the typed
                # control-plane config (boot artifact / auto-tuner
                # recommendation), not a hard-coded constant
                from ..control.config import global_config
                overlap_chunks = int(global_config().overlap_chunks)
        if int(overlap_chunks) < 1:
            raise InvalidParameterError(
                f"overlap_chunks must be >= 1, got {overlap_chunks}")
        k_eff = min(int(overlap_chunks), dist_plan.max_sticks,
                    dist_plan.max_planes)
        if dist_plan.num_shards == 1:
            k_eff = 1  # comm-size-1: no collective to overlap
        if k_eff != int(overlap_chunks):
            logger.info(
                "spfft_tpu: overlap_chunks clamped %s -> %d (bounded by "
                "max_sticks/max_planes; 1 on a single shard)",
                overlap_chunks, k_eff)
        self.overlap_chunks = k_eff
        self._overlap = None
        # Fused-plausible plans snap the backward chunk bounds to
        # super-tile multiples (overlap.chunk_bounds_aligned) so the
        # per-chunk fused decompress+z-DFT launches waste no partial
        # super-tile at chunk seams. Cheap pre-check only — the full
        # gate runs in _init_fused_dist once the schedule exists; the
        # per-chunk table sets handle unaligned bounds too, so a
        # later decline costs nothing.
        from ..ops import fused_kernel as _fkm
        stick_align = 1
        if (k_eff > 1 and _fkm.enabled()
                and (jax.default_backend() == "tpu"
                     or _fkm.interpret_forced())
                and use_pallas is not False
                and self.precision == "single"
                and _fkm.eligible_dim(dist_plan.dim_z) is None):
            stick_align = _fkm.super_tile_geometry(dist_plan.dim_z)[0]
        use_ppermute_compact = _os.environ.get(
            "SPFFT_TPU_COMPACT_PPERMUTE") == "1"
        with _obs.compile_span("exchange_schedule"):
            if self.exchange.compact:
                if dist_plan.num_shards > 1 and not use_ppermute_compact:
                    if k_eff > 1:
                        self._overlap = build_overlap_schedule(
                            dist_plan, k_eff, "ragged",
                            x_window=self._split_x,
                            stick_align=stick_align)
                    else:
                        self._ragged = build_ragged_schedule(
                            dist_plan, x_window=self._split_x)
                elif k_eff > 1 and dist_plan.num_shards > 1:
                    self._overlap = build_overlap_schedule(
                        dist_plan, k_eff, "compact",
                        x_window=self._split_x, stick_align=stick_align)
                else:
                    self._compact = build_compact_schedule(
                        dist_plan, x_window=self._split_x)
            elif k_eff > 1:
                self._overlap = build_overlap_schedule(
                    dist_plan, k_eff, "block", stick_align=stick_align)
        # SPFFT_TPU_FORCE_RAGGED_OP=1 lowers the REAL ragged op off-TPU
        # (XLA:CPU can lower it but not execute it) — used by the HLO
        # launch-count checks in tests and scripts/scaling_model.py.
        self._ragged_emulate = (jax.default_backend() != "tpu"
                                and _os.environ.get(
                                    "SPFFT_TPU_FORCE_RAGGED_OP") != "1")
        if (self._compact is not None or self._ragged is not None
                or (self._overlap is not None
                    and self._overlap.kind != "block")):
            self._exchange_fn = None
        elif self.exchange == ExchangeType.UNBUFFERED:
            self._exchange_fn = ring_exchange_blocks
        else:
            self._exchange_fn = all_to_all_blocks
        # Error-budgeted wire ladder: pick the rung (and _wire_dtype) now
        # that the mechanism is known — the int8 rung needs the padded
        # block layout, and the measured probe must run BEFORE the
        # comm-size-1 local delegation so every plan records its rung.
        self._resolve_wire_rung(wire_precision, wire_error_budget)
        with _obs.compile_span("exchange_tables"):
            self._build_tables()
        with _obs.compile_span("gather_tables"):
            self._init_pallas(use_pallas)
        self._sharded = NamedSharding(self.mesh, P(self.axis_name))
        self._replicated = NamedSharding(self.mesh, P())
        # Commit the static tables to device once, at plan time (never on the
        # hot path — SURVEY.md §3.1's plan/execute split).
        self._device_tables = (
            jax.device_put(self._vi, self._sharded),
            jax.device_put(self._slot_src, self._sharded),
            jax.device_put(self._onehot, self._sharded),
            jax.device_put(self._cols_flat, self._replicated),
            jax.device_put(self._col_inv, self._replicated),
            jax.device_put(self._zmap, self._replicated),
            jax.device_put(self._z_src, self._replicated),
            jax.device_put(self._conj_mult, self._sharded))
        if self._pallas_dist is not None:
            self._device_tables = self._device_tables + tuple(
                jax.device_put(a, self._sharded)
                for a in self._pallas_dist["stacked"])
        self._n_ptables = (len(self._pallas_dist["stacked"])
                           if self._pallas_dist is not None else 0)
        # Exact-count exchange tables (all sharded): per-hop pack tables +
        # the unpack table, both directions. Overlap schedules ship one
        # table set PER CHUNK plus the two late global unpack tables
        # (overlap.OverlapSchedule.device_tables); block-kind overlap
        # needs no tables at all (static slice bounds only).
        self._n_ctables = 0
        self._ov_slices = None
        if self._overlap is not None and self._overlap.kind != "block":
            ctables = self._overlap.device_tables()
            self._ov_slices = self._overlap.chunk_table_slices()
            self._n_ctables = len(ctables)
            self._device_tables = self._device_tables + tuple(
                jax.device_put(a, self._sharded) for a in ctables)
        elif self._compact is not None:
            ctables = (list(self._compact.bwd_pack)
                       + [self._compact.bwd_unpack]
                       + list(self._compact.fwd_pack)
                       + [self._compact.fwd_unpack])
            self._n_ctables = len(ctables)
            self._device_tables = self._device_tables + tuple(
                jax.device_put(a, self._sharded) for a in ctables)
        elif self._ragged is not None:
            ctables = self._ragged.device_tables()
            self._n_ctables = len(ctables)
            self._device_tables = self._device_tables + tuple(
                jax.device_put(a, self._sharded) for a in ctables)
        # Fused local-stage twins (ops/fused_kernel.py): the backward
        # decompress+z-DFT (one table set PER OVERLAP CHUNK) and the
        # forward z-DFT+compress. Tables appended LAST — backward set
        # then forward set — so the bodies keep slicing
        # ptables/ctables by the existing counts.
        with _obs.compile_span("fused_tables"):
            self._init_fused_dist(use_pallas)
            self._init_fused_dist_fwd(use_pallas)
        self._n_fb = 0
        self._n_ff = 0
        fused_specs = ()
        if self._fused_dist is not None:
            fd = self._fused_dist
            self._n_fb = len(fd["stacked"]) + len(fd["mats"])
            fused_specs += ((P(self.axis_name),) * len(fd["stacked"])
                            + (P(),) * len(fd["mats"]))
            self._device_tables = self._device_tables + tuple(
                jax.device_put(a, self._sharded)
                for a in fd["stacked"]) + tuple(
                jax.device_put(m, self._replicated) for m in fd["mats"])
        if self._fused_dist_fwd is not None:
            ff = self._fused_dist_fwd
            self._n_ff = len(ff["stacked"]) + len(ff["mats"])
            fused_specs += ((P(self.axis_name),) * len(ff["stacked"])
                            + (P(),) * len(ff["mats"]))
            self._device_tables = self._device_tables + tuple(
                jax.device_put(a, self._sharded)
                for a in ff["stacked"]) + tuple(
                jax.device_put(m, self._replicated) for m in ff["mats"])
        self._n_ftables = self._n_fb + self._n_ff
        # Comm-size-1 collapse (reference: grid_internal.cpp:182 treats a
        # size-1 communicator as local): single-shard plans EXECUTE
        # through the local pipeline (planar T-layout matmul-DFT, stick
        # padding, no pack/exchange/unpack round trip — measured 1.65x
        # faster at 256^3), while the distributed API surface (padded
        # (1, ...) layouts, shard helpers, getters, wire-byte model)
        # stays. Large pair-layout plans keep the SPMD path (the local
        # boundary would transpose the values through the host).
        from ..plan import PAIR_IO_THRESHOLD, TransformPlan
        self._local1 = None
        self._local1_fns = {}
        if (dist_plan.num_shards == 1 and jax.process_count() == 1
                and use_pallas is not True  # explicit force keeps the
                # SPMD kernel path (interpret-mode semantics on CPU)
                and dist_plan.shard_plans[0].num_values
                < PAIR_IO_THRESHOLD):
            # device_double=False: the delegate must keep the distributed
            # API contract (sharded f32 jax.Array outputs, pointwise fns)
            # — the on-device double mode changes both (review r5)
            self._local1 = TransformPlan(dist_plan.shard_plans[0],
                                         precision=precision,
                                         use_pallas=use_pallas,
                                         device_double=False)
        self._base_in_specs = (
            (P(self.axis_name),                       # data
             P(self.axis_name), P(self.axis_name),    # vi, slot_src
             P(self.axis_name),                       # onehot
             P(), P(), P(), P(),      # cols, col_inv, zmap, z_src
             P(self.axis_name))                       # conj_mult
            + (P(self.axis_name),) * (self._n_ptables + self._n_ctables)
            + fused_specs)
        # pallas_call outputs carry no varying-mesh-axes metadata, so the
        # vma consistency check must be off when the kernel is in the body;
        # XLA-path plans keep the check (specs pin every sharding anyway)
        self._check_vma = (self._pallas_dist is None
                           and self._fused_dist is None
                           and self._fused_dist_fwd is None)
        shmap = functools.partial(
            jax.shard_map, mesh=self.mesh, in_specs=self._base_in_specs,
            out_specs=P(self.axis_name), check_vma=self._check_vma)
        self._pair_jits = {}
        self._batched = None
        self._backward_jit = jax.jit(shmap(self._backward_body))
        self._forward_jit = {
            s: jax.jit(shmap(_entry(f"_forward_body_{s.name.lower()}",
                                    self._forward_body,
                                    scaled=(s == Scaling.FULL))))
            for s in (Scaling.NONE, Scaling.FULL)
        }
        # exchange observability (spfft_tpu.obs): plan-build span plus
        # the exact wire/busiest-link byte accounting — per chunk when
        # the overlap pipeline is active — surfaced as metrics so
        # distributed rounds stop hand-rolling them into bench JSON
        _dt = time.perf_counter() - _t0_build
        _obs.record_plan_build(self, _dt, _t0_build)
        _obs.record_exchange_plan(self, _dt, _t0_build)

    # -- wire precision ladder ----------------------------------------------
    def _resolve_wire_rung(self, wire_precision, wire_error_budget) -> None:
        """Resolve the wire-compression rung (docs/distributed.md
        "Compressed wire"): walk DOWN from the requested rung, declining
        any rung the plan cannot carry (int8 needs the padded block
        layout for its scale sidecar) or whose MEASURED probe error
        exceeds the declared l2 budget, until one fits — rung 0 ("full")
        always does. Each decline is recorded with a reason
        (``spfft_wire_rung_declined_total{reason}`` + ``wire_declines``)
        so a refusal is observable, never silent. Legacy ``*_FLOAT``
        exchange variants map onto the ladder here (requested rung 1 for
        double, 2 for single) so their one-rung downcast keeps working
        unchanged under the same budget gate."""
        import os as _os
        from ..control.config import global_config
        if wire_precision is None:
            env = _os.environ.get(WIRE_PRECISION_ENV)
            wire_precision = (int(env) if env
                              else int(global_config().wire_precision))
        if wire_error_budget is None:
            env = _os.environ.get(WIRE_ERROR_BUDGET_ENV)
            wire_error_budget = (
                float(env) if env
                else float(global_config().wire_error_budget))
        requested = int(wire_precision)
        if not 0 <= requested < len(WIRE_RUNGS):
            raise InvalidParameterError(
                f"wire_precision must be in [0, {len(WIRE_RUNGS) - 1}], "
                f"got {requested}")
        if float(wire_error_budget) <= 0:
            raise InvalidParameterError(
                f"wire_error_budget must be > 0, got {wire_error_budget}")
        if requested == 0 and self.exchange.float_wire:
            requested = 1 if self.precision == "double" else 2
        # int8 packs per-stick scales alongside the padded block payload;
        # the exact-count layouts (ragged/compact and their overlap
        # kinds) address individual elements, leaving no room on the
        # wire for the scale sidecar in one collective round.
        int8_ok = (self._compact is None and self._ragged is None
                   and (self._overlap is None
                        or self._overlap.kind == "block"))
        self.wire_rung_requested = requested
        self.wire_error_budget = float(wire_error_budget)
        declines = []
        rung = requested
        probe_err = 0.0
        from .. import obs as _obs
        while rung > 0:
            if rung == 3 and not int8_ok:
                reason = "exact_count_layout"
            else:
                try:
                    probe_err = self._probe_wire_error(rung)
                except _faults.InjectedFault:
                    reason = "fault_injected"
                else:
                    if probe_err <= self.wire_error_budget:
                        break
                    reason = "over_budget"
            declines.append((WIRE_RUNGS[rung], reason))
            _obs.GLOBAL_COUNTERS.inc("spfft_wire_rung_declined_total",
                                     reason=reason)
            _obs.record_event("wire.decline", rung=WIRE_RUNGS[rung],
                              reason=reason)
            rung -= 1
        if rung == 0:
            probe_err = 0.0
        _obs.record_event("wire.resolve",
                          requested=WIRE_RUNGS[requested],
                          resolved=WIRE_RUNGS[rung],
                          probe_error=float(probe_err))
        self.wire_rung = rung
        self.wire_rung_name = WIRE_RUNGS[rung]
        self.wire_probe_error = float(probe_err)
        self.wire_declines = tuple(declines)
        self._wire_dtype = {0: None, 1: np.float32, 2: jnp.bfloat16,
                            3: jnp.int8}[rung]
        if declines:
            logger.info(
                "spfft_tpu: wire rung %s declined to %s (%s; budget %g, "
                "probe err %g)", WIRE_RUNGS[requested],
                self.wire_rung_name,
                ", ".join(f"{n}:{r}" for n, r in declines),
                self.wire_error_budget, self.wire_probe_error)

    def _probe_wire_error(self, rung: int) -> float:
        """Measured rel-l2 round-trip error of ``rung`` on an adversarial
        host-side probe spectrum: seeded gaussian stick rows with a huge
        per-row dynamic range (10^±6 magnitudes) — the shape the int8
        per-stick scales exist to survive. The reference signal is the
        device payload (probe cast to the transform's real dtype), so
        rung 1 under single precision measures exactly 0. Runs once at
        plan build, never on the hot path; the int8 twin mirrors
        ``exchange.quantize_blocks_int8`` in numpy, with the
        ``exchange.quantize`` fault seam guarding the scale
        computation."""
        rng = np.random.default_rng(0x51F8)
        dp = self.dist_plan
        rows = int(min(max(dp.max_sticks, 1), 64))
        cols = int(min(max(dp.dim_z, 1), 64))
        mags = 10.0 ** rng.uniform(-6.0, 6.0, size=(rows, 1, 1))
        il = (rng.standard_normal((rows, cols, 2)) * mags)
        ref = il.astype(self._rdt).astype(np.float64)
        if rung == 3:
            _faults.check_site("exchange.quantize")
            absmax = np.max(np.abs(ref), axis=(1, 2), keepdims=True)
            scale = np.where(absmax > 0, absmax / 127.0, 1.0)
            q = np.clip(np.rint(ref / scale), -127, 127).astype(np.int8)
            back = q.astype(np.float64) * scale
        else:
            wdt = np.float32 if rung == 1 else jnp.bfloat16
            back = ref.astype(wdt).astype(np.float64)
        denom = float(np.linalg.norm(ref))
        return float(np.linalg.norm(back - ref) / denom) if denom else 0.0

    # -- static tables -------------------------------------------------------
    def _init_split_x(self) -> None:
        """Global sparse-x xy-stage (the distributed form of the reference's
        y-over-non-empty-rows optimization, execution_host.cpp:139-145):
        when the union of all shards' occupied x columns spans under 70% of
        the x extent, every shard's plane grid — and both exchange unpack
        layouts — shrink to the occupied window, and the y-FFT runs only on
        it. Cyclic (wrapped) window for C2C centered sets; linear window of
        the half spectrum for R2C."""
        dp = self.dist_plan
        self._split_x = None
        self._xf_eff = dp.dim_x_freq
        cols = [p.scatter_cols for p in dp.shard_plans if p.num_sticks]
        if not cols:
            return
        xs = np.concatenate(cols) % dp.dim_x_freq
        x0, w = occupied_x_window(xs, dp.dim_x_freq,
                                  allow_wrap=not dp.hermitian)
        if w > 0.7 * dp.dim_x_freq:
            return
        self._split_x = (x0, w)
        self._xf_eff = w

    def _sub_cols(self, cols: np.ndarray) -> np.ndarray:
        """Map full-grid plane columns to occupied-window columns."""
        if self._split_x is None:
            return cols
        x0, w = self._split_x
        return window_sub_cols(cols, self.dist_plan.dim_x_freq, x0, w)

    def _build_tables(self) -> None:
        dp = self.dist_plan
        S, ms, mp_, mv = (dp.num_shards, dp.max_sticks, dp.max_planes,
                          dp.max_values)
        dim_z = dp.dim_z
        # Per-shard value indices, padded with an out-of-range sentinel
        # (gathers route sentinels to an appended zero row). All data
        # movement is gather-based with plan-time inverse maps — runtime
        # scatters lower near-serially on TPU (see indexing.inverse_slot_map).
        pad_vi = ms * dim_z
        vi = np.full((S, mv), pad_vi, np.int32)
        for r, p in enumerate(dp.shard_plans):
            vi[r, :p.num_values] = p.value_indices
        # Per-shard inverse slot map for the gather-based decompress
        # (sharded): slot -> local value position, sentinel mv.
        slot_src = np.full((S, ms * dim_z), mv, np.int32)
        for r, p in enumerate(dp.shard_plans):
            slot_src[r, :p.num_sticks * dim_z] = \
                np.where(p.slot_src == p.num_values, mv, p.slot_src)
        # Every shard's scatter columns (replicated): the global stick table,
        # the analogue of the reference's plan-time stick-list exchange
        # (indices.hpp:58-102 create_distributed_transform_indices). When
        # the split-x window is active, columns index the occupied window
        # (width _xf_eff), not the full plane.
        pad_col = dp.dim_y * self._xf_eff
        cols = np.full((S, ms), pad_col, np.int32)
        for r, p in enumerate(dp.shard_plans):
            cols[r, :p.num_sticks] = self._sub_cols(p.scatter_cols)
        # Global inverse column map (replicated): plane column -> global
        # padded stick index shard*ms + i, sentinel S*ms.
        col_inv = np.full(dp.dim_y * self._xf_eff, S * ms, np.int32)
        for r, p in enumerate(dp.shard_plans):
            col_inv[self._sub_cols(p.scatter_cols)] = \
                r * ms + np.arange(p.num_sticks)
        # z index owned by each shard's p-th plane (replicated), sentinel
        # dim_z for slab padding — drives the backward pack.
        zmap = np.full((S, mp_), dim_z, np.int32)
        for r in range(S):
            n = dp.num_planes[r]
            zmap[r, :n] = dp.plane_offsets[r] + np.arange(n)
        # Inverse: global z -> owner_shard * mp_ + plane (total map) — drives
        # the forward unpack gather.
        z_src = np.empty(dim_z, np.int32)
        for r in range(S):
            n = dp.num_planes[r]
            z_src[dp.plane_offsets[r]:dp.plane_offsets[r] + n] = \
                r * mp_ + np.arange(n)
        # One-hot mask of the (0,0) stick per shard (sharded) — drives the
        # R2C stick-symmetry fixup without per-shard Python branches
        # (reference: parameters.cpp:133-139 locates the stick; the owner is
        # shard-dependent but the SPMD body is traced once).
        onehot = np.zeros((S, ms), np.float32)
        for r, p in enumerate(dp.shard_plans):
            if p.zero_stick_id is not None:
                onehot[r, p.zero_stick_id] = 1.0
        # Hermitian x < 0 folding (indexing.canonicalize_hermitian_triplets):
        # per-shard ±1 multiplier on the interleaved value lanes, -1 on the
        # imaginary lane of folded conjugate mirrors. Static _has_conj keeps
        # unfolded plans byte-identical (the multiply is never traced); the
        # table stays a (S, 1, 2) ones placeholder then, so the extra pytree
        # leaf ships nothing per call.
        self._has_conj = any(
            p.value_conj is not None and bool(p.value_conj.any())
            for p in dp.shard_plans)
        if self._has_conj:
            conj_mult = np.ones((S, mv, 2), self._rdt)
            for r, p in enumerate(dp.shard_plans):
                if p.value_conj is not None:
                    conj_mult[r, :p.num_values, 1] = np.where(
                        p.value_conj, -1.0, 1.0)
        else:
            conj_mult = np.ones((S, 1, 2), self._rdt)
        self._conj_mult = conj_mult
        self._vi = vi
        self._slot_src = slot_src
        self._cols_flat = cols.reshape(-1)
        self._col_inv = col_inv
        self._zmap = zmap
        self._z_src = z_src
        self._onehot = onehot

    def _init_pallas(self, use_pallas: Optional[bool]) -> None:
        """Build per-shard Pallas windowed-gather tables for the compression
        stages, stacked into SPMD-sharded arrays (the same kernel the local
        plan uses; see ops/gather_kernel.py).

        Per-shard chunk counts differ, so each shard's tables are padded to
        the maximum with no-op chunks targeting a dummy output tile
        (gather_kernel.pad_tables_to); the DMA window height K and source
        rows are unified across shards (the SPMD body is one program).
        The kernel handles any value order (stick-major/z-ascending is
        optimal); a shard whose order is too scattered for the chunk
        decomposition drops ALL shards to the XLA path with a logged
        notice. Active in single precision on TPU; ``use_pallas=True`` on
        a non-TPU backend runs the kernel in interpret mode (testing) —
        note the asymmetry with the local ``TransformPlan``, whose
        ``use_pallas=True`` on non-TPU builds tables but executes the XLA
        path (interpret mode per value-array would dominate local
        runtimes; here the SPMD body must be one program)."""
        from ..ops import gather_kernel as gk

        dp = self.dist_plan
        self._pallas_dist = None
        self._pallas_interpret = False
        backend_ok = jax.default_backend() == "tpu"
        if use_pallas is True and self.precision != "single":
            raise InvalidParameterError(
                "the Pallas compression kernel is single-precision only")
        if use_pallas is False or (use_pallas is None and not backend_ok):
            return
        if use_pallas is None and self.precision != "single":
            return
        if use_pallas is None and dp.max_values < 200_000:
            # Same measured crossover as the local plan (plan._init_pallas,
            # round-3 sweep): below ~200k per-shard values the XLA gather
            # beats the kernel's fixed launch overhead (64^3 1-shard:
            # XLA 1.35 vs kernel 3.6 ms; 96^3: kernel 1.5 vs XLA 5.4).
            return
        ms, mv, dim_z = dp.max_sticks, dp.max_values, dp.dim_z
        num_slots = ms * dim_z
        if mv == 0 or num_slots == 0:
            return

        per_shard = _in_parallel(
            lambda p: gk.compression_gather_inputs(
                p.value_indices, num_slots, pad_values_to=mv),
            dp.shard_plans)

        def build_uniform(which, num_src, num_out, builder, pad_fn,
                          geom_keys, extra):
            """Two passes: discover each shard's preferred geometry, then
            rebuild with the common (max of each ``geom_keys`` attribute)
            forced so the SPMD program is uniform; pad chunk counts to the
            max and stack. Returns None if any shard declines (caller
            falls through to the next kind / the XLA path)."""
            tables = _in_parallel(
                lambda s: builder(*s[which], num_src, allow_segments=False),
                per_shard)
            if any(t is None for t in tables):
                return None
            forced = {kw: max(getattr(t, attr) for t in tables)
                      for attr, kw in geom_keys.items()}

            def uniform(r):
                t = tables[r]
                if all(getattr(t, a) == forced[kw]
                       for a, kw in geom_keys.items()):
                    return t
                return builder(*per_shard[r][which], num_src,
                               allow_segments=False, **forced)

            tables = _in_parallel(uniform, range(len(tables)))
            if any(t is None for t in tables):
                return None  # a forced rebuild crossed the chunk ceiling
            c_max = max(t.row0.shape[0] for t in tables)
            padded = [pad_fn(t, c_max) for t in tables]
            stacked = [np.stack([p[i] for p in padded])
                       for i in range(len(padded[0]))]
            out = {"stacked": stacked, "num_out": num_out,
                   "src_rows": max(t.src_rows for t in tables),
                   "k": forced["k_rows"]}
            out.update(extra(tables[0]))
            return out

        def build_all(which, num_src, num_out):
            # num_super / num_tiles are identical across shards already
            # (the idx length is the padded uniform max_values /
            # max_sticks * dim_z on every shard). A wide cover takes at
            # least one chunk a super-tile, and the stacked tables cannot
            # split a launch: past the chunk limit it cannot fit.
            supers = -(-num_out // (gk.WIDE_P * gk.TILE))
            wide = supers <= gk.WIDE_SEG_CHUNK_LIMIT and build_uniform(
                which, num_src, num_out, gk.build_wide_gather_tables,
                gk.pad_wide_tables_to,
                {"kp_rows": "kp_rows", "span_rows": "k_rows"},
                lambda t0: {"kind": "wide", "kp": t0.kp_rows,
                            "p_tiles": t0.p_tiles,
                            "super_p1": t0.num_super + 1})
            return wide or build_uniform(
                which, num_src, num_out, gk.build_monotone_gather_tables,
                gk.pad_tables_to, {"span_rows": "k_rows"},
                lambda t0: {"kind": "narrow",
                            "tiles_p1": t0.num_tiles + 1},
            )

        # the two directions are independent too
        dec, cmp_ = _in_parallel(lambda args: build_all(*args),
                                [(0, mv, num_slots), (1, num_slots, mv)])
        if dec is None or cmp_ is None:
            logger.warning(
                "spfft_tpu: a shard's value order is too scattered for the "
                "Pallas compression kernel — using the slower XLA gather "
                "path (sort triplets with utils.workloads."
                "sort_triplets_stick_major for the fast path)")
            return
        self._pallas_dist = {
            "dec": dec, "cmp": cmp_,
            "stacked": dec["stacked"] + cmp_["stacked"],
            "n_dec": len(dec["stacked"]),  # wide = 5 tables, narrow = 4
        }
        self._pallas_interpret = not backend_ok

    @jax.named_scope("spfft.gather")
    def _pallas_gather(self, flat_il, t, tables):
        """Run the windowed gather (wide or narrow kernel) on one shard's
        (N, 2) interleaved data."""
        from ..ops import gather_kernel as gk
        shard_tabs = tuple(a[0] for a in tables)
        re, im = gk.planar_from_interleaved(
            flat_il.astype(np.float32), t["src_rows"])
        if t["kind"] == "wide":
            out_re, out_im = gk.wide_gather(
                re, im, *shard_tabs, span_rows=t["k"], kp_rows=t["kp"],
                p_tiles=t["p_tiles"], src_rows=t["src_rows"],
                num_super=t["super_p1"], interpret=self._pallas_interpret)
        else:
            out_re, out_im = gk.monotone_gather(
                re, im, *shard_tabs, span_rows=t["k"],
                src_rows=t["src_rows"], num_tiles=t["tiles_p1"],
                interpret=self._pallas_interpret)
        return gk.interleaved_from_planar(out_re, out_im, t["num_out"])

    def _fused_inactive_why(self, use_pallas: Optional[bool]) -> Optional[str]:
        """Shared activation envelope for BOTH distributed fused local
        stages (backward decompress+z-DFT, forward z-DFT+compress):
        returns the ``inactive:<why>`` introspection value when the
        fused kernels were never in play for this configuration — a
        by-design inactivity, reported through the fallback-reason
        properties but NOT counted as a plan fallback — or None when
        the builds should proceed to the real eligibility gates."""
        from ..ops import fused_kernel as fkm
        dp = self.dist_plan
        if not fkm.enabled():
            return "inactive:env_disabled"
        if not (jax.default_backend() == "tpu" or fkm.interpret_forced()):
            return "inactive:backend"
        if use_pallas is False:
            return "inactive:use_pallas_false"
        if self.precision != "single":
            return "inactive:precision"
        if dp.max_values == 0 or dp.max_sticks == 0:
            return "inactive:empty"
        if (use_pallas is None and not fkm.interpret_forced()
                and dp.max_values < 200_000):
            # below the kernel-vs-XLA crossover (_init_pallas)
            return "inactive:below_crossover"
        return None

    def _init_fused_dist(self, use_pallas: Optional[bool]) -> None:
        """Fused decompress + z-DFT tables for the distributed backward's
        local pre-exchange stage: one ``run_decompress_zdft`` launch
        replaces the decompress gather, the r2c (0,0)-stick hermitian
        completion AND ``stages.z_backward`` — the dense raw stick array
        never round-trips through HBM (the same fusion the local plan
        runs, ops/fused_kernel.py). Shape-uniform per-shard tables (a
        common DMA window height, chunk counts padded with no-op chunks
        routed to one dummy output super-tile) keep the SPMD body a
        single program. With ``overlap_chunks > 1`` one table set is
        built PER OVERLAP CHUNK (restricted to that chunk's stick rows)
        so the pipeline keeps one fused launch per chunk with each
        chunk's collective issued as its sticks emerge — the monolithic
        plan is simply the single-chunk case of the same build. Gated by
        the same eligibility/cost model as the local fusion; every
        decline that keeps an otherwise-kernel-ready plan on the
        two-launch path is recorded as a ``dist_fused_decompress_zdft``
        fallback reason."""
        from .. import obs as _obs
        from ..ops import dft as _dft
        from ..ops import fused_kernel as fkm
        from ..ops import gather_kernel as gk

        dp = self.dist_plan
        self._fused_dist = None
        self._fused_dist_reason = None
        self._fused_dist_inactive = self._fused_inactive_why(use_pallas)
        if self._fused_dist_inactive is not None:
            return
        backend_ok = jax.default_backend() == "tpu"
        ms, mv, dim_z = dp.max_sticks, dp.max_values, dp.dim_z

        def decline(reason: str) -> None:
            self._fused_dist_reason = reason
            _obs.record_plan_fallback("dist_fused_decompress_zdft", reason)
            logger.info(
                "spfft_tpu: distributed fused decompress+z-DFT kernel "
                "unavailable (%s) — keeping the two-launch backward",
                reason)

        if not _dft.use_matmul_dft(dim_z, np.dtype(np.complex64)):
            return decline("no_matmul_dft")
        reason = fkm.eligible_dim(dim_z)
        if reason:
            return decline(reason)
        num_slots = ms * dim_z
        per = [gk.compression_gather_inputs(p.value_indices, num_slots,
                                            pad_values_to=mv)[0]
               for p in dp.shard_plans]
        # One table set per overlap chunk, each restricted to the
        # chunk's stick rows [s0, s1). A chunk slice of a stick-major
        # monotone index sequence is itself monotone, and every chunk
        # launch reads from the SAME full-height planar value source,
        # so num_src stays mv throughout.
        bounds = (self._overlap.stick_bounds()
                  if self._overlap is not None else ((0, ms),))

        def build(r, s0, s1, k_rows=0):
            idx, valid = per[r]
            return gk.build_monotone_gather_tables(
                idx[s0 * dim_z:s1 * dim_z], valid[s0 * dim_z:s1 * dim_z],
                mv, k_rows=k_rows, allow_segments=False)

        chunk_tabs = []
        for s0, s1 in bounds:
            tabs = [build(r, s0, s1) for r in range(dp.num_shards)]
            if any(t is None for t in tabs):
                return decline("value_order")
            chunk_tabs.append(tabs)
        # force one DMA window height K across shards AND chunks
        # (selector words encode (row, lane, valid) independent of K, so
        # rebuilding the smaller-span sets under the max is exact)
        k_u = max(t.span_rows for tabs in chunk_tabs for t in tabs)
        chunk_tabs = [
            [t if t.span_rows == k_u else build(r, s0, s1, k_rows=k_u)
             for r, t in enumerate(tabs)]
            for (s0, s1), tabs in zip(bounds, chunk_tabs)]
        if any(t is None for tabs in chunk_tabs for t in tabs):
            return decline("value_order")
        # one padded planar source height feeds every chunk's launch
        src_rows = max(t.src_rows for tabs in chunk_tabs for t in tabs)
        chunks = []
        stacked_all: list = []
        for (s0, s1), tabs in zip(bounds, chunk_tabs):
            fused = []
            for r, t in enumerate(tabs):
                zid = (dp.shard_plans[r].zero_stick_id
                       if dp.hermitian else None)
                # hermitian completion is within-stick, so the zero
                # stick completes inside whichever chunk slices it
                zc = (zid - s0 if zid is not None and s0 <= zid < s1
                      else None)
                ft = fkm.build_fused_decompress_tables(
                    t, dim_z, s1 - s0, zero_stick_id=zc)
                if isinstance(ft, str):
                    return decline(ft)
                fused.append(ft)
            # num_super/p_tiles/r_sticks are uniform across shards (the
            # chunk's slot count (s1-s0)*dim_z is common); the
            # zero-stick owner differs, so non-owners get the
            # never-matching (-1) zinfo sentinel and the static
            # `complete` flag stays shard-invariant.
            complete = any(f.zinfo is not None for f in fused)
            num_super = fused[0].num_super
            c_max = max(f.row0.shape[0] for f in fused)

            def pad(f):
                p_ = c_max - f.row0.shape[0]
                # no-op padding chunks: all-invalid selector words gather
                # zeros, never first/last, and target the DUMMY
                # super-tile ``num_super`` so the flush-on-block-change
                # at the real->pad boundary lands outside the sliced
                # result.
                return (np.concatenate([f.row0, np.zeros(p_, np.int32)]),
                        np.concatenate([f.pos, np.zeros(p_, np.int32)]),
                        np.concatenate([f.sfirst, np.zeros(p_, np.int32)]),
                        np.concatenate([f.slast, np.zeros(p_, np.int32)]),
                        np.concatenate([f.sup,
                                        np.full(p_, num_super, np.int32)]),
                        np.concatenate([f.packed,
                                        np.zeros((p_, 8, 128), np.int32)]))

            padded = [pad(f) for f in fused]
            stacked = [np.stack([p_[i] for p_ in padded])
                       for i in range(6)]
            if complete:
                stacked.append(np.stack([
                    f.zinfo if f.zinfo is not None
                    else np.array([-1, 0], np.int32) for f in fused]))
            rep = dataclasses.replace(
                fused[0], row0=padded[0][0], pos=padded[0][1],
                sfirst=padded[0][2], slast=padded[0][3], sup=padded[0][4],
                packed=padded[0][5], num_super=num_super + 1,
                src_rows=src_rows, span_rows=k_u, num_sticks=s1 - s0,
                zinfo=(np.array([-1, 0], np.int32) if complete else None))
            chunks.append({"rep": rep, "t0": len(stacked_all),
                           "t1": len(stacked_all) + len(stacked),
                           "n_sticks": s1 - s0})
            stacked_all.extend(stacked)
        # the shape-uniform per-shard tables are the narrow decomposition
        _obs.record_fused_geometry("backward", "narrow")
        self._fused_dist = {
            "chunks": chunks, "stacked": stacked_all,
            "n_tabs": len(stacked_all), "src_rows": src_rows,
            "mats": fkm.commit_mats(_dft.c2c_mats(dim_z, _dft.BACKWARD)),
            "interpret": not backend_ok,
        }

    def _fused_bwd_chunk_sticks(self, vals, xtables):
        """Per-shard fused decompress + (0,0)-stick completion + z-IFFT,
        ONE ``run_decompress_zdft`` launch per overlap chunk (one total
        for monolithic plans): the drop-in for ``_decompress_shard``
        followed by ``_bwd_pre_exchange``. ``vals`` is (mv, 2)
        interleaved — or batched (B, mv, 2) through the batched kernel
        grid. Returns the list of per-chunk complex z-transformed stick
        arrays (..., stick_hi - stick_lo, dim_z), chunk order matching
        ``self._overlap.chunks``."""
        from ..ops import fused_kernel as fkm
        from ..ops import gather_kernel as gk
        fd = self._fused_dist
        ft = xtables[self._n_ptables + self._n_ctables:]
        tabs = ft[:fd["n_tabs"]]
        mats = ft[fd["n_tabs"]:fd["n_tabs"] + 3]      # replicated, as-is
        re, im = gk.planar_from_interleaved(vals.astype(np.float32),
                                            fd["src_rows"])
        out = []
        for ch in fd["chunks"]:
            # drop the shard axis on this chunk's table slice
            dev = tuple(a[0] for a in tabs[ch["t0"]:ch["t1"]])
            sr, si = fkm.run_decompress_zdft(re, im, dev, mats, ch["rep"],
                                             interpret=fd["interpret"])
            n = ch["n_sticks"]
            with jax.named_scope("spfft.decompress_z"):
                out.append((sr[..., :n, :]
                            + 1j * si[..., :n, :]).astype(self._cdt))
        return out

    def _fused_dec_zdft_shard(self, vals, xtables):
        """Monolithic (no-overlap) fused backward local stage: the
        single-chunk case of :meth:`_fused_bwd_chunk_sticks`."""
        return self._fused_bwd_chunk_sticks(vals, xtables)[0]

    def _init_fused_dist_fwd(self, use_pallas: Optional[bool]) -> None:
        """Fused z-DFT + compress tables for the distributed forward's
        local post-exchange stage: one ``run_zdft_compress`` launch
        replaces ``stages.z_forward`` + the compress gather — the dense
        z-transformed stick array never round-trips through HBM (the
        forward twin of :meth:`_init_fused_dist`, built with the same
        shape-uniform per-shard machinery: a common DMA window height,
        chunk counts padded with no-op chunks storing zeros into one
        dummy output tile). A z-stick needs exchanged planes from EVERY
        chunk, so this launch runs once, post-exchange; the chunked
        overlap pipeline upstream (xy + exchange) keeps its
        one-launch-per-chunk structure either way, which is why there is
        no ``overlap_chunks`` decline here. Declines that keep an
        otherwise-kernel-ready plan on the two-launch forward are
        recorded as ``dist_fused_zdft_compress`` fallback reasons."""
        from .. import obs as _obs
        from ..ops import dft as _dft
        from ..ops import fused_kernel as fkm
        from ..ops import gather_kernel as gk

        dp = self.dist_plan
        self._fused_dist_fwd = None
        self._fused_dist_fwd_reason = None
        if self._fused_inactive_why(use_pallas) is not None:
            return  # shared envelope, reported via _fused_dist_inactive
        backend_ok = jax.default_backend() == "tpu"
        ms, mv, dim_z = dp.max_sticks, dp.max_values, dp.dim_z

        def decline(reason: str) -> None:
            self._fused_dist_fwd_reason = reason
            _obs.record_plan_fallback("dist_fused_zdft_compress", reason)
            logger.info(
                "spfft_tpu: distributed fused z-DFT+compress kernel "
                "unavailable (%s) — keeping the two-launch forward",
                reason)

        if not _dft.use_matmul_dft(dim_z, np.dtype(np.complex64)):
            return decline("no_matmul_dft")
        reason = fkm.eligible_dim(dim_z)
        if reason:
            return decline(reason)
        num_slots = ms * dim_z
        per = [gk.compression_gather_inputs(p.value_indices, num_slots,
                                            pad_values_to=mv)[1]
               for p in dp.shard_plans]
        tables = [gk.build_monotone_gather_tables(idx, valid, num_slots,
                                                  allow_segments=False)
                  for idx, valid in per]
        if any(t is None for t in tables):
            return decline("value_order")
        # force one DMA window height K across shards (exact rebuild,
        # same argument as the backward build)
        k_u = max(t.span_rows for t in tables)
        tables = [t if t.span_rows == k_u else
                  gk.build_monotone_gather_tables(
                      per[r][0], per[r][1], num_slots, k_rows=k_u,
                      allow_segments=False)
                  for r, t in enumerate(tables)]
        if any(t is None for t in tables):
            return decline("value_order")
        fused = []
        for t in tables:
            ft = fkm.build_fused_compress_tables(t, dim_z, ms)
            if isinstance(ft, str):
                return decline(ft)
            fused.append(ft)
        # num_tiles and win_sticks are uniform (mv and the forced window
        # height are common); src_sticks and chunk counts differ, so pad
        # to the maxima with no-op chunks that store zeros into the
        # DUMMY output tile ``num_tiles`` (all-invalid selector words
        # gather zeros; first=1 so nothing accumulates onto garbage;
        # wnew=0 so they reuse the last window and DMA nothing).
        num_tiles = fused[0].num_tiles
        c_max = max(f.s0.shape[0] for f in fused)
        src_sticks = max(f.src_sticks for f in fused)

        def pad(f):
            p_ = c_max - f.s0.shape[0]
            return (np.concatenate([f.s0, np.zeros(p_, np.int32)]),
                    np.concatenate([f.off, np.zeros(p_, np.int32)]),
                    np.concatenate([f.out_tile,
                                    np.full(p_, num_tiles, np.int32)]),
                    np.concatenate([f.first, np.ones(p_, np.int32)]),
                    np.concatenate([f.wnew, np.zeros(p_, np.int32)]),
                    np.concatenate([f.packed,
                                    np.zeros((p_, 8, 128), np.int32)]))

        padded = [pad(f) for f in fused]
        stacked = [np.stack([p_[i] for p_ in padded]) for i in range(6)]
        rep = dataclasses.replace(
            fused[0], s0=padded[0][0], off=padded[0][1],
            out_tile=padded[0][2], first=padded[0][3], wnew=padded[0][4],
            packed=padded[0][5],
            num_tiles=num_tiles + 1, src_sticks=src_sticks,
            span_rows=k_u, num_out=mv)
        # UNSCALED forward matrices: Scaling.FULL stays the same
        # post-gather multiply the unfused _compress_shard applies, so
        # the fused forward is bit-identical to the unfused
        # z_forward+gather+scale path (folding the scale into the
        # matrix values would not be).
        _obs.record_fused_geometry("forward", "narrow")
        self._fused_dist_fwd = {
            "rep": rep, "stacked": stacked, "n_tabs": len(stacked),
            "mats": fkm.commit_mats(_dft.c2c_mats(dim_z, _dft.FORWARD)),
            "interpret": not backend_ok,
        }

    def _fused_zdft_cmp_shard(self, sticks, xtables, scaled: bool):
        """Per-shard fused z-FFT + compress gather: the drop-in for
        ``stages.z_forward`` followed by ``_compress_shard`` after the
        forward exchange. ``sticks`` are RAW (un-z-transformed) complex
        local sticks (..., max_sticks, dim_z); the exchange unpack fills
        padding rows with zeros, satisfying the kernel's
        rows-past-num_sticks-are-zero contract. Returns (..., mv, 2)
        interleaved real values."""
        from ..ops import fused_kernel as fkm
        from ..ops import gather_kernel as gk
        ff = self._fused_dist_fwd
        rep = ff["rep"]
        base = self._n_ptables + self._n_ctables + self._n_fb
        seg = xtables[base:base + self._n_ff]
        dev = tuple(a[0] for a in seg[:ff["n_tabs"]])  # drop the shard axis
        mats = seg[ff["n_tabs"]:ff["n_tabs"] + 3]      # replicated, as-is
        with jax.named_scope("spfft.layout"):
            sr = jnp.real(sticks).astype(jnp.float32)
            si = jnp.imag(sticks).astype(jnp.float32)
        sr, si = fkm.pad_sticks_planar(sr, si, rep.src_sticks)
        out_re, out_im = fkm.run_zdft_compress(sr, si, dev, mats, rep,
                                               interpret=ff["interpret"])
        values = gk.interleaved_from_planar(out_re, out_im, rep.num_out)
        with jax.named_scope("spfft.z_compress"):
            if scaled:
                values = values * jnp.asarray(1.0 / self.global_size,
                                              self._rdt)
            return values.astype(self._rdt)

    # -- SPMD bodies ---------------------------------------------------------
    # The exchange's own methods are its layout work, the collectives
    # inside carry ``spfft.exchange`` (parallel/exchange.py).
    @jax.named_scope("spfft.exchange_layout")
    def _exchange_freq_to_grid(self, sticks, zmap, col_inv, ctables):
        """z-sticks -> local plane grid across the mesh, via the selected
        exchange mechanism."""
        _faults.check_site("exchange.collective")  # trace time: per compile
        dp = self.dist_plan
        if self._ragged is not None:
            # sticks: (max_sticks, dim_z) or batched (B, max_sticks, dim_z)
            batch = sticks.shape[:-2]
            flat = sticks.reshape(batch + (-1,))
            buf = jnp.take(flat, ctables[0][0], axis=-1, mode="fill",
                           fill_value=0)
            offs = tuple(t[0] for t in ctables[4:8])
            recv = ragged_exchange(buf, offs, ctables[12][0],
                                   self._ragged.recv_cap, self.axis_name,
                                   self._ragged_emulate, self._wire_dtype)
            grid_flat = jnp.take(recv, ctables[1][0], axis=-1,
                                 mode="fill", fill_value=0)
            return grid_flat.reshape(batch + (dp.max_planes, dp.dim_y,
                                              self._xf_eff))
        if self._compact is not None:
            nb = len(self._compact.hop_sizes)
            flat = sticks.reshape(-1)
            bufs = [jnp.take(flat, t[0], mode="fill", fill_value=0)
                    for t in ctables[:nb]]
            recv = compact_exchange(bufs, self._compact.ops,
                                    dp.num_shards, self.axis_name,
                                    reverse=False,
                                    wire_real_dtype=self._wire_dtype)
            return jnp.take(recv, ctables[nb][0], mode="fill",
                            fill_value=0).reshape(dp.max_planes, dp.dim_y,
                                                  self._xf_eff)
        blocks = pack_freq_to_blocks(sticks, zmap)
        if dp.num_shards > 1:
            # comm-size-1 skips the collective entirely, like the
            # reference treating a 1-rank communicator as local
            # (grid_internal.cpp:182); the block transposes on a size-1
            # leading axis are layout no-ops (256^3 dist1 pair:
            # 20.2 -> 17.5 ms).
            blocks = self._exchange_fn(blocks, self.axis_name,
                                       self._wire_dtype, quant_axis=1)
        return unpack_blocks_to_grid(blocks, col_inv, dp.dim_y,
                                     self._xf_eff)

    @jax.named_scope("spfft.exchange_layout")
    def _exchange_grid_to_sticks(self, grid, cols_flat, z_src, ctables):
        """Local plane grid -> z-sticks across the mesh (forward mirror)."""
        _faults.check_site("exchange.collective")  # trace time: per compile
        dp = self.dist_plan
        if self._ragged is not None:
            batch = grid.shape[:-3]
            flat = grid.reshape(batch + (-1,))
            buf = jnp.take(flat, ctables[2][0], axis=-1, mode="fill",
                           fill_value=0)
            offs = tuple(t[0] for t in ctables[8:12])
            recv = ragged_exchange(buf, offs, ctables[13][0],
                                   self._ragged.recv_cap, self.axis_name,
                                   self._ragged_emulate, self._wire_dtype)
            sticks_flat = jnp.take(recv, ctables[3][0], axis=-1,
                                   mode="fill", fill_value=0)
            return sticks_flat.reshape(batch + (dp.max_sticks, dp.dim_z))
        if self._compact is not None:
            nb = len(self._compact.hop_sizes)
            flat = grid.reshape(-1)
            bufs = [jnp.take(flat, t[0], mode="fill", fill_value=0)
                    for t in ctables[nb + 1:2 * nb + 1]]
            recv = compact_exchange(bufs, self._compact.ops,
                                    dp.num_shards, self.axis_name,
                                    reverse=True,
                                    wire_real_dtype=self._wire_dtype)
            return jnp.take(recv, ctables[2 * nb + 1][0], mode="fill",
                            fill_value=0).reshape(dp.max_sticks, dp.dim_z)
        blocks = pack_space_to_blocks(grid, cols_flat, dp.num_shards,
                                      dp.max_sticks)
        if dp.num_shards > 1:
            # comm-size-1 local collapse (see _exchange_freq_to_grid)
            blocks = self._exchange_fn(blocks, self.axis_name,
                                       self._wire_dtype, quant_axis=2)
        return unpack_blocks_to_sticks(blocks, z_src)

    # -- chunk-pipelined exchange (compute/communication overlap) -----------
    # (the chunk slices and late unpacks are layout work too; the stages
    # inside carry scopes of their own)
    @jax.named_scope("spfft.exchange_layout")
    def _overlap_bwd_to_grid(self, sticks_raw, onehot_row, col_inv, zmap,
                             ctables, pre_chunks=None):
        """Backward overlap pipeline: per chunk, run stick symmetry +
        z-IFFT on the chunk's stick rows and ISSUE its collective
        immediately; unpack once, after every chunk's exchange has been
        issued (issue early, unpack late). The loop builds K independent
        compute->collective chains — the dependence structure XLA's
        latency-hiding scheduler needs to split each collective into an
        async start/done pair and run chunk i's z-stage during chunk
        i-1's wire time. Batch-aware for the ragged kind only (batch
        dims lead, collectives carry them trailing); block/compact
        batched callers vmap the whole per-example tail instead.

        ``pre_chunks`` (the fused pipeline) supplies the per-chunk
        z-transformed stick arrays directly — one fused
        decompress+z-DFT launch per chunk has already replaced the
        slice + stick symmetry + z-IFFT — so the loop only packs and
        issues each chunk's collective."""
        ov = self._overlap
        dp = self.dist_plan
        batch = (pre_chunks[0].shape[:-2] if pre_chunks is not None
                 else sticks_raw.shape[:-2])
        recvs = []
        for c, ch in enumerate(ov.chunks):
            _faults.check_site("exchange.chunk")  # trace: once per chunk
            if pre_chunks is not None:
                s_c = pre_chunks[c]
            else:
                s_c = sticks_raw[..., ch.stick_lo:ch.stick_hi, :]
                oh_c = onehot_row[ch.stick_lo:ch.stick_hi]
                if batch:
                    s_c = jax.vmap(
                        lambda s, oh=oh_c:
                        self._bwd_pre_exchange(s, oh))(s_c)
                else:
                    s_c = self._bwd_pre_exchange(s_c, oh_c)
            if ov.kind == "block":
                blocks = pack_freq_to_blocks(s_c, zmap)
                if dp.num_shards > 1:
                    # int8 quant rows = sticks (axis 1): the chunk slice
                    # axis, so per-chunk scale sidecars partition the
                    # monolithic one exactly at every K
                    blocks = self._exchange_fn(blocks, self.axis_name,
                                               self._wire_dtype,
                                               quant_axis=1)
                recvs.append(blocks)
                continue
            flat = s_c.reshape(batch + (-1,))
            sl = self._ov_slices[c]
            if ov.kind == "ragged":
                buf = jnp.take(flat, ctables[sl["bwd_pack"]][0], axis=-1,
                               mode="fill", fill_value=0)
                offs = tuple(t[0] for t in
                             ctables[sl["offs_b"][0]:sl["offs_b"][1]])
                recvs.append(ragged_exchange(
                    buf, offs, ctables[sl["emu_bwd"]][0], ch.recv_cap,
                    self.axis_name, self._ragged_emulate,
                    self._wire_dtype))
            else:  # compact ppermute chunk (unbatched by contract)
                lo, hi = sl["bwd_ops"]
                bufs = [jnp.take(flat, ctables[i][0], mode="fill",
                                 fill_value=0) for i in range(lo, hi)]
                recvs.append(compact_exchange(
                    bufs, ch.bwd_ops, dp.num_shards, self.axis_name,
                    reverse=False, wire_real_dtype=self._wire_dtype))
        if ov.kind == "block":
            # received chunk blocks are contiguous stick-row slices of
            # the monolithic (S, max_sticks, max_planes) block
            blocks = jnp.concatenate(recvs, axis=1)
            return unpack_blocks_to_grid(blocks, col_inv, dp.dim_y,
                                         self._xf_eff)
        recv = jnp.concatenate(recvs, axis=-1)
        grid_flat = jnp.take(recv, ctables[-2][0], axis=-1, mode="fill",
                             fill_value=0)
        return grid_flat.reshape(batch + (dp.max_planes, dp.dim_y,
                                          self._xf_eff))

    @jax.named_scope("spfft.exchange_layout")
    def _overlap_fwd_to_sticks(self, space, cols_flat, z_src, ctables):
        """Forward overlap pipeline (the backward's mirror): per chunk,
        xy-FFT the chunk's plane rows and issue its collective; one late
        unpack reassembles the full-z local sticks. Batch-aware for the
        ragged kind only, like :meth:`_overlap_bwd_to_grid`."""
        ov = self._overlap
        dp = self.dist_plan
        nd_slab = 3 if dp.hermitian else 4   # (planes, Y, X[, 2])
        batch = space.shape[:-nd_slab]
        axis = space.ndim - nd_slab
        recvs = []
        for c, ch in enumerate(ov.chunks):
            _faults.check_site("exchange.chunk")  # trace: once per chunk
            s_c = jax.lax.slice_in_dim(space, ch.plane_lo, ch.plane_hi,
                                       axis=axis)
            g_c = (jax.vmap(self._fwd_pre_exchange)(s_c) if batch
                   else self._fwd_pre_exchange(s_c))
            if ov.kind == "block":
                blocks = pack_space_to_blocks(g_c, cols_flat,
                                              dp.num_shards,
                                              dp.max_sticks)
                if dp.num_shards > 1:
                    # forward chunks slice planes (axis 2) — the int8
                    # quant axis follows, keeping scale-sidecar bytes
                    # conserved at every K (mirror of the backward)
                    blocks = self._exchange_fn(blocks, self.axis_name,
                                               self._wire_dtype,
                                               quant_axis=2)
                recvs.append(blocks)
                continue
            flat = g_c.reshape(batch + (-1,))
            sl = self._ov_slices[c]
            if ov.kind == "ragged":
                buf = jnp.take(flat, ctables[sl["fwd_pack"]][0], axis=-1,
                               mode="fill", fill_value=0)
                offs = tuple(t[0] for t in
                             ctables[sl["offs_f"][0]:sl["offs_f"][1]])
                recvs.append(ragged_exchange(
                    buf, offs, ctables[sl["emu_fwd"]][0], ch.recv_cap,
                    self.axis_name, self._ragged_emulate,
                    self._wire_dtype))
            else:
                lo, hi = sl["fwd_ops"]
                bufs = [jnp.take(flat, ctables[i][0], mode="fill",
                                 fill_value=0) for i in range(lo, hi)]
                recvs.append(compact_exchange(
                    bufs, ch.fwd_ops, dp.num_shards, self.axis_name,
                    reverse=False, wire_real_dtype=self._wire_dtype))
        if ov.kind == "block":
            # chunk blocks are contiguous plane slices of the monolithic
            # (S, max_sticks, max_planes) block
            blocks = jnp.concatenate(recvs, axis=2)
            return unpack_blocks_to_sticks(blocks, z_src)
        recv = jnp.concatenate(recvs, axis=-1)
        sticks_flat = jnp.take(recv, ctables[-1][0], axis=-1,
                               mode="fill", fill_value=0)
        return sticks_flat.reshape(batch + (dp.max_sticks, dp.dim_z))

    def _decompress_shard(self, values_il, slot_src, ptables):
        """Per-shard decompress: (mv, 2) -> (max_sticks, dim_z) sticks —
        or batched (B, mv, 2) -> (B, max_sticks, dim_z) through the same
        kernel tables (batched pallas grid / vmapped XLA gather)."""
        dp = self.dist_plan
        if self._pallas_dist is not None:
            dec_il = self._pallas_gather(
                values_il, self._pallas_dist["dec"],
                ptables[:self._pallas_dist["n_dec"]])
            with jax.named_scope("spfft.layout"):
                flat = dec_il[..., 0] + 1j * dec_il[..., 1]
                return flat.reshape(values_il.shape[:-2]
                                    + (dp.max_sticks, dp.dim_z))
        dec = lambda v: stages.decompress(v.astype(self._rdt), slot_src[0],
                                          dp.max_sticks, dp.dim_z)
        if values_il.ndim == 3:
            return jax.vmap(dec)(values_il)
        return dec(values_il)

    def _bwd_pre_exchange(self, sticks, onehot_row):
        """Stick symmetry + z-IFFT (the per-example half before the
        exchange; batched callers vmap this). ``onehot_row`` is the
        per-shard (max_sticks,) mask row — the overlap pipeline passes
        chunk SLICES of both arguments (the stages are per-stick
        independent, so a row slice is exact)."""
        _faults.check_site("exchange.pack")  # trace time: per compile
        dp = self.dist_plan
        if dp.hermitian:
            # Complete every stick, then blend by the one-hot (0,0)-stick
            # mask — SPMD-safe stand-in for the reference's "owner rank
            # applies StickSymmetry" branch (execution_host.cpp:306-308).
            with jax.named_scope("spfft.stick_symmetry"):
                completed = jax.vmap(stages.complete_stick_hermitian)(
                    sticks)
                oh = onehot_row[:, None].astype(self._rdt)
                sticks = sticks * (1 - oh) + completed * oh
        return stages.z_backward(sticks)

    def _bwd_post_exchange(self, grid):
        """Plane symmetry + xy-IFFT (after the exchange)."""
        _faults.check_site("exchange.unpack")  # trace time: per compile
        dp = self.dist_plan
        if dp.hermitian:
            if self._split_x is not None:
                x0, _ = self._split_x
                if x0 == 0:
                    grid = stages.complete_plane_hermitian(grid)
                with jax.named_scope("spfft.xy"):
                    return stages.xy_backward_r2c_split(
                        grid, x0, dp.dim_x, dp.dim_x_freq)
            grid = stages.complete_plane_hermitian(grid)
            with jax.named_scope("spfft.xy"):
                return stages.xy_backward_r2c(grid, dp.dim_x)
        with jax.named_scope("spfft.xy"):
            if self._split_x is not None:
                x0, _ = self._split_x
                space = stages.xy_backward_c2c_split(grid, x0, dp.dim_x)
            else:
                space = stages.xy_backward_c2c(grid)
        with jax.named_scope("spfft.grid_io"):
            return complex_to_interleaved(space)

    def _backward_tail(self, sticks, onehot, col_inv, zmap, ctables):
        """Per-shard pipeline after decompress: symmetry, z-IFFT, exchange,
        plane symmetry, xy-IFFT. Input (max_sticks, dim_z); output the
        per-shard space slab (unbatched — batched callers vmap the
        pre/post halves and run the exchange batch-natively, see
        _backward_body_batched). With ``overlap_chunks > 1`` the z-stage
        and exchange run CHUNK-PIPELINED instead (parallel/overlap.py)."""
        if self._overlap is not None:
            grid = self._overlap_bwd_to_grid(sticks, onehot[0], col_inv,
                                             zmap, ctables)
        else:
            sticks = self._bwd_pre_exchange(sticks, onehot[0])
            grid = self._exchange_freq_to_grid(sticks, zmap, col_inv,
                                               ctables)
        return self._bwd_post_exchange(grid)

    def _backward_body(self, values_il, vi, slot_src, onehot, cols_flat,
                       col_inv, zmap, z_src, conj_mult, *xtables):
        ptables = xtables[:self._n_ptables]
        ctables = xtables[self._n_ptables:self._n_ptables + self._n_ctables]
        with jax.named_scope("spfft.value_conj"):
            vals = values_il[0]
            if self._has_conj:  # conjugate the folded hermitian mirrors
                vals = vals * conj_mult[0]
        if self._fused_dist is not None:
            # decompress + stick symmetry + z-IFFT in ONE kernel launch
            # per overlap chunk (one total for monolithic plans), each
            # chunk's collective issued as its fused launch completes
            if self._overlap is not None:
                pre = self._fused_bwd_chunk_sticks(vals, xtables)
                grid = self._overlap_bwd_to_grid(None, None, col_inv,
                                                 zmap, ctables,
                                                 pre_chunks=pre)
            else:
                sticks_z = self._fused_dec_zdft_shard(vals, xtables)
                grid = self._exchange_freq_to_grid(sticks_z, zmap,
                                                   col_inv, ctables)
            space = self._bwd_post_exchange(grid)
        else:
            sticks = self._decompress_shard(vals, slot_src, ptables)
            space = self._backward_tail(sticks, onehot, col_inv, zmap,
                                        ctables)
        with jax.named_scope("spfft.grid_io"):
            return space[None]

    def _backward_body_batched(self, values_il, vi, slot_src, onehot,
                               cols_flat, col_inv, zmap, z_src, conj_mult,
                               *xtables):
        """Batched SPMD body: data carries a per-shard batch axis
        (1, B, ...); compression runs ONE batched-grid kernel launch, the
        rest of the pipeline (collectives included) is vmapped over B —
        the distributed analogue of the local plan's fused batch
        (reference interleaves N transforms by hand,
        multi_transform_internal.hpp:47-94)."""
        ptables = xtables[:self._n_ptables]
        ctables = xtables[self._n_ptables:self._n_ptables + self._n_ctables]
        with jax.named_scope("spfft.value_conj"):
            vals_b = values_il[0]
            if self._has_conj:  # (B, mv, 2) * (mv, 2) broadcasts over B
                vals_b = vals_b * conj_mult[0]
        space_b = self._backward_batched_tail(vals_b, slot_src, onehot,
                                              col_inv, zmap, ptables,
                                              ctables, xtables)
        with jax.named_scope("spfft.grid_io"):
            return space_b[None]

    def _backward_batched_tail(self, vals_b, slot_src, onehot, col_inv,
                               zmap, ptables, ctables, xtables):
        """The batched backward after the value conjugation: (B, mv, 2)
        values -> (B, planes, ...) slabs."""
        if self._fused_dist is not None:
            # one batched-grid fused launch per chunk covers decompress
            # + symmetry + z-IFFT for the whole batch
            if self._overlap is not None:
                pre_b = self._fused_bwd_chunk_sticks(vals_b, xtables)
                if self._overlap.kind == "ragged":
                    # ragged collectives carry the batch trailing
                    grid_b = self._overlap_bwd_to_grid(
                        None, None, col_inv, zmap, ctables,
                        pre_chunks=pre_b)
                else:
                    # block/compact exchange per example: batched fused
                    # launches first, then the pack/exchange/unpack tail
                    # vmapped over the per-chunk stick arrays
                    grid_b = jax.vmap(
                        lambda *cs: self._overlap_bwd_to_grid(
                            None, None, col_inv, zmap, ctables,
                            pre_chunks=cs))(*pre_b)
                return jax.vmap(self._bwd_post_exchange)(grid_b)
            sticks_zb = self._fused_dec_zdft_shard(vals_b, xtables)
            if self._ragged is not None:
                grid_b = self._exchange_freq_to_grid(sticks_zb, zmap,
                                                     col_inv, ctables)
            else:
                grid_b = jax.vmap(
                    lambda s: self._exchange_freq_to_grid(
                        s, zmap, col_inv, ctables))(sticks_zb)
            return jax.vmap(self._bwd_post_exchange)(grid_b)
        sticks_b = self._decompress_shard(vals_b, slot_src, ptables)
        if self._overlap is not None and self._overlap.kind == "ragged":
            # chunk loop identical to the unbatched path; each chunk's
            # collective carries the batch as trailing dims
            # (_overlap_bwd_to_grid is batch-aware for the ragged kind)
            grid_b = self._overlap_bwd_to_grid(sticks_b, onehot[0],
                                               col_inv, zmap, ctables)
            return jax.vmap(self._bwd_post_exchange)(grid_b)
        if self._ragged is not None:
            # ragged_all_to_all has no vmap batching rule: vmap the
            # per-example halves, run ONE collective with the batch as a
            # trailing dimension (exchange.ragged_exchange)
            s2 = jax.vmap(
                lambda s: self._bwd_pre_exchange(s, onehot[0]))(sticks_b)
            grid_b = self._exchange_freq_to_grid(s2, zmap, col_inv,
                                                 ctables)
            return jax.vmap(self._bwd_post_exchange)(grid_b)
        # block/compact overlap flows through the vmapped per-example
        # tail (a2a/ppermute have batching rules), like the monolithic
        # non-ragged mechanisms
        return jax.vmap(
            lambda s: self._backward_tail(s, onehot, col_inv, zmap,
                                          ctables))(sticks_b)

    def _fwd_pre_exchange(self, space):
        """xy-FFT (the per-example half before the forward exchange)."""
        _faults.check_site("exchange.pack")  # trace time: per compile
        dp = self.dist_plan
        with jax.named_scope("spfft.grid_io"):
            if dp.hermitian:
                grid = space.astype(self._rdt)
            else:
                grid = interleaved_to_complex(space).astype(self._cdt)
        with jax.named_scope("spfft.xy"):
            if dp.hermitian:
                if self._split_x is not None:
                    x0, w = self._split_x
                    return stages.xy_forward_r2c_split(grid, x0, w)
                return stages.xy_forward_r2c(grid)
            if self._split_x is not None:
                x0, w = self._split_x
                return stages.xy_forward_c2c_split(grid, x0, w)
            return stages.xy_forward_c2c(grid)

    def _forward_head_raw(self, space, cols_flat, z_src, ctables):
        """Per-shard forward pipeline up to (not including) the z-stage:
        xy-FFT + exchange, output RAW (un-z-transformed) local sticks
        (max_sticks, dim_z) — the seam the fused z-DFT+compress kernel
        joins at. With ``overlap_chunks > 1`` the xy-stage and exchange
        run chunk-pipelined (the forward mirror of the backward
        overlap)."""
        if self._overlap is not None:
            return self._overlap_fwd_to_sticks(space, cols_flat, z_src,
                                               ctables)
        grid = self._fwd_pre_exchange(space)
        return self._exchange_grid_to_sticks(grid, cols_flat, z_src,
                                             ctables)

    def _forward_head(self, space, cols_flat, z_src, ctables):
        """Per-shard pipeline before compress: xy-FFT, exchange, z-FFT.
        Input the per-shard space slab; output (max_sticks, dim_z)."""
        return stages.z_forward(
            self._forward_head_raw(space, cols_flat, z_src, ctables))

    def _compress_shard(self, sticks, vi, ptables, scaled: bool):
        """Per-shard compress: (max_sticks, dim_z) -> (mv, 2) values —
        or batched (B, ...) -> (B, mv, 2)."""
        scale = 1.0 / self.global_size if scaled else None
        batch = sticks.shape[:-2]
        # vi carries the sentinel max_sticks*dim_z for value padding
        with jax.named_scope("spfft.layout"):
            flat = jnp.stack([jnp.real(sticks).reshape(batch + (-1,)),
                              jnp.imag(sticks).reshape(batch + (-1,))],
                             axis=-1)
        if self._pallas_dist is not None:
            values = self._pallas_gather(
                flat, self._pallas_dist["cmp"],
                ptables[self._pallas_dist["n_dec"]:])
        else:
            with jax.named_scope("spfft.compress"):
                if flat.ndim == 3:
                    values = jax.vmap(
                        lambda f: stages.gather_rows_with_sentinel(
                            f, vi[0]))(flat)
                else:
                    values = stages.gather_rows_with_sentinel(flat, vi[0])
        with jax.named_scope("spfft.compress"):
            if scale is not None:
                values = values * jnp.asarray(scale, self._rdt)
            return values

    def _forward_body(self, space, vi, slot_src, onehot, cols_flat, col_inv,
                      zmap, z_src, conj_mult, *xtables, scaled: bool):
        ptables = xtables[:self._n_ptables]
        ctables = xtables[self._n_ptables:self._n_ptables + self._n_ctables]
        with jax.named_scope("spfft.grid_io"):
            slab = space[0]
        if self._fused_dist_fwd is not None:
            # post-exchange z-FFT + compress gather in ONE kernel launch
            raw = self._forward_head_raw(slab, cols_flat, z_src, ctables)
            values = self._fused_zdft_cmp_shard(raw, xtables, scaled)
        else:
            sticks = self._forward_head(slab, cols_flat, z_src, ctables)
            values = self._compress_shard(sticks, vi, ptables, scaled)
        with jax.named_scope("spfft.value_conj"):
            if self._has_conj:  # folded mirrors leave conjugated
                values = values * conj_mult[0]
            return values[None]

    def _forward_body_batched(self, space, vi, slot_src, onehot, cols_flat,
                              col_inv, zmap, z_src, conj_mult, *xtables,
                              scaled: bool):
        ptables = xtables[:self._n_ptables]
        ctables = xtables[self._n_ptables:self._n_ptables + self._n_ctables]
        with jax.named_scope("spfft.grid_io"):
            slab_b = space[0]
        values_b = self._forward_batched_values(slab_b, vi, ptables,
                                                cols_flat, z_src, ctables,
                                                xtables, scaled)
        with jax.named_scope("spfft.value_conj"):
            if self._has_conj:
                values_b = values_b * conj_mult[0]
            return values_b[None]

    def _forward_batched_values(self, slab_b, vi, ptables, cols_flat, z_src,
                                ctables, xtables, scaled: bool):
        """The batched forward up to the value conjugation: (B, planes,
        ...) slabs -> (B, mv, 2) values."""
        if self._fused_dist_fwd is not None:
            # raw sticks assembled batched through the same exchange
            # structure as the unfused branches below, then ONE
            # batched-grid fused z-FFT+compress launch replaces
            # z_forward + the gather
            if (self._overlap is not None
                    and self._overlap.kind == "ragged"):
                raw_b = self._overlap_fwd_to_sticks(slab_b, cols_flat,
                                                    z_src, ctables)
            elif self._ragged is not None:
                grid_b = jax.vmap(self._fwd_pre_exchange)(slab_b)
                raw_b = self._exchange_grid_to_sticks(grid_b, cols_flat,
                                                      z_src, ctables)
            else:
                raw_b = jax.vmap(
                    lambda s: self._forward_head_raw(
                        s, cols_flat, z_src, ctables))(slab_b)
            return self._fused_zdft_cmp_shard(raw_b, xtables, scaled)
        if self._overlap is not None and self._overlap.kind == "ragged":
            # chunked forward with the batch on the collectives'
            # trailing dims (_overlap_fwd_to_sticks is batch-aware)
            sticks_b = stages.z_forward(self._overlap_fwd_to_sticks(
                slab_b, cols_flat, z_src, ctables))
        elif self._ragged is not None:
            # batch rides the collective's trailing dims (see
            # _backward_body_batched)
            grid_b = jax.vmap(self._fwd_pre_exchange)(slab_b)
            sticks_b = stages.z_forward(self._exchange_grid_to_sticks(
                grid_b, cols_flat, z_src, ctables))
        else:
            sticks_b = jax.vmap(
                lambda s: self._forward_head(s, cols_flat, z_src,
                                             ctables))(slab_b)
        return self._compress_shard(sticks_b, vi, ptables, scaled)

    def _pair_shmap(self, n_fn_args: int):
        """shard_map wrapper for the fused-pair entry points: base specs
        plus one sharded spec per fn_arg."""
        return functools.partial(
            jax.shard_map, mesh=self.mesh,
            in_specs=self._base_in_specs
            + (P(self.axis_name),) * n_fn_args,
            out_specs=P(self.axis_name), check_vma=self._check_vma)

    def _pair_body(self, values_il, vi, slot_src, onehot, cols_flat,
                   col_inv, zmap, z_src, conj_mult, *rest, scaled: bool, fn):
        n_tab = self._n_ptables + self._n_ctables + self._n_ftables
        xtables, fn_args = rest[:n_tab], rest[n_tab:]
        space = self._backward_body(values_il, vi, slot_src, onehot,
                                    cols_flat, col_inv, zmap, z_src,
                                    conj_mult, *xtables)
        if fn is not None:
            space = fn(space, *fn_args)
        return self._forward_body(space, vi, slot_src, onehot, cols_flat,
                                  col_inv, zmap, z_src, conj_mult, *xtables,
                                  scaled=scaled)

    def apply_pointwise(self, values, fn=None, *fn_args,
                        scaling: Scaling = Scaling.NONE):
        """backward → ``fn(space, *fn_args)`` → forward as ONE fused SPMD
        executable (both collectives inside a single program, so XLA can
        overlap the exchanges with neighbouring compute).

        ``fn`` runs *per shard inside shard_map* on the padded local slab
        — shape ``(1, max_planes, dim_y, dim_x, 2)`` interleaved for C2C,
        ``(1, max_planes, dim_y, dim_x)`` real for R2C; rows at and beyond
        the shard's true ``num_planes`` are padding and whatever ``fn``
        writes there is ignored (the z-selection tables read true planes
        only — tested in test_distributed.py). Each ``fn_args`` entry is a
        sharded array over the mesh axis (leading dim ``num_shards``),
        split like the data — the way to feed a shard-dependent operator
        (e.g. a potential field laid out as padded slabs) or step-varying
        data without recompiling.

        The compiled SPMD program is cached per ``(fn, scaling)`` by object
        identity: pass a stable callable, not a fresh lambda per call.
        Returns the padded sharded values array."""
        scaling = Scaling(scaling)
        if not isinstance(values, jax.Array):
            values = self.shard_values(values)
        if self._local1 is not None:
            with timed_transform("apply_pointwise") as box:
                box.value = self._local1.apply_pointwise(
                    values[0], self._local1_fn(fn), *fn_args,
                    scaling=scaling)[None]
            return box.value
        key = (fn, scaling, len(fn_args))
        jitted = self._pair_jits.get(key)
        if jitted is None:
            jitted = jax.jit(self._pair_shmap(len(fn_args))(
                _entry(f"_pair_body_{scaling.name.lower()}",
                       self._pair_body, scaled=(scaling is Scaling.FULL),
                       fn=fn)))
            self._pair_jits[key] = jitted
        with timed_transform("apply_pointwise") as box:
            box.value = jitted(values, *self._device_tables, *fn_args)
        return box.value

    def iterate_pointwise(self, values, fn, *fn_args, steps: int,
                          scaling: Scaling = Scaling.FULL):
        """``steps`` fused distributed round trips as ONE SPMD executable
        (``lax.scan`` inside shard_map — 2·steps collectives in a single
        program, one dispatch). Semantics as :meth:`apply_pointwise`;
        ``scaling`` defaults to FULL so the iteration is a fixed-point map.
        Returns the final padded sharded values array."""
        scaling = Scaling(scaling)
        if not isinstance(values, jax.Array):
            values = self.shard_values(values)
        if self._local1 is not None:
            with timed_transform("iterate_pointwise") as box:
                box.value = self._local1.iterate_pointwise(
                    values[0], self._local1_fn(fn), *fn_args, steps=steps,
                    scaling=scaling)[None]
            return box.value
        # scan carry dtype must match the step output (_rdt)
        values = values.astype(self._rdt)
        key = (fn, scaling, int(steps), "scan", len(fn_args))
        jitted = self._pair_jits.get(key)
        if jitted is None:
            scaled = scaling is Scaling.FULL

            def run_body(values_il, vi, slot_src, onehot, cols_flat,
                         col_inv, zmap, z_src, *rest):
                def step(v, _):
                    return self._pair_body(
                        v, vi, slot_src, onehot, cols_flat, col_inv, zmap,
                        z_src, *rest, scaled=scaled, fn=fn), None
                out, _ = jax.lax.scan(step, values_il, None,
                                      length=int(steps))
                return out

            jitted = jax.jit(self._pair_shmap(len(fn_args))(run_body))
            self._pair_jits[key] = jitted
        with timed_transform("iterate_pointwise") as box:
            box.value = jitted(values, *self._device_tables, *fn_args)
        return box.value

    # -- getters (reference transform.hpp:91-171) ---------------------------
    @property
    def transform_type(self) -> TransformType:
        return self.dist_plan.transform_type

    @property
    def dim_x(self) -> int:
        return self.dist_plan.dim_x

    @property
    def dim_y(self) -> int:
        return self.dist_plan.dim_y

    @property
    def dim_z(self) -> int:
        return self.dist_plan.dim_z

    @property
    def global_size(self) -> int:
        return self.dim_x * self.dim_y * self.dim_z

    @property
    def num_global_elements(self) -> int:
        return self.dist_plan.num_global_elements

    def local_z_length(self, shard: int) -> int:
        return self.dist_plan.num_planes[shard]

    def local_z_offset(self, shard: int) -> int:
        return self.dist_plan.plane_offsets[shard]

    def local_slice_size(self, shard: int) -> int:
        return self.dim_x * self.dim_y * self.local_z_length(shard)

    def num_local_elements(self, shard: int) -> int:
        return self.dist_plan.shard_plans[shard].num_values

    @property
    def fused_dist_active(self) -> bool:
        """True when BOTH distributed fused local stages run: the
        backward decompress + r2c stick symmetry + z-IFFT (one Pallas
        launch per overlap chunk) AND the forward z-FFT + compress
        gather (one post-exchange launch)."""
        return (self._fused_dist is not None
                and self._fused_dist_fwd is not None)

    @property
    def fused_dist_bwd_active(self) -> bool:
        """True when the backward's local pre-exchange stage (decompress
        + r2c stick symmetry + z-IFFT) runs as fused Pallas launches
        (one per overlap chunk)."""
        return self._fused_dist is not None

    @property
    def fused_dist_fwd_active(self) -> bool:
        """True when the forward's local post-exchange stage (z-FFT +
        compress gather) runs as ONE fused Pallas launch."""
        return self._fused_dist_fwd is not None

    @property
    def fused_dist_fallback_reason(self) -> Optional[str]:
        """Why the fused backward pre-exchange stage is not running:
        None when active; a decline reason (also recorded under
        ``dist_fused_decompress_zdft`` in obs) on an
        otherwise-kernel-ready plan; an ``inactive:<why>`` value when
        the fused kernels were never in play for this configuration
        (by design — not a fallback, so not counted in obs)."""
        if self._fused_dist is not None:
            return None
        return self._fused_dist_reason or self._fused_dist_inactive

    @property
    def fused_dist_fwd_fallback_reason(self) -> Optional[str]:
        """Forward-twin analogue of :attr:`fused_dist_fallback_reason`
        (decline reasons recorded under ``dist_fused_zdft_compress``)."""
        if self._fused_dist_fwd is not None:
            return None
        return self._fused_dist_fwd_reason or self._fused_dist_inactive

    def _wire_elem_bytes(self) -> int:
        elem = np.dtype(self._cdt).itemsize
        if self._wire_dtype is not None:
            # int8 rung: 2 bytes per complex element (re+im quantized);
            # the per-stick scale sidecar is counted separately
            # (_wire_scale_bytes), not folded into the element size.
            elem = 2 * np.dtype(self._wire_dtype).itemsize
        return elem

    def _wire_scale_bytes(self, forward: bool, busiest: bool = False) -> int:
        """int8 scale-sidecar bytes for ONE exchange: one f32 absmax
        scale per (destination slot, quant row), quant rows being sticks
        backward / planes forward — the overlap chunk-slice axes, so the
        total is conserved at every K (OverlapSchedule.scale_rows is the
        per-chunk decomposition). Zero on every other rung."""
        if (self._wire_dtype is None
                or np.dtype(self._wire_dtype) != np.dtype(np.int8)):
            return 0
        dp = self.dist_plan
        rows = dp.max_planes if forward else dp.max_sticks
        links = ((dp.num_shards - 1) if busiest
                 else dp.num_shards * (dp.num_shards - 1))
        return links * rows * 4

    def exchange_wire_bytes(self, forward: bool = False) -> int:
        """TOTAL off-shard bytes (summed over all shards) for ONE exchange
        under the selected mechanism — the aggregate-ICI-traffic model (the
        quantity the reference's Alltoallv layout exists to minimise,
        transpose_mpi_compact_buffered_host.cpp:83-105). Padded layouts
        ship ``S * (S-1) * max_sticks * max_planes`` complex elements
        regardless of the distribution; the compact schedule's size-classed
        ops track the true per-pair counts. See
        :meth:`exchange_busiest_link_bytes` for the bottleneck-link view."""
        dp = self.dist_plan
        elem = self._wire_elem_bytes()
        if self._overlap is not None and self._overlap.kind != "block":
            # chunking conserves wire elements exactly (overlap.py);
            # block-kind overlap ships the padded rows and falls through
            return self._overlap.wire_elements() * elem
        if self._ragged is not None:
            return self._ragged.wire_elements() * elem  # exact Alltoallv
        if self._compact is not None:
            return self._compact.wire_elements() * elem
        return (dp.num_shards * (dp.num_shards - 1)
                * dp.max_sticks * dp.max_planes * elem
                + self._wire_scale_bytes(forward))

    def exchange_busiest_link_bytes(self, forward: bool = False) -> int:
        """Max over shards of max(sent, received) off-shard bytes for ONE
        exchange — the bottleneck-link model. A shard that genuinely owns
        most of the slab receives that payload under ANY exact layout, so
        plane-skew savings show up in :meth:`exchange_wire_bytes`
        (aggregate), not here; stick-skew savings show up in both."""
        dp = self.dist_plan
        elem = self._wire_elem_bytes()
        if self._overlap is not None and self._overlap.kind != "block":
            return self._overlap.busiest_link_elements() * elem
        if self._ragged is not None:
            return self._ragged.busiest_link_elements() * elem
        if self._compact is not None:
            return self._compact.busiest_link_elements() * elem
        return ((dp.num_shards - 1) * dp.max_sticks * dp.max_planes * elem
                + self._wire_scale_bytes(forward, busiest=True))

    def estimated_device_bytes(self) -> int:
        """Approximate resident bytes this plan pins for its lifetime:
        the committed device tables (sharded across the mesh, counted
        whole). Same contract as the local plan's method — the serving
        plan registry's byte-aware LRU reads it on ``put`` (even though
        distributed plans are rejected at ``submit``; see
        errors.DistributedPlanUnsupportedError)."""
        leaves = jax.tree_util.tree_leaves(self._device_tables)
        return sum(int(getattr(leaf, "nbytes", 0)) for leaf in leaves)

    def check_build(self, wait: bool = False) -> None:
        """The local plan's readiness check. A distributed plan builds
        every table in the foreground, so there is no builder to
        surface; ``wait=True`` blocks until the tables committed at
        plan time are on the devices."""
        if wait:
            jax.block_until_ready(self._device_tables)

    # -- data movement helpers ----------------------------------------------
    def shard_values(self, values_per_shard: Sequence) -> jax.Array:
        """Per-shard numpy value arrays -> padded sharded device array."""
        dp = self.dist_plan
        if len(values_per_shard) != dp.num_shards:
            raise InvalidParameterError("one value array per shard required")
        out = np.zeros((dp.num_shards, dp.max_values, 2), self._rdt)
        for r, v in enumerate(values_per_shard):
            il = as_interleaved(v, self.precision)
            if il.shape != (dp.shard_plans[r].num_values, 2):
                raise InvalidParameterError(
                    f"shard {r}: expected {dp.shard_plans[r].num_values} "
                    f"values, got {il.shape[:-1]}")
            out[r, :il.shape[0]] = il
        return jax.device_put(out, self._sharded)

    def unshard_values(self, values: jax.Array):
        """Padded sharded values -> per-shard numpy complex arrays."""
        dp = self.dist_plan
        arr = np.asarray(values)
        return [arr[r, :dp.shard_plans[r].num_values, 0]
                + 1j * arr[r, :dp.shard_plans[r].num_values, 1]
                for r in range(dp.num_shards)]

    def shard_space(self, slabs: Sequence) -> jax.Array:
        """Per-shard space-domain slabs -> padded sharded device array."""
        dp = self.dist_plan
        if len(slabs) != dp.num_shards:
            raise InvalidParameterError("one slab per shard required")
        if dp.hermitian:
            out = np.zeros((dp.num_shards, dp.max_planes, dp.dim_y,
                            dp.dim_x), self._rdt)
        else:
            out = np.zeros((dp.num_shards, dp.max_planes, dp.dim_y, dp.dim_x,
                            2), self._rdt)
        for r, slab in enumerate(slabs):
            n = dp.num_planes[r]
            expect = (n, dp.dim_y, dp.dim_x)
            if dp.hermitian:
                arr = np.asarray(slab, self._rdt)
                if arr.shape != expect:
                    raise InvalidParameterError(
                        f"shard {r}: expected real slab {expect}, "
                        f"got {arr.shape}")
            else:
                arr = as_interleaved(slab, self.precision)
                if arr.shape != expect + (2,):
                    raise InvalidParameterError(
                        f"shard {r}: expected complex slab {expect}, "
                        f"got {arr.shape[:-1]}")
            out[r, :n] = arr
        return jax.device_put(out, self._sharded)

    def unshard_space(self, space: jax.Array):
        """Padded sharded space array -> per-shard numpy slabs (complex for
        C2C, real for R2C), trimmed to each shard's true slab height."""
        dp = self.dist_plan
        arr = np.asarray(space)
        out = []
        for r in range(dp.num_shards):
            slab = arr[r, :dp.num_planes[r]]
            if not dp.hermitian:
                slab = slab[..., 0] + 1j * slab[..., 1]
            out.append(slab)
        return out

    # -- execution -----------------------------------------------------------
    def _local1_fn(self, fn):
        """Adapter for the comm-size-1 local delegate: the distributed
        pointwise contract hands ``fn`` the padded (1, planes, ...) slab;
        the local pipeline produces the bare slab. Cached per fn so the
        delegate's executable cache keys stay stable."""
        if fn is None:
            return None
        w = self._local1_fns.get(fn)
        if w is None:
            def w(s, *a, _fn=fn):
                return _fn(s[None], *a)[0]
            self._local1_fns[fn] = w
        return w

    def backward(self, values) -> jax.Array:
        """Frequency -> space across the mesh. ``values``: a per-shard list
        (numpy) or the padded sharded device array. Returns the padded
        sharded space array."""
        if not isinstance(values, jax.Array):
            values = self.shard_values(values)
        if self._local1 is not None:
            with timed_transform("backward") as box:
                box.value = self._local1.backward(values[0])[None]
            return box.value
        with timed_transform("backward") as box:
            box.value = self._backward_jit(values, *self._device_tables)
        return box.value

    def forward(self, space, scaling: Scaling = Scaling.NONE) -> jax.Array:
        """Space -> frequency across the mesh. Returns the padded sharded
        values array."""
        scaling = Scaling(scaling)
        if not isinstance(space, jax.Array):
            space = self.shard_space(space)
        if self._local1 is not None:
            with timed_transform("forward") as box:
                box.value = self._local1.forward(space[0], scaling)[None]
            return box.value
        with timed_transform("forward") as box:
            box.value = self._forward_jit[scaling](space,
                                                   *self._device_tables)
        return box.value

    # -- batched execution ---------------------------------------------------
    def _batched_jits(self):
        """Lazily-built fused batch executables: one SPMD program with a
        per-shard batch axis (S, B, ...) — N shared-plan transforms become
        one program with B× larger FFT batches, one batched-grid kernel
        launch per compression stage and vmapped collectives, instead of N
        dispatches (the reference's hand-interleaved multi-transform
        overlap, multi_transform_internal.hpp:47-94)."""
        if self._batched is None:
            shmap = functools.partial(
                jax.shard_map, mesh=self.mesh, in_specs=self._base_in_specs,
                out_specs=P(self.axis_name), check_vma=self._check_vma)
            self._batched = {
                "backward": jax.jit(shmap(self._backward_body_batched)),
                Scaling.NONE: jax.jit(shmap(_entry(
                    "_forward_body_batched_none",
                    self._forward_body_batched, scaled=False))),
                Scaling.FULL: jax.jit(shmap(_entry(
                    "_forward_body_batched_full",
                    self._forward_body_batched, scaled=True))),
            }
        return self._batched

    def shard_values_batch(self, values_batch: Sequence) -> jax.Array:
        """B per-transform value sets (each a per-shard list or a padded
        sharded (S, mv, 2) array) -> one (S, B, mv, 2) sharded array."""
        arrs = [v if isinstance(v, jax.Array) else self.shard_values(v)
                for v in values_batch]
        return jnp.stack(arrs, axis=1)

    def unshard_values_batch(self, values: jax.Array):
        """(S, B, mv, 2) -> list of B per-shard numpy complex value lists."""
        arr = np.asarray(values)
        return [self.unshard_values(arr[:, b]) for b in range(arr.shape[1])]

    def backward_batched(self, values_batch) -> jax.Array:
        """Backward-execute a shared-plan batch as ONE fused SPMD program.
        ``values_batch``: a (S, B, mv, 2) sharded array or a sequence of B
        value sets. Returns the (S, B, planes, ...) sharded space array."""
        if not (isinstance(values_batch, jax.Array)
                and values_batch.ndim == 4):
            values_batch = self.shard_values_batch(values_batch)
        with timed_transform("backward_batched") as box:
            box.value = self._batched_jits()["backward"](
                values_batch, *self._device_tables)
        return box.value

    def forward_batched(self, space_batch,
                        scaling: Scaling = Scaling.NONE) -> jax.Array:
        """Forward-execute a shared-plan batch as ONE fused SPMD program.
        ``space_batch``: a (S, B, planes, ...) sharded array or a sequence
        of B per-shard slab lists. Returns the (S, B, mv, 2) values."""
        scaling = Scaling(scaling)
        nd = 4 if self.dist_plan.hermitian else 5
        if not (isinstance(space_batch, jax.Array)
                and space_batch.ndim == nd + 1):
            space_batch = jnp.stack(
                [s if isinstance(s, jax.Array) else self.shard_space(s)
                 for s in space_batch], axis=1)
        with timed_transform("forward_batched") as box:
            box.value = self._batched_jits()[scaling](
                space_batch, *self._device_tables)
        return box.value

    # -- cross-request coalescing --------------------------------------------
    def coalesce_backward(self, values_list: Sequence):
        """Backward-execute N independent requests' value sets as ONE fused
        SPMD program and demux: one exchange collective round moves all N
        payloads (the Grid amortization, resurrected for the pod lane).
        ``values_list``: N value sets (each a per-shard list or padded
        (S, mv, 2) array). Returns a list of N per-request (S, planes, ...)
        space arrays, each identical to ``self.backward(values_list[i])``."""
        if self._local1 is not None or len(values_list) == 1:
            # comm-size-1 delegates have no batched body; a batch of one
            # gains nothing — run the serial path per request.
            return [self.backward(v) for v in values_list]
        stacked = self.backward_batched(values_list)
        return [stacked[:, b] for b in range(len(values_list))]

    def coalesce_forward(self, space_list: Sequence,
                         scaling: Scaling = Scaling.NONE):
        """Forward twin of :meth:`coalesce_backward`: N space slabs through
        one batched SPMD program, demuxed to N per-request (S, mv, 2) value
        arrays, each identical to ``self.forward(space_list[i], scaling)``."""
        scaling = Scaling(scaling)
        if self._local1 is not None or len(space_list) == 1:
            return [self.forward(s, scaling) for s in space_list]
        stacked = self.forward_batched(space_list, scaling)
        return [stacked[:, b] for b in range(len(space_list))]


def make_distributed_plan(transform_type: TransformType,
                          dim_x: int, dim_y: int, dim_z: int,
                          triplets_per_shard: Sequence[np.ndarray],
                          planes_per_shard: Sequence[int],
                          mesh: Optional[Mesh] = None,
                          precision: str = "single",
                          exchange: ExchangeType = ExchangeType.DEFAULT,
                          use_pallas: Optional[bool] = None,
                          overlap_chunks: Optional[int] = None,
                          wire_precision: Optional[int] = None,
                          wire_error_budget: Optional[float] = None,
                          ) -> DistributedTransformPlan:
    """Plan a distributed transform in one call (the distributed analogue of
    ``Grid::create_transform``, reference grid.hpp:138-141). Under
    ``jax.distributed`` (multi-process), cross-checks that every process
    built the identical plan, like the reference's plan-time allreduce
    mismatch detection (grid_internal.cpp:148-167)."""
    dist = build_distributed_plan(TransformType(transform_type), dim_x, dim_y,
                                  dim_z, triplets_per_shard, planes_per_shard)
    if jax.process_count() > 1:  # pragma: no cover - multi-host only
        from .multihost import validate_consistent
        validate_consistent(dist)
    return DistributedTransformPlan(dist, mesh=mesh, precision=precision,
                                    exchange=exchange, use_pallas=use_pallas,
                                    overlap_chunks=overlap_chunks,
                                    wire_precision=wire_precision,
                                    wire_error_budget=wire_error_budget)
