"""Local (single-device) sparse 3D FFT plans.

The reference's ``Grid`` + ``Transform`` pair pre-allocates buffers and builds
FFTW/cuFFT plans at construction (reference: src/spfft/grid_internal.cpp:75-98,
src/spfft/transform_internal.cpp:86-136). The TPU-native equivalent of a
"plan" is a pair of jitted executables closed over static index tables: XLA
owns buffer allocation and intra-computation reuse (making the reference's
manual two-array aliasing unnecessary), and the compiled executable *is* the
plan cache.

Pipeline (reference: src/execution/execution_host.cpp:249-352):

  backward:  decompress -> [stick symmetry] -> z-IFFT -> scatter to planes
             -> [plane symmetry] -> xy-IFFT
  forward:   xy-FFT -> gather sticks -> z-FFT -> compress [scaled]

Complex I/O crosses the host<->device boundary as interleaved real arrays with
a trailing axis of 2 (see utils.dtypes), matching the reference's interleaved
complex format.
"""

from __future__ import annotations

import contextlib
import logging
import time as _time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger("spfft_tpu")

#: Above this many sparse values, the plan's device boundary for value
#: arrays is the planar PAIR layout (2, N) — row 0 real, row 1 imag —
#: instead of interleaved rows (N, 2): XLA can assign a large (N, 2)
#: boundary array TPU's T(8,128) tiled layout, padding the minor dim
#: 2 -> 128 — 64x memory, 36 GB at 512^3 (measured), while flat (2N,)
#: strided interleaves lower ~70x too slow; (2, N) is compact AND fast
#: (see ops/gather_kernel.planar_from_interleaved). 16M keeps the
#: battle-tested (N, 2) layout for every grid up to 256^3 and switches
#: 320^3+.
PAIR_IO_THRESHOLD = 16_000_000

from .errors import InvalidParameterError
from .indexing import IndexPlan, build_index_plan
from .ops import stages
from .timing import timed_transform
from .types import Scaling, TransformType
from .utils.dtypes import (as_interleaved, complex_dtype,
                           complex_to_interleaved, interleaved_to_complex,
                           real_dtype)

import dataclasses as _dataclasses


def _entry(name: str, impl, /, **kwargs):
    """``impl`` with ``kwargs`` bound, as a function named ``name``. JAX
    names a program after the function it jits (``jit_<name>``), and a
    ``functools.partial`` has no name: its program would be
    ``jit__unknown``, which no trace can tell from another."""
    def entry(*args):
        return impl(*args, **kwargs)
    entry.__name__ = entry.__qualname__ = name
    return entry


@_dataclasses.dataclass(frozen=True)
class PlanTables:
    """Host-side snapshot of everything a plan's background table build
    produces — the restore payload of the persistent plan-artifact
    store (:mod:`spfft_tpu.serve.store`).

    A plan restored from one of these skips BOTH expensive halves of a
    cold start: index-table construction (the ``IndexPlan`` arrives
    fully materialised from the artifact) and the background
    compression-table build thread (the gather/fused tables arrive
    prebuilt; only the cheap device commit runs). ``pallas_box`` /
    ``fused_box`` hold the same host table dataclasses the build thread
    would have produced (``gather_kernel.MonotoneGatherTables`` /
    ``WideGatherTables``, ``fused_kernel.Fused*Tables``); activation is
    re-decided at restore time from the RESTORING process's backend —
    tables exported on a TPU restore inactive-but-committed on CPU and
    vice versa, exactly like a fresh build would have decided."""

    s_pad: int
    pallas_box: Optional[dict]      # {"dec": tables|None, "cmp": ...}
    fused_box: dict                 # {"dec": tables|None, "cmp": ...}
    fused_reasons: dict             # per-direction gate decline reasons


def predicted_rel_error(precision: str, max_dim: int,
                        mdft_covered: Optional[bool] = None,
                        device_double: bool = False) -> float:
    """Conservative predicted relative l2 error of a backward transform vs
    a dense f64 oracle, for uniform-magnitude (O(1) dynamic range) value
    sets.

    Calibrated against the measured on-TPU precision matrix with the
    round-4 matmul-DFT stages (docs/precision.md;
    scripts/precision_matrix.py): single-precision backward l2 vs the
    dense f64 oracle measures 1.4e-7 (32^3) / 1.5e-7 (64^3) / 1.7e-7
    (128^3) / 1.8e-7 (256^3) / 1.9e-7 (512^3) — fit err ~ 1.5e-7 *
    (n/64)^0.13; the model uses a ~1.8x envelope so every measured point
    (including the adversarial rows, worst 1.92e-7) sits well below it.
    Double precision follows the same shape from the f64 epsilon. The
    model covers the reference-contract workload class (values of
    bounded dynamic range, test_check_values.hpp:46-50); measured 1e±6
    dynamic range stayed at 1.9e-7 relative l2
    (docs/precision.md 'Adversarial rows').

    Calibrated domain: the matmul-DFT forms (direct — incl. the
    prime-fallback lengths, measured 1.44e-7 at a 521 axis and 1.42e-7
    at 1021 on-chip — or two-stage, single precision) and the CPU f64
    path. Plans the matmul pipeline cannot cover (an unfactorable axis
    above the direct-fallback cap; an R2C x-axis above it) execute
    through XLA's ``jnp.fft`` lowering, where the envelope is
    extrapolation —
    an extra 4x safety factor applies so the contract fails loudly
    rather than promising uncalibrated accuracy (round-4 advisor
    finding). ``mdft_covered`` is the
    STRUCTURAL routing answer (ops.dft.mdft_coverable) from the caller;
    ``None`` infers it from ``max_dim`` alone (single-axis query).
    """
    from .ops.dft import mdft_coverable
    if mdft_covered is None:
        mdft_covered = mdft_coverable((max_dim,))
    shape = (max(max_dim, 1) / 64.0) ** 0.13
    if precision == "single":
        base = 2.8e-7 * shape
        if not mdft_covered:
            base *= 4.0  # uncalibrated jnp.fft path
        return base
    if device_double:
        # on-device double-single (ops/dsdft.py): exact-sliced dots with
        # an ORDER_MAX drop floor ~2^-54 and double-single carries;
        # measured pipeline ~1e-12-class (docs/precision.md round-5 rows)
        return 2.0e-11 * shape
    return 5.0e-15 * shape  # f64 eps * same shape, ~10x headroom


class TransformPlan:
    """A compiled sparse 3D FFT on a single device.

    Equivalent to a local reference ``Transform`` (reference:
    include/spfft/transform.hpp:56-227) — C2C or R2C, double or single
    precision, arbitrary sparse frequency triplets.
    """

    def __init__(self, index_plan: IndexPlan, precision: str = "single",
                 use_pallas: Optional[bool] = None,
                 donate_inputs: bool = False,
                 max_rel_error: Optional[float] = None,
                 device_double: Optional[bool] = None,
                 _restore: Optional[PlanTables] = None,
                 _trace_id: Optional[int] = None):
        from . import obs as _obs
        from .utils.platform import enable_persistent_compilation_cache
        enable_persistent_compilation_cache()
        t0 = _time.perf_counter()
        scope = contextlib.nullcontext()
        if _obs.active():
            scope = _obs.GLOBAL_TRACER.span(
                "compile.plan_build", cat="compile", track="compile",
                trace_id=(_obs.GLOBAL_TRACER.new_trace_id()
                          if _trace_id is None else _trace_id),
                args={"kind": "local", "precision": precision,
                      "dims": f"{index_plan.dim_x}x{index_plan.dim_y}"
                              f"x{index_plan.dim_z}"})
        #: the build's ``compile.plan_build`` span (None with tracing
        #: off), parent of the background table build's spans
        with scope as self._build_span:
            self._construct(index_plan, precision, use_pallas,
                            donate_inputs, max_rel_error, device_double,
                            _restore)
        _obs.record_plan_build(self, _time.perf_counter() - t0, t0)

    def _construct(self, index_plan, precision, use_pallas, donate_inputs,
                   max_rel_error, device_double, _restore) -> None:
        """The foreground of the plan build; the compression-table build
        it starts runs on past its end."""
        #: When True, the fused round-trip executables (apply_pointwise /
        #: iterate_pointwise) DONATE their values argument: the output has
        #: the same shape, so XLA aliases the input buffer into it, cutting
        #: peak HBM by one values array (measured: 417 -> 347 MB at 256^3,
        #: 1803 -> 1566 MB at 384^3 — scripts/probe_donation.py) — the TPU
        #: form of the reference's two-array in-place buffer economy
        #: (reference: src/spfft/grid_internal.cpp:75-98). backward/forward
        #: do NOT donate: their input and output shapes differ, so XLA
        #: could never alias them and the donation would only produce
        #: unusable-donation warnings. The caller's input device array is
        #: CONSUMED by the donating calls (invalid afterwards); numpy
        #: inputs are unaffected (their device copy is transient anyway).
        self.donate_inputs = bool(donate_inputs)
        self.index_plan = index_plan
        self.precision = precision
        self._rdt = real_dtype(precision)
        self._cdt = complex_dtype(precision)
        self._pair_io = index_plan.num_values >= PAIR_IO_THRESHOLD
        from .ops import dft as _dft
        # On-device double: double-single (hi, lo) f32 channels through
        # exact-sliced Ozaki dots (ops/dsdft.py) — ~1e-12 relative on the
        # chip, where f64 arrays cannot even exist. C2C and R2C,
        # direct-form axes. SPFFT_TPU_DEVICE_DOUBLE=0 restores the old
        # behavior
        # (CPU-backend f64; on a TPU session that silently truncated to
        # f32 — the bug this mode replaces); =force enables off-TPU for
        # tests.
        import os as _os
        _ds_env = _os.environ.get("SPFFT_TPU_DEVICE_DOUBLE", "")
        self._ds = (precision == "double" and _ds_env != "0"
                    and device_double is not False
                    and max(index_plan.dim_x, index_plan.dim_y,
                            index_plan.dim_z) <= _dft.MATMUL_DFT_MAX
                    and (_ds_env == "force"
                         or jax.default_backend() == "tpu"))
        if precision == "double" and not self._ds \
                and device_double is not False \
                and (jax.default_backend() == "tpu"
                     or not jax.config.jax_enable_x64):
            # device_double=False callers (the distributed comm-size-1
            # delegate) warn at their own layer with their own wording.
            # The CPU-without-x64 case is the same silent trap: JAX
            # truncates every f64 array to f32 with only a UserWarning.
            if jax.default_backend() != "tpu":
                why = "jax x64 is not enabled on this CPU backend"
            elif _ds_env == "0":
                why = "SPFFT_TPU_DEVICE_DOUBLE=0 disabled it"
            else:
                why = (f"an axis above {_dft.MATMUL_DFT_MAX} is outside "
                       f"the mode")
            logger.warning(
                "spfft_tpu: precision='double' without the on-device "
                "double mode (%s) runs at FLOAT32 device precision — "
                "use the CPU backend with jax x64 enabled "
                "(JAX_ENABLE_X64=1) for true f64 (docs/precision.md)",
                why)
        # the double-single pipeline has its own (N, 4) host-f64
        # boundary; the planar pair layout never applies to it
        if self._ds:
            self._pair_io = False
        if max_rel_error is not None:
            from .ops.dft import mdft_coverable
            predicted = predicted_rel_error(
                precision, max(index_plan.dim_x, index_plan.dim_y,
                               index_plan.dim_z),
                mdft_coverable((index_plan.dim_x, index_plan.dim_y,
                                index_plan.dim_z), index_plan.hermitian),
                device_double=self._ds)
            if predicted > max_rel_error:
                from .errors import PrecisionContractError
                hint = ("the CPU backend (JAX_PLATFORMS=cpu, jax x64) "
                        "reaches f64 epsilon"
                        if precision == "double" else
                        "precision='double' (on-device double-single "
                        "for axes <= 512, CPU backend otherwise)")
                raise PrecisionContractError(
                    f"precision='{precision}' predicts relative error "
                    f"~{predicted:.1e} at dims ({index_plan.dim_x},"
                    f"{index_plan.dim_y},{index_plan.dim_z}), above the "
                    f"requested max_rel_error={max_rel_error:.1e} — "
                    f"{hint} (docs/precision.md)")
        #: Matmul-DFT (T-layout) pipeline: every DFT contracts the minor
        #: axis against plan-time matrices, the plane grid stays
        #: transposed (planes, x, y) through the y-stage, and the round
        #: trip pays ONE transpose pair instead of XLA fft2's four
        #: internal layout copies (ops/dft.py; scripts/probe_r4_dft2.py).
        # The shared routing predicate (ops.dft.mdft_axes): every axis
        # direct or two-stage; the R2C x-axis needs the direct form
        # (half-spectrum matrices don't factor through the split).
        self._use_mdft = _dft.mdft_axes(
            self._cdt, index_plan.dim_x, index_plan.dim_y,
            index_plan.dim_z,
            direct_any=(index_plan.dim_x,) if index_plan.hermitian else ())
        if self._pair_io:
            # Layout flip is observable by callers (forward/apply_pointwise
            # return (2, N) instead of (N, 2)); say so once at plan build.
            logger.info(
                "spfft_tpu: plan has %d values (>= %d) — device value "
                "arrays use the planar pair layout (2, N); see "
                "TransformPlan.pair_values_io",
                index_plan.num_values, PAIR_IO_THRESHOLD)
        # Static tables, device-committed once (plan time, never at execute
        # time — mirroring SURVEY.md §3.1's plan/execute split). They are
        # passed to the jitted pipelines as arguments, not closure constants.
        # Only the tables the ACTIVE path touches live in the hot dict (an
        # unused pytree leaf would still ship to the device on every call);
        # the fallback-path tables (slot_src, value_indices — 87 MB at
        # 256^3) commit lazily via _commit_fallback / the _tables property.
        self._pallas_box = None
        self._pallas_active_flag = False
        #: Fused compression+z-DFT state (ops/fused_kernel.py): per-
        #: direction tables ("dec"/"cmp"), per-direction fallback
        #: reasons, and the activation flag. Built on the same
        #: background thread as the gather tables.
        self._fused_box = {"dec": None, "cmp": None}
        self._fused_reasons = {}
        self._fused_active_flag = False
        self._build_thread = None
        self._build_exc = None
        self._tables_full = None
        #: AOT executables installed by the plan-artifact store after a
        #: restore: ``{"backward": Exported, "forward_none": ...,
        #: "forward_full": ...}`` — ``jax.export`` deserialisations that
        #: skip the trace/lower half of the first execution. Only the
        #: default-placement public entries consult them (a device-pool
        #: pinned execution keeps the per-device jit path).
        self._aot = None
        self._restore_tables = _restore
        will_build = self._decide_pallas(use_pallas)  # also sets _s_pad
        p = index_plan
        extra = self._s_pad - p.num_sticks
        pads = np.zeros(extra, np.int32)
        self._tables_hot = {}
        if self._use_mdft or self._ds:
            self._tables_hot["col_inv_t"] = jnp.asarray(p.col_inv_t)
            self._tables_hot["scatter_cols_t"] = jnp.asarray(
                np.concatenate([p.scatter_cols_t, pads]) if extra
                else p.scatter_cols_t)
        if not self._use_mdft and not self._ds:
            # (_ds reads only the T tables + the compression fallbacks;
            # an unused pytree leaf would ship every call — see above)
            self._tables_hot["col_inv"] = jnp.asarray(p.col_inv)
            self._tables_hot["scatter_cols"] = jnp.asarray(
                np.concatenate([p.scatter_cols, pads]) if extra
                else p.scatter_cols)
        if _restore is not None:
            self._commit_restored(_restore)
        elif not will_build:
            self._commit_fallback("dec")
            self._commit_fallback("cmp")
        self._init_split_x()
        # Hermitian x < 0 folding (indexing.canonicalize_hermitian_triplets):
        # folded values are stored conjugated, so the boundary applies a
        # ±1 sign to the imaginary lane — backward input and forward
        # output — in each value layout. ±1 multiplies are exact in f32,
        # so the fold costs no precision and works identically under the
        # XLA gather, the Pallas gather kernel, and the fused z-DFT kernel.
        vc = index_plan.value_conj
        if vc is not None and bool(np.asarray(vc).any()):
            s = np.where(np.asarray(vc), -1.0, 1.0)
            o = np.ones_like(s)
            self._conj_mults = {
                "il": np.stack([o, s], axis=-1),        # (N, 2)
                "pair": np.stack([o, s], axis=0),       # (2, N)
                "ds": np.stack([o, o, s, s], axis=-1),  # (N, 4) [rh,rl,ih,il]
            }
        else:
            self._conj_mults = None
        if self._ds:
            from .ops import dsdft as _dsdft
            gs = 1.0 / float(self.global_size)
            herm = index_plan.hermitian
            self._ds_mats = {
                "z_b": _dsdft.ds_c2c_mats(p.dim_z, _dft.BACKWARD),
                "y_b": _dsdft.ds_c2c_mats(p.dim_y, _dft.BACKWARD),
                # hermitian x-stages are the REAL half-spectrum forms
                # (hermitian doubling folded into the c2r matrices)
                "x_b": (_dsdft.ds_c2r_mats(p.dim_x) if herm
                        else _dsdft.ds_c2c_mats(p.dim_x, _dft.BACKWARD)),
                "x_f": (_dsdft.ds_r2c_mats(p.dim_x) if herm
                        else _dsdft.ds_c2c_mats(p.dim_x, _dft.FORWARD)),
                "y_f": _dsdft.ds_c2c_mats(p.dim_y, _dft.FORWARD),
                "z_f": _dsdft.ds_c2c_mats(p.dim_z, _dft.FORWARD),
                "z_fs": _dsdft.ds_c2c_mats(p.dim_z, _dft.FORWARD, gs),
            }
        self._batched = None
        self._device_tables = {}
        self._pair_jits = {}
        # runtime fused-kernel demotion ladder (docs/kernels.md): per
        # direction {"reason", "unfused_ok", "probes", "probing",
        # "permanent"}. Written only by the thread driving executions
        # (the serving dispatcher, or the single caller thread).
        self._fused_demotions = {}
        self._backward_jit = jax.jit(self._backward_impl)
        self._forward_jit = self._scaled_jits(self._forward_impl)
        # the FOREGROUND half of the plan.build fault seam: constructing
        # the plan (the background builder thread carries the other half)
        from . import faults as _faults
        _faults.check_site("plan.build")
        if will_build:
            # The compression-table build (native cover + device commit,
            # ~2-3 s at 256^3) runs CONCURRENTLY with whatever the caller
            # does next — typically the first execution's trace + XLA
            # compile / cache load, which takes longer. Public execution
            # methods join via _finalize(); plan construction itself
            # returns in well under a second (the reference's sub-second
            # plan construction, parameters.cpp + FFTW_ESTIMATE).
            import threading
            self._build_thread = threading.Thread(
                target=self._build_compression_tables, daemon=True)
            self._build_thread.start()

    def _decide_pallas(self, use_pallas: Optional[bool]) -> bool:
        """Decide (cheaply, at construction) whether the Pallas
        windowed-gather compression tables will be built, and fix
        ``_s_pad`` accordingly. The heavy build itself runs in
        :meth:`_build_compression_tables` on a background thread.

        ``use_pallas=True`` on a non-TPU backend builds the tables (useful
        for table-level testing) but execution stays on the XLA path — note
        the asymmetry with ``DistributedTransformPlan``, whose
        ``use_pallas=True`` runs the kernel in *interpret mode* on non-TPU
        (its SPMD body must execute the same program on every backend); the
        kernel is float32-only, so forcing it on a double-precision plan is
        an error rather than a silent downcast."""
        p = self.index_plan
        #: Stick rows of the packed stick array. Plans that attempt
        #: compression tables pad to the next multiple of 32 past
        #: num_sticks: the pad sticks are zeros, so (a) the unpack gather
        #: needs NO sentinel concatenation (a 53 MB copy at 256^3 —
        #: probe_r4_hlo), and (b) dim_z % 4 == 0 grids make num_slots a
        #: whole number of kernel tiles, turning the kernel-output
        #: reshape into a bitcast.
        self._s_pad = p.num_sticks
        self._backend_ok = jax.default_backend() == "tpu"
        self._use_pallas_req = use_pallas
        if use_pallas is True and self.precision != "single":
            raise InvalidParameterError(
                "the Pallas compression kernel is single-precision only")
        if self._restore_tables is not None:
            # Artifact restore: the tables (and the padding they were
            # built against) come prebuilt from the store — never start
            # the background build thread, whatever the auto rule says.
            self._s_pad = int(self._restore_tables.s_pad)
            if self._s_pad < p.num_sticks:
                raise InvalidParameterError(
                    f"restored plan tables pad {self._s_pad} stick rows "
                    f"but the index plan has {p.num_sticks}")
            return False
        # Auto threshold, re-measured round 3 with sync-cancelled timing
        # (scripts/sweep.py; the round-2 numbers carried ~5 ms of sync
        # readback per measurement, which hid the XLA path's small-size
        # advantage): 64^3/137k values XLA 0.45 vs kernel 0.74 ms;
        # 96^3/463k values kernel 1.0 vs XLA 5.2 ms; 128^3 kernel 0.4 vs
        # 14.7; 256^3 kernel 12.4 vs 129.8. Crossover between 137k and
        # 463k values -> 200k.
        auto = self._backend_ok and self.precision == "single" \
            and p.num_values >= 200_000
        if use_pallas is False or (use_pallas is None and not auto):
            return False
        if p.num_values == 0 or p.num_sticks == 0:
            return False
        self._s_pad = -(-(p.num_sticks + 1) // 32) * 32
        return True

    def _build_compression_tables(self) -> None:
        """The heavy half of the Pallas setup: gather inputs, the wide/
        narrow cover builds (native C++), and the device commit of the
        packed tables. Runs on the plan's background build thread;
        :meth:`_finalize` joins and re-raises any failure. The value
        order handling is unchanged: any order works, stick-major/
        z-ascending (the layout the reference recommends,
        details.rst 'Data Distribution') is optimal, and a too-scattered
        order falls back to the XLA gather with a logged notice."""
        from . import obs as _obs
        try:
            with _obs.compile_span(
                    "compression_tables", parent=self._build_span,
                    args={"num_values": int(self.index_plan.num_values)}
                    ) as span:
                # the first build in a process pays the import of the
                # Pallas kernel modules here, a second or more
                from .ops import gather_kernel as gk
                from . import faults as _faults
                _faults.check_site("plan.build")
                p = self.index_plan
                use_pallas = self._use_pallas_req
                with _obs.compile_span("gather_tables", parent=span):
                    vi = p.value_indices.astype(np.int64)
                    num_slots = self._s_pad * p.dim_z
                    (dec_idx, occupied), (cmp_idx, cmp_valid) = \
                        gk.compression_gather_inputs(vi, num_slots)
                    dec = gk.build_best_gather_tables(dec_idx, occupied,
                                                      p.num_values)
                    # commit the first table set while the second builds
                    # on host
                    if dec is not None:
                        self._tables_hot["dec_tabs"] = \
                            gk.gather_device_tables(dec)
                    cmp_ = gk.build_best_gather_tables(cmp_idx, cmp_valid,
                                                       num_slots)
                    if cmp_ is not None:
                        self._tables_hot["cmp_tabs"] = \
                            gk.gather_device_tables(cmp_)
                if dec is None or cmp_ is None:
                    fell_back = [n for n, t in (("decompress", dec),
                                                ("compress", cmp_))
                                 if t is None]
                    for stage in fell_back:
                        _obs.record_plan_fallback(stage, "value_order")
                    # WARNING only when the caller explicitly asked for
                    # the kernel; auto mode logs at INFO.
                    log = logger.warning if use_pallas is True \
                        else logger.info
                    log(
                        "spfft_tpu: value order too scattered for the "
                        "Pallas compression kernel (%s) — using the slower "
                        "XLA gather path there (sort triplets with "
                        "utils.workloads.sort_triplets_stick_major for the "
                        "fast path)", " and ".join(fell_back))
                with _obs.compile_span("fused_tables",
                                       parent=span) as fspan:
                    self._build_fused_tables(dec_idx, occupied, cmp_idx,
                                             cmp_valid, num_slots, dec, cmp_,
                                             span=fspan)
                if dec is None and cmp_ is None:
                    self._pallas_box = None
                    return
                self._pallas_box = {"dec": dec, "cmp": cmp_}
                self._pallas_active_flag = self._backend_ok
        except BaseException as exc:  # re-raised by _finalize
            self._build_exc = exc

    def _build_fused_tables(self, dec_idx, occupied, cmp_idx, cmp_valid,
                            num_slots, dec_best, cmp_best, span=None) -> None:
        """Build the fused compression+z-DFT tables (ops/fused_kernel)
        for whichever directions pass the gate; record every decline as
        a ``spfft_plan_pallas_fallback_total`` reason. Runs on the
        background build thread, after the gather tables.

        Each direction takes the ``super_tile`` geometry (one grid step
        per super-tile, tables from the wide gather cover) where its
        tables fit, else the ``narrow`` one (one step per 1024-slot
        tile, from the narrow decomposition: the preferred gather
        tables when they came out narrow, else a narrow set built here),
        with the reason recorded under the ``fused_super_tile_*``
        stages. ``spfft_plan_fused_geometry_total`` counts what each
        direction got, and ``span`` (the ``compile.fused_tables`` span,
        None with tracing off) carries it with the grid steps."""
        from . import obs as _obs
        from .ops import dft as _dft
        from .ops import fused_kernel as fkm
        from .ops import gather_kernel as gk

        p = self.index_plan
        if not self._use_mdft or not fkm.enabled() \
                or not (self._backend_ok or fkm.interpret_forced()):
            return  # the fused path was never in play — nothing to record

        def narrow(best, idx, valid, n_src):
            if isinstance(best, gk.MonotoneGatherTables):
                return best
            if best is None:  # best-effort build already blew up
                return None
            return gk.build_monotone_gather_tables(idx, valid, n_src)

        reasons = {}
        box = {"dec": None, "cmp": None}
        # backward: gather-decompress + z-DFT. The r2c (0,0)-stick
        # hermitian completion runs BETWEEN decompress and the z stage
        # and rides INSIDE the kernel (ops/fused_kernel
        # ._complete_zero_stick), so r2c plans take the fused path too.
        zid = p.zero_stick_id if self._is_r2c else None

        def narrow_dec():
            nt = narrow(dec_best, dec_idx, occupied, p.num_values)
            if nt is None:
                return "value_order"
            return fkm.build_fused_decompress_tables(
                nt, p.dim_z, self._s_pad, zero_stick_id=zid)

        # forward twin: z-DFT + compress gather, FULL scaling folded
        # into a second matrix triple at plan time
        def narrow_cmp():
            ct = narrow(cmp_best, cmp_idx, cmp_valid, num_slots)
            if ct is None:
                return "value_order"
            return fkm.build_fused_compress_tables(ct, p.dim_z, self._s_pad)

        stage_name = {"dec": "fused_decompress_zdft",
                      "cmp": "fused_zdft_compress"}
        attrs = {}
        for which, direction in (("dec", "backward"), ("cmp", "forward")):
            if which == "dec":
                tabs = fkm.build_super_decompress_tables(
                    dec_idx, occupied, p.num_values, p.dim_z, p.num_sticks,
                    zero_stick_id=zid)
            else:
                tabs = fkm.build_super_compress_tables(
                    cmp_idx, cmp_valid, num_slots, p.dim_z, self._s_pad)
            why_super = None
            if isinstance(tabs, str):
                why_super = tabs
                tabs = narrow_dec() if which == "dec" else narrow_cmp()
            if isinstance(tabs, str):
                reasons[which] = tabs
                continue
            box[which] = tabs
            if why_super is not None:
                _obs.record_plan_fallback(f"fused_super_tile_{direction}",
                                          why_super)
                logger.info(
                    "spfft_tpu: super-tile tables do not fit %s (%s) — "
                    "keeping the narrow fused kernel there",
                    stage_name[which], why_super)
            _obs.record_fused_geometry(direction, tabs.geometry)
            attrs[f"{direction}_geometry"] = tabs.geometry
            attrs[f"{direction}_grid_steps"] = tabs.grid_steps
            # commit each direction's tables while the next one builds
            if which == "dec":
                self._tables_hot["fzd_tabs"] = \
                    fkm.decompress_device_tables(tabs)
                self._tables_hot["fzd_mats"] = fkm.commit_mats(
                    _dft.c2c_mats(p.dim_z, _dft.BACKWARD))
            else:
                self._tables_hot["fzc_tabs"] = \
                    fkm.compress_device_tables(tabs)
                self._tables_hot["fzc_mats"] = fkm.commit_mats(
                    _dft.c2c_mats(p.dim_z, _dft.FORWARD))
                self._tables_hot["fzc_mats_s"] = fkm.commit_mats(
                    _dft.c2c_mats(p.dim_z, _dft.FORWARD,
                                  scale=1.0 / float(self.global_size)))
        if span is not None:
            span.args = {**(span.args or {}), **attrs}
        for which, why in reasons.items():
            _obs.record_plan_fallback(stage_name[which], why)
            logger.info(
                "spfft_tpu: fused compression+DFT kernel unavailable for "
                "%s (%s) — keeping the two-kernel path there",
                stage_name[which], why)
        self._fused_reasons = reasons
        self._fused_box = box
        self._fused_active_flag = box["dec"] is not None \
            or box["cmp"] is not None

    def _commit_fallback(self, which: str) -> None:
        """Commit the XLA-gather fallback table for one compression
        direction (slot_src / value_indices — the big inverse maps that
        the Pallas path never reads)."""
        p = self.index_plan
        extra = self._s_pad - p.num_sticks
        if which == "dec" and "slot_src" not in self._tables_hot:
            ss = p.slot_src
            if extra:
                ss = np.concatenate(
                    [ss, np.full(extra * p.dim_z, p.num_values, np.int32)])
            self._tables_hot["slot_src"] = jnp.asarray(ss)
        if which == "cmp" and "value_indices" not in self._tables_hot:
            self._tables_hot["value_indices"] = jnp.asarray(
                p.value_indices)

    def _commit_restored(self, r: PlanTables) -> None:
        """Commit prebuilt tables from a plan artifact (the store's
        restore path): device-put the gather/fused tables the artifact
        carries, re-decide activation for THIS process's backend, and
        commit whatever fallback tables the outcome requires — the
        exact end state :meth:`_join_build` would have produced, with
        zero table construction."""
        from .ops import fused_kernel as fkm
        from .ops import gather_kernel as gk
        if self._use_pallas_req is False or self.precision != "single":
            # the caller (or the precision) rules the kernel path out —
            # mirror a fresh build's "never in play" end state
            self._commit_fallback("dec")
            self._commit_fallback("cmp")
            return
        box = r.pallas_box
        if box is not None and (box.get("dec") is not None
                                or box.get("cmp") is not None):
            self._pallas_box = {"dec": box.get("dec"),
                                "cmp": box.get("cmp")}
            if box.get("dec") is not None:
                self._tables_hot["dec_tabs"] = \
                    gk.gather_device_tables(box["dec"])
            if box.get("cmp") is not None:
                self._tables_hot["cmp_tabs"] = \
                    gk.gather_device_tables(box["cmp"])
            self._pallas_active_flag = self._backend_ok
        active = self._pallas_active_flag
        pb = self._pallas_box
        if pb is None or pb.get("dec") is None or not active:
            self._commit_fallback("dec")
        if pb is None or pb.get("cmp") is None or not active:
            self._commit_fallback("cmp")
        self._fused_reasons = dict(r.fused_reasons or {})
        fb = r.fused_box or {}
        if not self._use_mdft or not fkm.enabled() \
                or not (self._backend_ok or fkm.interpret_forced()):
            return
        from .ops import dft as _dft
        p = self.index_plan
        fbox = {"dec": None, "cmp": None}
        if fb.get("dec") is not None:
            fbox["dec"] = fb["dec"]
            self._tables_hot["fzd_tabs"] = \
                fkm.decompress_device_tables(fb["dec"])
            self._tables_hot["fzd_mats"] = fkm.commit_mats(
                _dft.c2c_mats(p.dim_z, _dft.BACKWARD))
        if fb.get("cmp") is not None:
            fbox["cmp"] = fb["cmp"]
            self._tables_hot["fzc_tabs"] = \
                fkm.compress_device_tables(fb["cmp"])
            self._tables_hot["fzc_mats"] = fkm.commit_mats(
                _dft.c2c_mats(p.dim_z, _dft.FORWARD))
            self._tables_hot["fzc_mats_s"] = fkm.commit_mats(
                _dft.c2c_mats(p.dim_z, _dft.FORWARD,
                              scale=1.0 / float(self.global_size)))
        self._fused_box = fbox
        self._fused_active_flag = fbox["dec"] is not None \
            or fbox["cmp"] is not None

    def export_tables(self) -> PlanTables:
        """Snapshot the plan's host-side built-table state for the
        persistent artifact store (joins the background build first).
        The snapshot is pure host data — numpy table dataclasses plus
        the stick padding they assume — and, together with the
        ``IndexPlan``, is everything a fresh process needs to
        reconstruct this plan without rebuilding anything."""
        self._finalize()
        box = None
        if self._pallas_box is not None:
            box = {"dec": self._pallas_box.get("dec"),
                   "cmp": self._pallas_box.get("cmp")}
        return PlanTables(
            s_pad=self._s_pad, pallas_box=box,
            fused_box={"dec": self._fused_box.get("dec"),
                       "cmp": self._fused_box.get("cmp")},
            fused_reasons=dict(self._fused_reasons))

    def install_aot(self, executables: dict) -> None:
        """Install ``jax.export``-deserialised executables for the
        default-placement public entries — the store's AOT prewarm.
        Single-request keys are ``"backward"`` / ``"forward_none"`` /
        ``"forward_full"``; batched keys (symbolic leading batch dim)
        are ``"batched_backward"`` / ``"batched_forward_none"`` /
        ``"batched_forward_full"``; identity fused-pair keys
        (``apply_pointwise`` with ``fn=None``) are ``"pair_none"`` /
        ``"pair_full"``. The first call then skips straight to
        execution instead of trace + lower (+ compile, when the
        backend's compilation cache misses)."""
        self._aot = dict(executables) if executables else None

    def _join_build(self) -> None:
        """Join the background table build (no-op afterwards) and commit
        whatever fallback tables the outcome requires. Never raises —
        :meth:`close`/``__del__`` use it for a silent teardown join."""
        th = self._build_thread
        if th is not None:
            if th.is_alive():
                from . import obs as _obs
                with _obs.compile_span("plan_join", parent=self._build_span):
                    th.join()
            self._build_thread = None
            if self._build_exc is None:
                box = self._pallas_box
                # an INACTIVE kernel (tables built off-TPU for testing)
                # still executes through the XLA gather, which needs the
                # fallback tables committed — use_pallas=True plans on
                # CPU used to KeyError on their first execution here
                active = self._pallas_active_flag
                if box is None or box["dec"] is None or not active:
                    self._commit_fallback("dec")
                if box is None or box["cmp"] is None or not active:
                    self._commit_fallback("cmp")

    def _finalize(self) -> None:
        """Join the background table build and surface any off-thread
        build failure as a typed :class:`~spfft_tpu.errors.TableBuildError`
        on first use. The failure is STICKY: every subsequent execution
        call re-raises it (a one-shot raise would leave later calls with
        neither pallas nor fallback tables committed and fail with a
        confusing KeyError inside the jitted pipeline — round-4 advisor
        finding)."""
        self._join_build()
        if self._build_exc is not None:
            from .errors import TableBuildError
            raise TableBuildError(
                f"the plan's background compression-table build failed: "
                f"{self._build_exc!r}", cause=self._build_exc)

    def check_build(self, wait: bool = False) -> None:
        """Surface background-builder DEATH without waiting for a
        request. ``wait=False`` (registration time: registry
        ``get_or_build`` resolution, executor registration) raises the
        sticky :class:`~spfft_tpu.errors.TableBuildError` only when the
        builder thread has ALREADY finished and failed — a live build
        is never blocked on. ``wait=True`` (warmup/prewarm, where
        blocking is the point) joins the build first, so a doomed plan
        fails before it is declared warm instead of on the first
        request (the round-14 error-latency fix)."""
        th = self._build_thread
        if not wait and th is not None and th.is_alive():
            return
        self._finalize()

    def close(self) -> None:
        """Join the plan's background compression-table build thread.
        Plans are otherwise passive (XLA owns the executables), but an
        abandoned plan must not leak a running builder: ``close`` (or
        garbage collection via ``__del__``) blocks until the thread is
        done. Never raises — a failed build surfaces as
        :class:`~spfft_tpu.errors.TableBuildError` on the next
        execution call, not at teardown. Idempotent."""
        self._join_build()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass  # interpreter teardown: modules may already be gone

    @property
    def _pallas(self):
        self._finalize()
        return self._pallas_box

    @property
    def _pallas_active(self) -> bool:
        self._finalize()
        return self._pallas_active_flag

    @_pallas_active.setter
    def _pallas_active(self, value: bool) -> None:
        # tests force the kernel path in interpret mode on CPU
        self._finalize()
        self._pallas_active_flag = bool(value)

    @property
    def _fused(self):
        self._finalize()
        return self._fused_box

    def _fused_on(self, which: str, pallas: bool = True) -> bool:
        """Trace-time dispatch gate for one fused direction (``"dec"``
        backward / ``"cmp"`` forward). Callers reach this inside the
        jitted pipelines, after the public entry already finalized. A
        direction demoted at RUNTIME (:meth:`_fused_demote`) gates off
        here too, except while its bounded re-probe is running."""
        rec = self._fused_demotions.get(which)
        if rec is not None and not rec["probing"]:
            return False
        return (pallas and self._fused_active_flag
                and self._fused_box.get(which) is not None)

    #: Unfused successes a demoted direction banks before one fused
    #: re-probe, and how many failed probes make the demotion permanent.
    FUSED_REPROBE_AFTER = 32
    FUSED_REPROBE_MAX = 3

    def _invalidate_fused_jits(self, which: str) -> None:
        """Drop every cached executable that baked the ``which``
        direction's fused gate into its traced program, so the next
        dispatch re-traces under the CURRENT gate. Runtime demotion
        needs this: a real execution-time kernel failure lives inside
        an already-compiled executable, which would otherwise re-run
        the same broken launch forever."""
        if which == "dec":
            self._backward_jit = jax.jit(self._backward_impl)
            if self._aot is not None:
                self._aot.pop("backward", None)
                self._aot.pop("batched_backward", None)
        else:
            self._forward_jit = self._scaled_jits(self._forward_impl)
            if self._aot is not None:
                self._aot.pop("forward_none", None)
                self._aot.pop("forward_full", None)
                self._aot.pop("batched_forward_none", None)
                self._aot.pop("batched_forward_full", None)
        if self._aot is not None:
            # the fused pair bakes BOTH directions into one program
            self._aot.pop("pair_none", None)
            self._aot.pop("pair_full", None)
        self._batched = None
        self._pair_jits = {}

    def _fused_demote(self, which: str, exc: BaseException,
                      probing: bool) -> None:
        """Stickily demote one direction to the unfused composition
        after a device-attributed launch/execution failure: record the
        reason (``fused_fallback_reasons`` + counter), gate the
        direction off and invalidate its executables. A failure during
        a re-probe re-demotes with the probe budget decremented; out of
        budget, the demotion is permanent (no further probes)."""
        rec = self._fused_demotions.get(which)
        if rec is None:
            rec = self._fused_demotions[which] = {
                "reason": "", "unfused_ok": 0, "probes": 0,
                "probing": False, "permanent": False}
        rec["reason"] = f"runtime: {type(exc).__name__}: {exc}"
        rec["unfused_ok"] = 0
        rec["probing"] = False
        if probing:
            rec["probes"] += 1
            rec["permanent"] = rec["probes"] >= self.FUSED_REPROBE_MAX
        self._fused_reasons[which] = rec["reason"]
        from . import obs as _obs
        _obs.GLOBAL_COUNTERS.inc("spfft_fused_demotions_total",
                                 which=which)
        if probing:
            _obs.GLOBAL_COUNTERS.inc("spfft_fused_reprobes_total",
                                     which=which, outcome="failed")
        _obs.record_event("fused.demote", which=which,
                          reason=rec["reason"],
                          permanent=rec["permanent"])
        logger.warning(
            "spfft_tpu: fused %s kernel failed at runtime (%r) — "
            "demoted to the unfused composition%s", which, exc,
            " permanently" if rec["permanent"] else
            f" (re-probe after {self.FUSED_REPROBE_AFTER} requests)")
        self._invalidate_fused_jits(which)

    def _fused_readmit(self, which: str) -> None:
        """A re-probe succeeded: lift the demotion (the fused trace that
        just ran stays cached) and count the readmission."""
        rec = self._fused_demotions.pop(which, None)
        self._fused_reasons.pop(which, None)
        from . import obs as _obs
        _obs.GLOBAL_COUNTERS.inc("spfft_fused_reprobes_total",
                                 which=which, outcome="readmitted")
        _obs.record_event("fused.readmit", which=which,
                          probes=rec["probes"] if rec else 0)
        logger.info(
            "spfft_tpu: fused %s kernel re-probe succeeded after %d "
            "failed probe(s) — readmitted", which,
            rec["probes"] if rec else 0)

    def fused_demotions(self) -> dict:
        """Snapshot of the runtime demotion ladder, per direction:
        ``{"dec"/"cmp": {"reason", "unfused_ok", "probes", "probing",
        "permanent"}}`` — empty when nothing is demoted."""
        return {k: dict(v) for k, v in self._fused_demotions.items()}

    def _guarded(self, which: str, call):
        """Dispatch one public execution whose traced program may run
        the ``which`` fused kernel, under the runtime demotion ladder:
        a device-attributed failure (an injected ``kernel.launch``
        fault, a Mosaic lowering error, a runtime launch failure)
        demotes the direction and RETRIES the same dispatch unfused —
        the request succeeds on the fallback composition instead of
        failing, and so does every subsequent request. Request-shaped
        errors (bad payloads) propagate untouched. ``call`` must read
        the jit caches at call time (a closure over ``self``), so the
        post-demotion retry picks up the re-traced executables."""
        rec = self._fused_demotions.get(which)
        probing = rec is not None and rec["probing"]
        fused = (self._fused_active_flag
                 and self._fused_box.get(which) is not None
                 and (rec is None or probing))
        if not fused:
            out = call()
            if rec is not None and not probing and not rec["permanent"]:
                rec["unfused_ok"] += 1
                if rec["unfused_ok"] >= self.FUSED_REPROBE_AFTER:
                    rec["probing"] = True
                    self._invalidate_fused_jits(which)
            return out
        from . import faults as _faults
        try:
            _faults.check_site("kernel.launch")
            out = call()
        except Exception as exc:
            if not _faults.attributes_device(exc):
                raise
            self._fused_demote(which, exc, probing)
            return call()
        if probing:
            self._fused_readmit(which)
        return out

    @property
    def _tables(self):
        """The FULL committed table set (hot-path tables plus every
        fallback/debug table) — for tests, probes and explicit
        ``pallas=False`` comparisons. Hot execution passes
        ``_tables_hot``, which carries only what the active path reads."""
        self._finalize()
        if self._tables_full is None:
            p = self.index_plan
            full = dict(self._tables_hot)
            if "slot_src" not in full:
                extra = self._s_pad - p.num_sticks
                ss = p.slot_src
                if extra:
                    ss = np.concatenate(
                        [ss, np.full(extra * p.dim_z, p.num_values,
                                     np.int32)])
                full["slot_src"] = jnp.asarray(ss)
            if "value_indices" not in full:
                full["value_indices"] = jnp.asarray(p.value_indices)
            if "scatter_cols" not in full:
                extra = self._s_pad - p.num_sticks
                sc = p.scatter_cols
                if extra:
                    sc = np.concatenate([sc, np.zeros(extra, np.int32)])
                full["scatter_cols"] = jnp.asarray(sc)
            if "col_inv" not in full:
                full["col_inv"] = jnp.asarray(p.col_inv)
            self._tables_full = full
        return self._tables_full

    def _init_split_x(self) -> None:
        """Enable the sparse-x xy-stage when the occupied x columns span
        under 70% of the x extent (the reference's "y transform over
        non-empty x-rows only", execution_host.cpp:139-145): the y-FFT then
        runs only on the occupied x window instead of the full plane. For
        C2C the window is *cyclic* — centered sets (negative x stored high)
        occupy a wrapped window, the flagship plane-wave sphere on a
        2x-cutoff grid included. For R2C it is a linear window of the half
        spectrum; plane symmetry applies to the x=0 sub-column when the
        window starts at 0 (when it doesn't, no x=0 stick exists and there
        is nothing to complete)."""
        from .indexing import (inverse_col_map, occupied_x_window,
                               window_sub_cols)

        p = self.index_plan
        self._split_x = None
        if p.num_sticks == 0:
            return
        if self._ds:
            return  # the double-single pipeline runs the dense path
        from .ops.dft import (MATMUL_DFT_DIRECT_FALLBACK_MAX,
                              _direct_form_len)
        x_direct = (p.dim_x <= MATMUL_DFT_DIRECT_FALLBACK_MAX
                    if self._is_r2c else _direct_form_len(p.dim_x))
        if self._use_mdft and not x_direct:
            # the split-x contraction needs PLAIN row/column-selected
            # matrices: the C2C builders return TwoStageMats for
            # composite axes above the cap (those run dense), while the
            # r2c/c2r builders are direct at any length up to the
            # fallback cap — prime-fallback and R2C axes keep the split
            return
        xf = p.dim_x_freq
        xs = p.scatter_cols % xf
        x0, w = occupied_x_window(xs, xf, allow_wrap=not self._is_r2c)
        if w > 0.7 * xf:
            return
        self._split_x = (x0, w)
        pads = np.zeros(self._s_pad - p.num_sticks, np.int32)
        if self._use_mdft:
            # T layout: window-x-major columns x_w * dim_y + y
            x_w = (p.stick_x.astype(np.int64) - x0) % xf
            cols_sub_t = (x_w * p.dim_y
                          + p.stick_y.astype(np.int64)).astype(np.int32)
            self._tables_hot["col_inv_sub_t"] = jnp.asarray(
                inverse_col_map(cols_sub_t, w * p.dim_y, p.num_sticks))
            self._tables_hot["scatter_cols_sub_t"] = jnp.asarray(
                np.concatenate([cols_sub_t, pads]))
        else:
            cols_sub = window_sub_cols(p.scatter_cols, xf, x0, w)
            col_inv_sub = inverse_col_map(cols_sub, p.dim_y * w,
                                          p.num_sticks)
            self._tables_hot["col_inv_sub"] = jnp.asarray(col_inv_sub)
            self._tables_hot["scatter_cols_sub"] = jnp.asarray(
                np.concatenate([cols_sub, pads]))

    def _tables_on(self, device):
        """The hot table set replicated onto ``device`` (cached per
        device; ``None`` = the default placement). Serving executors
        schedule independent requests across a device pool — jit
        dispatches on argument placement, so pinning an execution to a
        device means its tables must live there too. Call after
        ``_finalize`` (the hot dict can still gain fallback entries
        before the background build resolves)."""
        if device is None:
            return self._tables_hot
        cached = self._device_tables.get(device)
        if cached is None:
            cached = jax.device_put(self._tables_hot, device)
            self._device_tables[device] = cached
        return cached

    def estimated_device_bytes(self) -> int:
        """Approximate resident bytes this plan pins for its lifetime:
        the committed device tables (hot dict, whatever paths have
        committed so far) plus the host-side index arrays and the
        double-single matrix set when present. Used by the serving
        plan registry (spfft_tpu.serve.registry) for its byte-aware
        LRU budget; an ESTIMATE — XLA executable buffers are excluded
        (they are owned by the compilation cache, not the plan) and a
        still-running background table build is counted at its current
        state rather than joined."""
        pieces = [self._tables_hot]
        if getattr(self, "_ds_mats", None):
            pieces.append(tuple(self._ds_mats.values()))
        p = self.index_plan
        pieces.append((p.value_indices, p.stick_keys))
        leaves = jax.tree_util.tree_leaves(pieces)
        return sum(int(getattr(leaf, "nbytes", 0)) for leaf in leaves)

    @property
    def pallas_active(self) -> bool:
        """True when the compression stages run the Pallas windowed-gather
        kernel (TPU backend, single precision, value order coherent enough
        for the chunk decomposition). False means the XLA gather path."""
        return self._pallas_active

    @property
    def fused_active(self) -> bool:
        """True when at least one direction runs the FUSED
        compression+z-DFT kernel (ops/fused_kernel.py): the dense
        ``(num_sticks, dim_z)`` planar stick intermediate between the
        compression gather and the z stage never touches HBM there.
        Per-direction detail in :attr:`fused_fallback_reasons`."""
        self._finalize()
        return self._fused_active_flag

    @property
    def fused_geometry(self) -> dict:
        """Kernel geometry of each fused direction (``"backward"`` /
        ``"forward"``): ``"super_tile"`` (one grid step per super-tile
        of whole sticks) or ``"narrow"`` (one per 1024-slot tile);
        directions that are not fused are absent."""
        self._finalize()
        return {d: t.geometry for d, t in self._fused_directions()}

    @property
    def fused_grid_steps(self) -> dict:
        """Grid steps of each fused direction's kernel per transform
        (per field of a batched call), keyed like :attr:`fused_geometry`."""
        self._finalize()
        return {d: t.grid_steps for d, t in self._fused_directions()}

    def _fused_directions(self):
        for which, d in (("dec", "backward"), ("cmp", "forward")):
            if self._fused_box.get(which) is not None:
                yield d, self._fused_box[which]

    @property
    def fused_fallback_reasons(self) -> dict:
        """Per-direction fallback reasons of the fused
        compression+z-DFT gate: ``{"dec": reason, "cmp": reason}`` with
        entries only for directions that DECLINED (empty dict = both
        fused, or the fused path was never in play — non-mdft pipeline,
        disabled, or no Pallas build). Reasons mirror the
        ``spfft_plan_pallas_fallback_total{stage,reason}`` counter."""
        self._finalize()
        return dict(self._fused_reasons)

    @property
    def pair_values_io(self) -> bool:
        """True when this plan's device-side value arrays use the planar
        PAIR layout (2, num_values) — row 0 real, row 1 imaginary —
        instead of interleaved rows (num_values, 2); large plans only
        (see PAIR_IO_THRESHOLD). ``backward`` accepts both (and complex/
        numpy inputs as always); ``forward``/``apply_pointwise`` then
        RETURN the pair layout; ``np.asarray(out).T`` gives rows."""
        return self._pair_io

    # -- reference Transform getters (transform.hpp:91-151) -----------------
    @property
    def transform_type(self) -> TransformType:
        return self.index_plan.transform_type

    @property
    def dim_x(self) -> int:
        return self.index_plan.dim_x

    @property
    def dim_y(self) -> int:
        return self.index_plan.dim_y

    @property
    def dim_z(self) -> int:
        return self.index_plan.dim_z

    @property
    def local_z_length(self) -> int:
        return self.index_plan.dim_z

    @property
    def local_z_offset(self) -> int:
        return 0

    @property
    def local_slice_size(self) -> int:
        """dim_x * dim_y * local_z_length (reference: transform.cpp:99)."""
        return self.dim_x * self.dim_y * self.local_z_length

    @property
    def num_local_elements(self) -> int:
        return self.index_plan.num_values

    @property
    def num_global_elements(self) -> int:
        return self.index_plan.num_values

    @property
    def global_size(self) -> int:
        return self.dim_x * self.dim_y * self.dim_z

    # -- jitted pipelines ----------------------------------------------------
    @property
    def _is_r2c(self) -> bool:
        return self.index_plan.hermitian

    def _decompress(self, values_il, tables, pallas=True):
        p = self.index_plan
        if not pallas or not self._pallas_active \
                or self._pallas["dec"] is None:
            with jax.named_scope("spfft.layout"):
                if self._pair_io and values_il.shape[0] == 2:
                    values_il = values_il.T  # pair boundary -> rows
                values_il = values_il.astype(self._rdt)
            return stages.decompress(values_il, tables["slot_src"],
                                     self._s_pad, p.dim_z)
        from .ops import gather_kernel as gk
        t = self._pallas["dec"]
        re, im = gk.planar_from_interleaved(values_il.astype(np.float32),
                                            t.src_rows, pair=self._pair_io)
        out_re, out_im = gk.run_gather(re, im, tables["dec_tabs"], t)
        with jax.named_scope("spfft.gather"):
            flat = (out_re.reshape(-1)[:t.num_out]
                    + 1j * out_im.reshape(-1)[:t.num_out])
            return flat.reshape(self._s_pad, p.dim_z)

    def _compress(self, sticks, tables, scale, pallas=True):
        p = self.index_plan
        if not pallas or not self._pallas_active \
                or self._pallas["cmp"] is None:
            values = stages.compress(sticks, tables["value_indices"], scale)
            with jax.named_scope("spfft.layout"):
                return values.T if self._pair_io else values
        from .ops import gather_kernel as gk
        t = self._pallas["cmp"]
        re, im = gk.planar_from_complex(sticks, t.src_rows)
        out_re, out_im = gk.run_gather(re, im, tables["cmp_tabs"], t)
        values = gk.interleaved_from_planar(out_re, out_im, t.num_out,
                                            pair=self._pair_io)
        if scale is not None:
            with jax.named_scope("spfft.compress"):
                values = values * jnp.asarray(scale, values.dtype)
        return values

    def _decompress_planar(self, values_il, tables, pallas=True):
        """Values -> PLANAR stick channels (sr, si), each (s_pad, dim_z)
        f32 — the mdft pipeline's native form (no complex interleave)."""
        p = self.index_plan
        if not pallas or not self._pallas_active \
                or self._pallas["dec"] is None:
            with jax.named_scope("spfft.decompress"):
                if self._pair_io and values_il.shape[0] == 2:
                    values_il = values_il.T
                flat = stages.gather_rows_with_sentinel(
                    values_il.astype(self._rdt), tables["slot_src"])
                return (flat[:, 0].reshape(self._s_pad, p.dim_z),
                        flat[:, 1].reshape(self._s_pad, p.dim_z))
        from .ops import gather_kernel as gk
        t = self._pallas["dec"]
        re, im = gk.planar_from_interleaved(values_il.astype(np.float32),
                                            t.src_rows, pair=self._pair_io)
        out_re, out_im = gk.run_gather(re, im, tables["dec_tabs"], t)
        with jax.named_scope("spfft.gather"):
            return (out_re.reshape(-1)[:t.num_out].reshape(self._s_pad,
                                                           p.dim_z),
                    out_im.reshape(-1)[:t.num_out].reshape(self._s_pad,
                                                           p.dim_z))

    def _compress_planar(self, sr, si, tables, pallas=True):
        """PLANAR stick channels -> the plan's value output layout
        (scaling already folded into the z-DFT matrix upstream)."""
        p = self.index_plan
        if not pallas or not self._pallas_active \
                or self._pallas["cmp"] is None:
            with jax.named_scope("spfft.compress"):
                flat = jnp.stack([sr.reshape(-1), si.reshape(-1)], axis=-1)
                values = flat[tables["value_indices"]]
                return values.T if self._pair_io else values
        from .ops import gather_kernel as gk
        t = self._pallas["cmp"]
        pad = t.src_rows * 128 - sr.size
        with jax.named_scope("spfft.layout"):
            re = jnp.pad(sr.reshape(-1), (0, pad)).reshape(t.src_rows, 128)
            im = jnp.pad(si.reshape(-1), (0, pad)).reshape(t.src_rows, 128)
        out_re, out_im = gk.run_gather(re, im, tables["cmp_tabs"], t)
        return gk.interleaved_from_planar(out_re, out_im, t.num_out,
                                          pair=self._pair_io)

    def _decompress_zdft(self, values_il, tables):
        """Values -> z-TRANSFORMED planar stick channels (sr, si) in ONE
        fused Pallas kernel (ops/fused_kernel.run_decompress_zdft): the
        dense pre-FFT stick intermediate never touches HBM. Accepts the
        batched (B, ...) boundary too (batched kernel grid)."""
        from .ops import fused_kernel as fkm
        from .ops import gather_kernel as gk
        t = self._fused_box["dec"]
        re, im = gk.planar_from_interleaved(values_il.astype(np.float32),
                                            t.src_rows, pair=self._pair_io)
        sr, si = fkm.run_decompress_zdft(
            re, im, tables["fzd_tabs"], tables["fzd_mats"], t,
            interpret=not self._backend_ok)
        s = self._s_pad
        with jax.named_scope("spfft.decompress_z"):
            rows = sr.shape[-2]
            if rows < s:
                # super-tile tables end at the last real stick; the
                # unpack's sentinel reads the zero rows past it
                pad = [(0, 0)] * (sr.ndim - 2) + [(0, s - rows), (0, 0)]
                return jnp.pad(sr, pad), jnp.pad(si, pad)
            return sr[..., :s, :], si[..., :s, :]

    def _zdft_compress(self, sr, si, tables, scaled: bool):
        """RAW planar stick channels -> the plan's value output layout
        in ONE fused Pallas kernel (ops/fused_kernel.run_zdft_compress);
        FULL scaling is folded into the plan-time matrix triple
        (compile-time scaling). Accepts batched (B, ...) sticks."""
        from .ops import fused_kernel as fkm
        from .ops import gather_kernel as gk
        t = self._fused_box["cmp"]
        sr, si = fkm.pad_sticks_planar(sr, si, t.src_sticks)
        out_re, out_im = fkm.run_zdft_compress(
            sr, si, tables["fzc_tabs"],
            tables["fzc_mats_s" if scaled else "fzc_mats"], t,
            interpret=not self._backend_ok)
        return gk.interleaved_from_planar(out_re, out_im, t.num_out,
                                          pair=self._pair_io)

    def _bwd_space_tp(self, values_il, tables, pallas=True):
        """The mdft backward pipeline with the fused-kernel dispatch:
        values -> planar/real space. Fused when the gate admitted the
        decompress direction, else decompress + z + tail unfused."""
        if self._fused_on("dec", pallas):
            sr, si = self._decompress_zdft(values_il, tables)
            return self._backward_after_z(sr, si, tables)
        sr, si = self._decompress_planar(values_il, tables, pallas)
        return self._backward_rest_tp(sr, si, tables)

    def _fwd_values_tp(self, space_p, tables, scaled: bool, pallas=True):
        """The mdft forward pipeline with the fused-kernel dispatch:
        planar/real space -> values in the plan's output layout."""
        if self._fused_on("cmp", pallas):
            sr, si = self._forward_pre_z(space_p, tables)
            return self._zdft_compress(sr, si, tables, scaled)
        scale = 1.0 / self.global_size if scaled else None
        sr, si = self._forward_head_tp(space_p, tables, scale)
        return self._compress_planar(sr, si, tables, pallas)

    def _backward_rest_tp(self, sr, si, tables):
        """Matmul-DFT T-layout tail of backward, fully PLANAR (separate
        re/im f32 arrays — XLA stores c64 interleaved T(2,128), so every
        complex materialisation between stages is an interleave copy the
        planar form never pays): z-DFT on sticks, unpack into the
        TRANSPOSED plane grid (planes, x, y), y-DFT on the minor axis,
        one swap, then the x-stage. Returns (xr, xi) planar space for
        C2C, the real space slab for R2C."""
        from .ops import dft
        p = self.index_plan
        if self._is_r2c and p.zero_stick_id is not None:
            # complete the (0,0) stick: conj = im sign flip in planar form
            zid = p.zero_stick_id
            with jax.named_scope("spfft.stick_symmetry"):
                rr, ri = sr[zid], si[zid]
                nz = (rr != 0) | (ri != 0)
                sr = sr.at[zid].set(
                    jnp.where(nz, rr, jnp.roll(rr[::-1], 1)))
                si = si.at[zid].set(
                    jnp.where(nz, ri, -jnp.roll(ri[::-1], 1)))
        with jax.named_scope("spfft.z"):
            sr, si = dft.pdft_last_opt(sr, si,
                                       dft.c2c_mats(p.dim_z, dft.BACKWARD))
        return self._backward_after_z(sr, si, tables)

    def _backward_after_z(self, sr, si, tables):
        """Everything of the T-layout backward tail AFTER the z-stage:
        unpack into the transposed plane grid, y-DFT, swap, x-stage.
        Split out so the fused decompress+z-DFT kernel (which emits
        already-transformed sticks) can join the pipeline here."""
        from .ops import dft
        p = self.index_plan
        xf = p.dim_x_freq
        unpack = stages.sticks_to_grid_padded \
            if self._s_pad > p.num_sticks else stages.sticks_to_grid
        if self._split_x is not None:
            x0, w = self._split_x
            col_tab = tables["col_inv_sub_t"]
            rows = tuple(int(r) for r in (x0 + np.arange(w)) % xf)
        else:
            x0, w = 0, xf
            col_tab = tables["col_inv_t"]
            rows = None
        gr = unpack(sr, col_tab, w, p.dim_y)
        gi = unpack(si, col_tab, w, p.dim_y)
        if self._is_r2c and x0 == 0:
            # complete the x=0 sub-plane along y (contiguous in T layout)
            with jax.named_scope("spfft.plane_symmetry"):
                cr, ci = gr[:, 0, :], gi[:, 0, :]
                nz = (cr != 0) | (ci != 0)
                gr = gr.at[:, 0, :].set(
                    jnp.where(nz, cr, jnp.roll(cr[:, ::-1], 1, axis=-1)))
                gi = gi.at[:, 0, :].set(
                    jnp.where(nz, ci, -jnp.roll(ci[:, ::-1], 1, axis=-1)))
        y_mats = dft.c2c_mats(p.dim_y, dft.BACKWARD)
        if self._is_r2c:
            mats = dft.c2r_mats(p.dim_x) if rows is None \
                else dft.sub_rows_c2r_mats(p.dim_x, rows)
            return dft.pdft2_minor_cr(gr, gi, y_mats, mats)
        mats = dft.c2c_mats(p.dim_x, dft.BACKWARD) if rows is None \
            else dft.sub_rows_mats(p.dim_x, dft.BACKWARD, rows)
        return dft.pdft2_minor(gr, gi, y_mats, mats)

    def _backward_rest_t(self, sticks, tables):
        """Complex-dtype wrapper of :meth:`_backward_rest_tp` (the batched
        path feeds complex sticks); returns the public interleaved (C2C)
        or real (R2C) space layout."""
        with jax.named_scope("spfft.layout"):
            sr, si = jnp.real(sticks), jnp.imag(sticks)
        out = self._backward_rest_tp(sr, si, tables)
        if self._is_r2c:
            return out
        return self._space_interleaved(out)

    def _forward_head_tp(self, space_p, tables, scale):
        """Planar T-layout head of forward: x-stage on the minor axis,
        one swap into the transposed grid, y-DFT minor, pack, then the
        z-DFT with any FULL scaling folded into its matrix. ``space_p``
        is (xr, xi) planar for C2C, the real slab for R2C. Returns
        (sr, si) planar sticks."""
        from .ops import dft
        p = self.index_plan
        sr, si = self._forward_pre_z(space_p, tables)
        with jax.named_scope("spfft.z"):
            return dft.pdft_last_opt(
                sr, si, dft.c2c_mats(p.dim_z, dft.FORWARD,
                                     scale=scale if scale else 1.0))

    def _forward_pre_z(self, space_p, tables):
        """The forward head UP TO the z-stage (xy stages + pack into
        raw sticks) — the seam the fused z-DFT+compress kernel joins
        at. Returns un-transformed (sr, si) planar sticks."""
        from .ops import dft
        p = self.index_plan
        xf = p.dim_x_freq
        y_mats = dft.c2c_mats(p.dim_y, dft.FORWARD)
        if self._split_x is not None:
            x0, w = self._split_x
            cols = tuple(int(c) for c in (x0 + np.arange(w)) % xf)
            cols_tab = tables["scatter_cols_sub_t"]
            if self._is_r2c:
                gr, gi = dft.prdft2_minor(
                    space_p.astype(self._rdt),
                    dft.sub_cols_r2c_mats(p.dim_x, cols), y_mats)
            else:
                gr, gi = dft.pdft2_minor(
                    space_p[0].astype(self._rdt),
                    space_p[1].astype(self._rdt),
                    dft.sub_cols_mats(p.dim_x, dft.FORWARD, cols), y_mats)
        else:
            cols_tab = tables["scatter_cols_t"]
            if self._is_r2c:
                gr, gi = dft.prdft2_minor(space_p.astype(self._rdt),
                                          dft.r2c_mats(p.dim_x), y_mats)
            else:
                gr, gi = dft.pdft2_minor(space_p[0].astype(self._rdt),
                                         space_p[1].astype(self._rdt),
                                         dft.c2c_mats(p.dim_x, dft.FORWARD),
                                         y_mats)
        sr = stages.grid_to_sticks(gr, cols_tab)
        si = stages.grid_to_sticks(gi, cols_tab)
        return sr, si

    def _forward_head_t(self, space, tables, scale):
        """Complex-dtype wrapper of :meth:`_forward_head_tp` (batched
        path): interleaved/real space in, complex sticks out."""
        sp = space if self._is_r2c else self._space_planar(space)
        sr, si = self._forward_head_tp(sp, tables, scale)
        with jax.named_scope("spfft.layout"):
            return sr + 1j * si

    def _backward_rest(self, sticks, tables):
        """Everything after decompress: symmetry, z-IFFT, unpack, xy-IFFT."""
        if self._use_mdft:
            return self._backward_rest_t(sticks, tables)
        p = self.index_plan
        if self._is_r2c and p.zero_stick_id is not None:
            zid = p.zero_stick_id
            sticks = sticks.at[zid].set(
                stages.complete_stick_hermitian(sticks[zid]))
        sticks = stages.z_backward(sticks)
        unpack = stages.sticks_to_grid_padded \
            if self._s_pad > p.num_sticks else stages.sticks_to_grid
        if self._split_x is not None:
            x0, w = self._split_x
            sub = unpack(sticks, tables["col_inv_sub"], p.dim_y, w)
            if self._is_r2c:
                if x0 == 0:
                    sub = stages.complete_plane_hermitian(sub)
                return stages.xy_backward_r2c_split(sub, x0, p.dim_x,
                                                    p.dim_x_freq)
            grid = stages.xy_backward_c2c_split(sub, x0, p.dim_x)
        else:
            grid = unpack(sticks, tables["col_inv"], p.dim_y, p.dim_x_freq)
            if self._is_r2c:
                grid = stages.complete_plane_hermitian(grid)
                return stages.xy_backward_r2c(grid, p.dim_x)
            grid = stages.xy_backward_c2c(grid)
        with jax.named_scope("spfft.grid_io"):
            return complex_to_interleaved(grid)

    # -- on-device double (double-single channels, ops/dsdft.py) ------------
    @staticmethod
    def _ds_complete(ch, idx):
        """Hermitian completion of ``ch[..., idx, :]`` along the minor
        axis on double-single channels [rh, rl, ih, il]: where an
        element was not supplied (all four channels zero), fill the
        conj reflection — sign-flipped on the imaginary channels; hi
        and lo transform identically (the DS twin of
        stages.complete_stick_hermitian / the x=0 plane completion)."""
        rows = tuple(c[..., idx, :] for c in ch)
        nz = (rows[0] != 0) | (rows[1] != 0) \
            | (rows[2] != 0) | (rows[3] != 0)

        def refl(v):
            return jnp.roll(v[..., ::-1], 1, axis=-1)

        return tuple(
            c.at[..., idx, :].set(jnp.where(
                nz, r, refl(r) if k < 2 else -refl(r)))
            for k, (c, r) in enumerate(zip(ch, rows)))

    def _ds_backward_impl(self, values_il, tables):
        """Backward on (N, 4) double-single channels [rh, rl, ih, il]:
        gathers are dtype-agnostic row moves, every DFT stage is the
        exact-sliced complex contraction, T-layout with one swap per
        direction (same dataflow as the mdft pipeline). Returns the
        (dim_z, dim_y, dim_x, 4) channel slab."""
        from .ops import dsdft
        p = self.index_plan
        with jax.named_scope("spfft.decompress"):
            flat = stages.gather_rows_with_sentinel(values_il,
                                                    tables["slot_src"])
            ch = tuple(flat[..., k].reshape(flat.shape[:-2]
                                            + (p.num_sticks, p.dim_z))
                       for k in range(4))
        if self._is_r2c and p.zero_stick_id is not None:
            # complete the (0,0) stick (conj reflection = sign flip on
            # the imaginary channels) — hi and lo transform identically
            with jax.named_scope("spfft.stick_symmetry"):
                ch = self._ds_complete(ch, p.zero_stick_id)
        with jax.named_scope("spfft.z"):
            ch = dsdft.ds_cdft_last(*ch, self._ds_mats["z_b"])
        ch = tuple(stages.sticks_to_grid(c, tables["col_inv_t"],
                                         p.dim_x_freq, p.dim_y)
                   for c in ch)
        if self._is_r2c:
            # complete the x=0 sub-plane along y (minor axis in T layout)
            with jax.named_scope("spfft.plane_symmetry"):
                ch = self._ds_complete(ch, 0)
        with jax.named_scope("spfft.xy"):
            ch = dsdft.ds_cdft_last(*ch, self._ds_mats["y_b"])
            ch = tuple(jnp.swapaxes(c, -1, -2) for c in ch)
            if self._is_r2c:
                ch = dsdft.ds_irdft_last(*ch, self._ds_mats["x_b"])
            else:
                ch = dsdft.ds_cdft_last(*ch, self._ds_mats["x_b"])
        with jax.named_scope("spfft.grid_io"):
            return jnp.stack(ch, axis=-1)

    def _ds_forward_impl(self, space4, tables, scaled: bool):
        """Forward mirror: (dim_z, dim_y, dim_x, 4) -> (N, 4), FULL
        scaling folded into the f64 z matrix before slicing."""
        from .ops import dsdft
        with jax.named_scope("spfft.grid_io"):
            ch = tuple(space4[..., k] for k in range(space4.shape[-1]))
        with jax.named_scope("spfft.xy"):
            if self._is_r2c:
                ch = dsdft.ds_rdft_last(*ch, self._ds_mats["x_f"])
            else:
                ch = dsdft.ds_cdft_last(*ch, self._ds_mats["x_f"])
            ch = tuple(jnp.swapaxes(c, -1, -2) for c in ch)
            ch = dsdft.ds_cdft_last(*ch, self._ds_mats["y_f"])
        ch = tuple(stages.grid_to_sticks(c, tables["scatter_cols_t"])
                   for c in ch)
        with jax.named_scope("spfft.z"):
            ch = dsdft.ds_cdft_last(
                *ch, self._ds_mats["z_fs" if scaled else "z_f"])
        with jax.named_scope("spfft.compress"):
            flat = jnp.stack([c.reshape(-1) for c in ch], axis=-1)
            return flat[tables["value_indices"]]

    def _ds_space_to_host(self, out) -> np.ndarray:
        """Channel slab -> host f64: (…, 4) -> interleaved (…, 2), or
        the R2C real slab (…, 2) [hi, lo] -> real (…,)."""
        from .ops import dsdft
        a = np.asarray(out)
        if a.shape[-1] == 2:  # real (hi, lo)
            return dsdft.combine_host_f64(a[..., 0], a[..., 1])
        return np.stack([dsdft.combine_host_f64(a[..., 0], a[..., 1]),
                         dsdft.combine_host_f64(a[..., 2], a[..., 3])],
                        axis=-1)

    _ds_values_to_host = _ds_space_to_host  # same channel layout

    @staticmethod
    @jax.named_scope("spfft.grid_io")
    def _space_interleaved(planar):
        """Planar (re, im) C2C space -> the public interleaved
        (..., 2) layout."""
        return jnp.stack([planar[0], planar[1]], axis=-1)

    @staticmethod
    @jax.named_scope("spfft.grid_io")
    def _space_planar(space):
        """The public interleaved C2C space (..., 2) -> planar
        (re, im)."""
        return space[..., 0], space[..., 1]

    @jax.named_scope("spfft.value_conj")
    def _apply_value_conj(self, values, *, batched=False):
        """Sign-flip the imaginary lane of the values folded from the
        redundant hermitian half (:attr:`IndexPlan.value_conj`): the
        backward input and the forward output are conjugated at the
        boundary in whatever layout the values take — interleaved
        (..., N, 2), the planar pair (..., 2, N), or double-single
        channels (..., N, 4). No-op (no graph nodes) when nothing was
        folded."""
        if self._conj_mults is None:
            return values
        if self._ds:
            m = self._conj_mults["ds"]
        elif self._pair_io and values.shape[1 if batched else 0] == 2:
            m = self._conj_mults["pair"]
        else:
            m = self._conj_mults["il"]
        return values * jnp.asarray(m, values.dtype)

    def _backward_impl(self, values_il, tables, *, pallas=True):
        values_il = self._apply_value_conj(values_il)
        if self._ds:
            return self._ds_backward_impl(values_il, tables)
        if self._use_mdft:
            out = self._bwd_space_tp(values_il, tables, pallas)
            if self._is_r2c:
                return out
            return self._space_interleaved(out)
        return self._backward_rest(
            self._decompress(values_il, tables, pallas), tables)

    def _forward_head(self, space, tables, scale=None):
        """Everything before compress: xy-FFT, pack, z-FFT -> sticks.
        ``scale`` (mdft path only) folds FULL scaling into the z matrix."""
        if self._use_mdft:
            return self._forward_head_t(space, tables, scale)
        if self._is_r2c:
            if self._split_x is not None:
                x0, w = self._split_x
                grid = stages.xy_forward_r2c_split(
                    space.astype(self._rdt), x0, w)
                sticks = stages.grid_to_sticks(grid,
                                               tables["scatter_cols_sub"])
            else:
                grid = stages.xy_forward_r2c(space.astype(self._rdt))
                sticks = stages.grid_to_sticks(grid, tables["scatter_cols"])
        else:
            with jax.named_scope("spfft.grid_io"):
                space = interleaved_to_complex(space).astype(self._cdt)
            if self._split_x is not None:
                x0, w = self._split_x
                grid = stages.xy_forward_c2c_split(space, x0, w)
                sticks = stages.grid_to_sticks(grid,
                                               tables["scatter_cols_sub"])
            else:
                grid = stages.xy_forward_c2c(space)
                sticks = stages.grid_to_sticks(grid, tables["scatter_cols"])
        return stages.z_forward(sticks)

    def _forward_impl(self, space, tables, *, scaled: bool, pallas=True):
        if self._ds:
            return self._apply_value_conj(
                self._ds_forward_impl(space, tables, scaled))
        if self._use_mdft:  # planar pipeline, scale folded into z matrix
            sp = space if self._is_r2c else self._space_planar(space)
            return self._apply_value_conj(
                self._fwd_values_tp(sp, tables, scaled, pallas))
        scale = 1.0 / self.global_size if scaled else None
        sticks = self._forward_head(space, tables)
        return self._apply_value_conj(
            self._compress(sticks, tables, scale, pallas))

    # -- batched execution ---------------------------------------------------
    def _decompress_batched(self, values_b, tables):
        """(B, num_values, 2) -> (B, num_sticks, dim_z) — one batched-grid
        kernel launch when the Pallas path is active, vmapped XLA gather
        otherwise."""
        p = self.index_plan
        if not self._pallas_active or self._pallas["dec"] is None:
            if self._pair_io and values_b.shape[1] == 2:
                values_b = jnp.swapaxes(values_b, 1, 2)  # pair -> rows
            return jax.vmap(
                lambda v: stages.decompress(v.astype(self._rdt),
                                            tables["slot_src"],
                                            self._s_pad, p.dim_z))(values_b)
        from .ops import gather_kernel as gk
        t = self._pallas["dec"]
        re, im = gk.planar_from_interleaved(values_b.astype(np.float32),
                                            t.src_rows,
                                            pair=self._pair_io)
        out_re, out_im = gk.run_gather(re, im, tables["dec_tabs"], t)
        B = values_b.shape[0]
        flat = (out_re.reshape(B, -1)[:, :t.num_out]
                + 1j * out_im.reshape(B, -1)[:, :t.num_out])
        return flat.reshape(B, self._s_pad, p.dim_z)

    def _compress_batched(self, sticks_b, tables, scale):
        """(B, num_sticks, dim_z) -> (B, num_values, 2) — or the planar
        pair (B, 2, num_values) for large plans (see pair_values_io)."""
        p = self.index_plan
        if not self._pallas_active or self._pallas["cmp"] is None:
            values = jax.vmap(
                lambda s: stages.compress(s, tables["value_indices"],
                                          scale))(sticks_b)
            return jnp.swapaxes(values, 1, 2) if self._pair_io else values
        from .ops import gather_kernel as gk
        t = self._pallas["cmp"]
        re, im = gk.planar_from_complex(sticks_b, t.src_rows)
        out_re, out_im = gk.run_gather(re, im, tables["cmp_tabs"], t)
        values = gk.interleaved_from_planar(out_re, out_im, t.num_out,
                                            pair=self._pair_io)
        if scale is not None:
            values = values * jnp.asarray(scale, values.dtype)
        return values

    def _backward_after_z_il(self, sr, si, tables):
        """:meth:`_backward_after_z` in the public space layout
        (interleaved for C2C, real for R2C) — the batched fused path's
        vmap body."""
        out = self._backward_after_z(sr, si, tables)
        if self._is_r2c:
            return out
        return self._space_interleaved(out)

    def _backward_impl_batched(self, values_b, tables):
        values_b = self._apply_value_conj(values_b, batched=True)
        if self._ds:
            return jax.vmap(
                lambda v: self._ds_backward_impl(v, tables))(values_b)
        if self._use_mdft and self._fused_on("dec"):
            # one batched-grid fused kernel launch, then the xy tail
            # per slab (the z-transformed sticks never touch HBM dense)
            sr_b, si_b = self._decompress_zdft(values_b, tables)
            return jax.vmap(self._backward_after_z_il,
                            in_axes=(0, 0, None))(sr_b, si_b, tables)
        sticks_b = self._decompress_batched(values_b, tables)
        return jax.vmap(self._backward_rest,
                        in_axes=(0, None))(sticks_b, tables)

    def _forward_impl_batched(self, space_b, tables, *, scaled: bool):
        if self._ds:
            return self._apply_value_conj(jax.vmap(
                lambda sp: self._ds_forward_impl(sp, tables, scaled))(
                    space_b), batched=True)
        scale = 1.0 / self.global_size if scaled else None
        if self._use_mdft and self._fused_on("cmp"):
            sp_b = space_b if self._is_r2c \
                else self._space_planar(space_b)
            sr_b, si_b = jax.vmap(self._forward_pre_z,
                                  in_axes=(0, None))(sp_b, tables)
            return self._apply_value_conj(
                self._zdft_compress(sr_b, si_b, tables, scaled),
                batched=True)
        if self._use_mdft:
            sticks_b = jax.vmap(
                lambda s, t: self._forward_head(s, t, scale),
                in_axes=(0, None))(space_b, tables)
            return self._apply_value_conj(
                self._compress_batched(sticks_b, tables, None),
                batched=True)
        sticks_b = jax.vmap(self._forward_head,
                            in_axes=(0, None))(space_b, tables)
        return self._apply_value_conj(
            self._compress_batched(sticks_b, tables, scale), batched=True)

    @staticmethod
    def _scaled_jits(impl) -> dict:
        """Scaling -> ``impl(arg, tables, scaled=...)`` jitted under a
        program name of its own (see :func:`_entry`)."""
        return {s: jax.jit(_entry(f"{impl.__name__}_{s.name.lower()}", impl,
                                  scaled=s is Scaling.FULL))
                for s in (Scaling.NONE, Scaling.FULL)}

    def _pair_entry(self, fn, scaling: Scaling):
        """The fused round trip ``(values, tables, *fn_args) -> values``
        of :meth:`apply_pointwise`, not jitted, under the program name
        ``_pair_impl_<scaling>``."""
        scaling = Scaling(scaling)
        return _entry(f"_pair_impl_{scaling.name.lower()}", self._pair_impl,
                      scaled=scaling is Scaling.FULL, fn=fn)

    def _batched_jits(self):
        """Lazily-built batched executables over a leading batch axis.

        The reference's multi-transform hand-interleaves the phases of N
        transforms for comm/compute overlap (reference:
        multi_transform_internal.hpp:47-145). For N transforms sharing one
        plan, the TPU-native form is a single executable with a batch
        dimension: XLA sees N× larger FFT batches and one gather per stage
        instead of N dispatches. The compression stages run the Pallas
        kernel with a batched grid (same tables, one launch) when active."""
        if self._batched is None:
            self._batched = {
                "backward": jax.jit(self._backward_impl_batched),
                **self._scaled_jits(self._forward_impl_batched),
            }
        return self._batched

    def _stack_coerced(self, items, coerce):
        """Stack per-request arrays into the batch boundary layout. When
        every element coerces to a HOST array (the serving executor's
        common case: numpy request payloads), stack on host and pay ONE
        device transfer — ``jnp.stack`` over B separately-committed
        device arrays costs a device concat kernel plus B puts, which
        measurably erases the batching win for ms-scale transforms
        (spfft_tpu.serve; measured on the CPU backend)."""
        coerced = [coerce(v) for v in items]
        if all(isinstance(c, np.ndarray) for c in coerced):
            return jnp.asarray(np.stack(coerced))
        return jnp.stack(coerced)

    def batch_row_template(self, kind: str):
        """``(shape, dtype)`` of one COERCED host row of a batched
        execution — ``kind`` is ``"values"`` (backward input) or
        ``"space"`` (forward input) — or ``None`` when rows coerce to
        device arrays (double-single plans split on device put).

        This is the contract the serving executor's preallocated staging
        buffers rely on: a host buffer of ``(B,) + shape`` and exactly
        this dtype, filled row-by-row with ``_coerce_values`` /
        ``_coerce_space`` outputs, is accepted by
        :meth:`backward_batched` / :meth:`forward_batched` without any
        per-row re-coercion or host re-stack."""
        if self._ds:
            return None
        p = self.index_plan
        if kind == "values":
            if self._pair_io:
                return (2, p.num_values), self._rdt
            return (p.num_values, 2), self._rdt
        if kind != "space":
            raise InvalidParameterError(
                f"kind must be 'values' or 'space', got {kind!r}")
        shape3 = (self.local_z_length, p.dim_y, p.dim_x)
        if self._is_r2c:
            return shape3, self._rdt
        return shape3 + (2,), self._rdt

    def _prestaged(self, batch, per) -> bool:
        """True when ``batch`` is a host array already in the coerced
        batched layout ``(B,) + per`` at the plan's exact real dtype —
        the serving executor's reusable staging buffers. The dtype check
        is part of the bit-exactness contract: a wider dtype slipping
        through would retrace the jit at that dtype and compute in a
        different precision than the serial path."""
        return (isinstance(batch, np.ndarray)
                and batch.shape[1:] == per
                and batch.dtype == self._rdt)

    def backward_batched(self, values_batch, device=None):
        """Backward-execute a batch: ``values_batch`` is (B, num_values)
        complex or (B, num_values, 2) interleaved ((B, 2, num_values) for
        pair_values_io plans). Returns the (B, ...) stacked space-domain
        result in one fused execution. ``device`` pins the batch to one
        device of a pool (see :meth:`backward`)."""
        with timed_transform("backward_batched") as box:
            per = ((self.index_plan.num_values, 4) if self._ds
                   else (2, self.index_plan.num_values) if self._pair_io
                   else (self.index_plan.num_values, 2))
            if isinstance(values_batch, jax.Array) \
                    and values_batch.shape[1:] == per:
                batch = values_batch
            elif self._prestaged(values_batch, per):
                # pre-staged host buffer (serving executor): one
                # transfer, no per-row coercion
                batch = jnp.asarray(values_batch)
            else:
                batch = self._stack_coerced(values_batch,
                                            self._coerce_values)
            self._finalize()
            if device is not None:
                batch = jax.device_put(batch, device)
            box.value = self._guarded(
                "dec", lambda: self._call_aot_or_jit(
                    "batched_backward", self._batched_jits()["backward"],
                    batch, device))
            if self._ds:
                box.value = self._ds_space_to_host(box.value)
        return box.value

    def forward_batched(self, space_batch, scaling: Scaling = Scaling.NONE,
                        device=None):
        """Forward-execute a batch of space-domain slabs in one fused
        execution. Returns (B, num_values, 2) interleaved values —
        (B, 2, num_values) for pair_values_io plans. ``device`` as in
        :meth:`backward`."""
        scaling = Scaling(scaling)
        with timed_transform("forward_batched") as box:
            if self._ds:
                # coerced DS slabs always carry a trailing channel axis:
                # (B, z, y, x, 2) hi/lo for R2C, (B, z, y, x, 4) for C2C
                # — a raw real R2C batch is also ndim 4, so the channel
                # count must be checked, not just the rank
                nch = 2 if self._is_r2c else 4
                coerced = (isinstance(space_batch, jax.Array)
                           and space_batch.ndim == 5
                           and space_batch.shape[-1] == nch)
            else:
                coerced = (isinstance(space_batch, jax.Array)
                           and space_batch.ndim
                           == (4 if self._is_r2c else 5))
            if coerced:
                batch = space_batch
            elif not self._ds and self._prestaged(
                    space_batch, self.batch_row_template("space")[0]):
                batch = jnp.asarray(space_batch)
            else:
                batch = self._stack_coerced(space_batch,
                                            self._coerce_space)
            self._finalize()
            if device is not None:
                batch = jax.device_put(batch, device)
            box.value = self._guarded(
                "cmp", lambda: self._call_aot_or_jit(
                    "batched_forward_full" if scaling is Scaling.FULL
                    else "batched_forward_none",
                    self._batched_jits()[scaling], batch, device))
            if self._ds:
                box.value = self._ds_values_to_host(box.value)
        return box.value

    # -- fused round trip ----------------------------------------------------
    def _pair_impl(self, values_il, tables, *fn_args, scaled, fn):
        # the ds/mdft branches bypass _backward_impl/_forward_impl, so
        # the hermitian-fold conjugation applies here; the final branch
        # delegates to those impls, which conjugate themselves
        if self._ds:
            # fn is rejected up front (apply_pointwise): a pointwise fn
            # would run at f32 and silently break the double contract
            space4 = self._ds_backward_impl(
                self._apply_value_conj(values_il), tables)
            return self._apply_value_conj(
                self._ds_forward_impl(space4, tables, scaled))
        if self._use_mdft:
            # fully planar round trip; the space domain is materialised
            # in the public interleaved layout ONLY when a pointwise fn
            # needs to see it
            space = self._bwd_space_tp(
                self._apply_value_conj(values_il), tables)
            if fn is not None:
                if self._is_r2c:
                    space = fn(space, *fn_args)
                else:
                    space = self._space_planar(
                        fn(self._space_interleaved(space), *fn_args))
            return self._apply_value_conj(
                self._fwd_values_tp(space, tables, scaled))
        space = self._backward_impl(values_il, tables)
        if fn is not None:
            space = fn(space, *fn_args)
        return self._forward_impl(space, tables, scaled=scaled)

    def apply_pointwise(self, values, fn=None, *fn_args,
                        scaling: Scaling = Scaling.NONE):
        """backward → ``fn(space, *fn_args)`` → forward as ONE fused
        executable.

        The plane-wave-code inner loop (apply a local operator in the space
        domain): ``fn`` receives the space-domain array in its device layout
        — ``(dim_z, dim_y, dim_x, 2)`` interleaved for C2C, real
        ``(dim_z, dim_y, dim_x)`` for R2C — and must return the same shape.
        ``fn=None`` is the identity round trip (the reference benchmark's
        backward+forward pair, benchmark.cpp:84-96). Fusing saves a
        dispatch round trip and lets XLA schedule across the stage
        boundary: 18.6 vs 25.6 ms for the 256^3 identity pair on TPU v5e.

        The compiled executable is cached per ``(fn, scaling)`` by object
        identity, so pass a *stable* callable (module-level function or one
        created once) — a fresh lambda per call recompiles every call and
        grows the cache without bound. Data that changes between calls
        (e.g. the potential field of an SCF iteration) must flow through
        ``fn_args``, which are traced arguments, not compile-time
        constants.

        Returns the (num_values, 2) interleaved frequency values —
        (2, num_values) for pair_values_io plans."""
        scaling = Scaling(scaling)
        if self._ds and fn is not None:
            raise InvalidParameterError(
                "on-device double plans fuse only the identity round "
                "trip (fn=None): a pointwise fn would execute at f32 "
                "and silently break the double contract — compose "
                "backward / fn on the host f64 slab / forward instead "
                "(docs/precision.md)")
        with timed_transform("apply_pointwise") as box:
            values_il = self._coerce_values(values)
            key = (fn, scaling)
            jitted = self._pair_jits.get(key)
            if jitted is None:
                jitted = jax.jit(
                    self._pair_entry(fn, scaling),
                    donate_argnums=(0,) if self.donate_inputs else ())
                self._pair_jits[key] = jitted
            self._finalize()
            if fn is None and not fn_args:
                # the identity pair is a store-exported AOT entry; fn
                # captures are compile-time constants, so only the
                # fn-free round trip can reuse a serialized executable
                box.value = self._call_aot_or_jit(
                    "pair_full" if scaling is Scaling.FULL
                    else "pair_none", jitted, values_il, None)
            else:
                box.value = jitted(values_il, self._tables_hot, *fn_args)
            if self._ds:
                box.value = self._ds_values_to_host(box.value)
        return box.value

    def iterate_pointwise(self, values, fn, *fn_args, steps: int,
                          scaling: Scaling = Scaling.FULL):
        """Run ``steps`` fused round trips values → backward → fn(space) →
        forward → values as ONE executable (``lax.scan`` over the pair), so
        an N-step iterative solver costs a single dispatch.

        ``fn(space, *fn_args)`` as in :meth:`apply_pointwise`; ``fn_args``
        are loop-invariant traced arguments. ``scaling`` defaults to FULL
        so the iteration is a fixed-point map (NONE would multiply by the
        grid size every step). Returns the final (num_values, 2) values.
        Cached per ``(fn, scaling, steps)``; pass a stable callable."""
        scaling = Scaling(scaling)
        if self._ds:
            raise InvalidParameterError(
                "on-device double plans do not fuse iterate_pointwise "
                "(the pointwise fn would execute at f32) — loop "
                "apply_pointwise / backward+forward instead "
                "(docs/precision.md)")
        with timed_transform("iterate_pointwise") as box:
            # the scan carry dtype must match the step output (_rdt);
            # coerce up-front rather than per step
            values_il = self._coerce_values(values).astype(self._rdt)
            key = (fn, scaling, int(steps), "scan")
            jitted = self._pair_jits.get(key)
            if jitted is None:
                pair = self._pair_entry(fn, scaling)

                def iterate_pointwise(values_il, tables, *fn_args):
                    def step(v, _):
                        return pair(v, tables, *fn_args), None
                    out, _ = jax.lax.scan(step, values_il, None,
                                          length=int(steps))
                    return out

                jitted = jax.jit(
                    iterate_pointwise,
                    donate_argnums=(0,) if self.donate_inputs else ())
                self._pair_jits[key] = jitted
            self._finalize()
            box.value = jitted(values_il, self._tables_hot, *fn_args)
        return box.value

    # -- public execution (reference: transform.hpp:198-211) -----------------
    def backward(self, values, device=None):
        """Frequency -> space. ``values`` is (num_values,) complex (or
        interleaved (num_values, 2) real). Returns the space-domain slab:
        (dim_z, dim_y, dim_x, 2) interleaved for C2C, real (dim_z, dim_y,
        dim_x) for R2C. Unnormalised inverse DFT (details.rst
        "Transform Definition").

        ``device`` pins the execution (input + replicated tables) to one
        device of a pool — the serving executor's cross-device
        round-robin; ``None`` keeps the default placement."""
        with timed_transform("backward") as box:
            values_il = self._coerce_values(values)
            self._finalize()
            if device is not None:
                values_il = jax.device_put(values_il, device)
            box.value = self._guarded(
                "dec", lambda: self._call_aot_or_jit(
                    "backward", self._backward_jit, values_il, device))
            if self._ds:
                box.value = self._ds_space_to_host(box.value)
        return box.value

    def _call_aot_or_jit(self, key: str, jitted, arg, device):
        """Dispatch one public execution through the installed AOT
        executable when there is one for this ``key`` and the default
        placement, falling back PERMANENTLY to the jit path on any AOT
        failure (an executable exported under different plan-time env
        decisions can disagree with this process's table pytree — a
        cold-start optimisation must never fail a request)."""
        aot = self._aot.get(key) if self._aot is not None \
            and device is None else None
        tables = self._tables_on(device)
        if aot is not None:
            try:
                return aot.call(arg, tables)
            except Exception as exc:
                self._aot.pop(key, None)
                from . import obs as _obs
                _obs.record_store_aot_skip("call_failed")
                logger.warning(
                    "spfft_tpu: AOT executable %s failed (%r) — "
                    "falling back to the jit path permanently", key,
                    exc)
        return jitted(arg, tables)

    def forward(self, space, scaling: Scaling = Scaling.NONE,
                device=None):
        """Space -> frequency. Returns (num_values, 2) interleaved sparse
        values — (2, num_values) for pair_values_io plans;
        ``scaling=Scaling.FULL`` multiplies by 1/(Nx·Ny·Nz)
        (details.rst "Normalization"). ``device`` as in
        :meth:`backward`."""
        scaling = Scaling(scaling)
        with timed_transform("forward") as box:
            space = self._coerce_space(space)
            self._finalize()
            if device is not None:
                space = jax.device_put(space, device)
            key = "forward_full" if scaling is Scaling.FULL \
                else "forward_none"
            box.value = self._guarded(
                "cmp", lambda: self._call_aot_or_jit(
                    key, self._forward_jit[scaling], space, device))
            if self._ds:
                box.value = self._ds_values_to_host(box.value)
        return box.value

    # -- input coercion ------------------------------------------------------
    def _coerce_values(self, values):
        N = self.index_plan.num_values
        if self._ds:
            from .ops.dsdft import split_host_f64
            if isinstance(values, jax.Array) and values.ndim == 2 \
                    and values.shape == (N, 4):
                return values
            arr = np.asarray(values)
            if arr.shape == (N, 4) and not np.iscomplexobj(arr):
                return jnp.asarray(
                    np.ascontiguousarray(arr.astype(np.float32)))
            if np.iscomplexobj(arr) and arr.shape == (N,):
                re = arr.real.astype(np.float64)
                im = arr.imag.astype(np.float64)
            elif arr.shape == (N, 2):
                re = arr[:, 0].astype(np.float64)
                im = arr[:, 1].astype(np.float64)
            else:
                raise InvalidParameterError(
                    f"expected {N} frequency values, got shape "
                    f"{arr.shape}")
            rh, rl = split_host_f64(re)
            ih, il = split_host_f64(im)
            return jnp.asarray(np.ascontiguousarray(
                np.stack([rh, rl, ih, il], axis=-1)))
        if self._pair_io:
            # planar pair (2, N) device boundary (see pair_values_io)
            if isinstance(values, jax.Array):
                if values.shape == (2, N):
                    return values
                if values.shape == (N, 2):
                    # relayout via host: an on-device transpose materialises
                    # the tiled (N, 2) copy this layout exists to avoid
                    values = np.asarray(values)
            else:
                arr = np.asarray(values)
                if arr.shape == (2, N) and not np.iscomplexobj(arr):
                    # the plan's own output layout, round-tripped via host
                    return jnp.asarray(np.ascontiguousarray(
                        arr.astype(self._rdt)))
            arr = np.asarray(as_interleaved(values, self.precision))
            if arr.shape != (N, 2):
                raise InvalidParameterError(
                    f"expected {N} frequency values, "
                    f"got shape {arr.shape[:-1]}")
            return jnp.asarray(np.ascontiguousarray(arr.T))
        if isinstance(values, jax.Array) and values.ndim == 2 \
                and values.shape == (N, 2):
            return values
        arr = as_interleaved(values, self.precision)
        if arr.shape != (N, 2):
            raise InvalidParameterError(
                f"expected {N} frequency values, "
                f"got shape {arr.shape[:-1]}")
        return arr

    def _coerce_space(self, space):
        p = self.index_plan
        shape3 = (self.local_z_length, p.dim_y, p.dim_x)
        if self._ds:
            from .ops.dsdft import split_host_f64
            nch = 2 if self._is_r2c else 4
            if isinstance(space, jax.Array) \
                    and space.shape == shape3 + (nch,):
                return space
            arr = np.asarray(space)
            if arr.shape == shape3 + (nch,) and not np.iscomplexobj(arr):
                return jnp.asarray(
                    np.ascontiguousarray(arr.astype(np.float32)))
            if self._is_r2c:
                if arr.shape != shape3 or np.iscomplexobj(arr):
                    raise InvalidParameterError(
                        f"expected real space-domain slab {shape3}, "
                        f"got {arr.shape}")
                rh, rl = split_host_f64(arr.astype(np.float64))
                return jnp.asarray(np.ascontiguousarray(
                    np.stack([rh, rl], axis=-1)))
            if np.iscomplexobj(arr) and arr.shape == shape3:
                re = arr.real.astype(np.float64)
                im = arr.imag.astype(np.float64)
            elif arr.shape == shape3 + (2,):
                re = arr[..., 0].astype(np.float64)
                im = arr[..., 1].astype(np.float64)
            else:
                raise InvalidParameterError(
                    f"expected space-domain slab {shape3} complex, "
                    f"got {arr.shape}")
            rh, rl = split_host_f64(re)
            ih, il = split_host_f64(im)
            return jnp.asarray(np.ascontiguousarray(
                np.stack([rh, rl, ih, il], axis=-1)))
        if self._is_r2c:
            arr = space if isinstance(space, jax.Array) \
                else np.asarray(space, self._rdt)
            if arr.shape != shape3:
                raise InvalidParameterError(
                    f"expected real space-domain slab {shape3}, "
                    f"got {arr.shape}")
            return arr
        if isinstance(space, jax.Array) and space.shape == shape3 + (2,):
            return space
        arr = as_interleaved(space, self.precision)
        if arr.shape != shape3 + (2,):
            raise InvalidParameterError(
                f"expected space-domain slab {shape3} complex, "
                f"got {arr.shape[:-1]}")
        return arr


def restore_plan(index_plan: IndexPlan, tables: PlanTables,
                 precision: str = "single", **plan_kwargs) -> TransformPlan:
    """Reconstruct a :class:`TransformPlan` from persisted artifact
    state (:mod:`spfft_tpu.serve.store`): the index plan arrives fully
    materialised and ``tables`` carries the prebuilt gather/fused
    tables, so neither index-table construction nor the background
    compression-table build runs — the restored plan's construction
    cost is the device commit of the tables it ships with.
    ``plan_kwargs`` as in :class:`TransformPlan` (use_pallas,
    donate_inputs, max_rel_error, device_double)."""
    return TransformPlan(index_plan, precision=precision,
                         _restore=tables, **plan_kwargs)


def make_local_plan(transform_type: TransformType, dim_x: int, dim_y: int,
                    dim_z: int, triplets, precision: str = "single",
                    use_pallas: Optional[bool] = None,
                    donate_inputs: bool = False,
                    max_rel_error: Optional[float] = None) -> TransformPlan:
    """Build a local plan from raw index triplets — the moral equivalent of
    ``Grid::create_transform`` without a communicator (reference:
    grid.hpp:138-141). ``donate_inputs=True`` lets XLA reuse the caller's
    input device buffers for outputs (see TransformPlan.donate_inputs).
    ``max_rel_error`` demands an accuracy contract at construction: when
    the calibrated error model (:func:`predicted_rel_error`) says the
    chosen precision cannot meet it, a typed
    :class:`~spfft_tpu.errors.PrecisionContractError` is raised instead
    of returning silently-degraded results."""
    from . import obs as _obs
    with _obs.compile_span("index_plan") as span:
        plan = build_index_plan(TransformType(transform_type), dim_x, dim_y,
                                dim_z, np.asarray(triplets))
    return TransformPlan(plan, precision=precision, use_pallas=use_pallas,
                         donate_inputs=donate_inputs,
                         max_rel_error=max_rel_error,
                         _trace_id=None if span is None else span.trace_id)
