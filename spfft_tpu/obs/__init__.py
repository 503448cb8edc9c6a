"""spfft_tpu.obs — unified observability: tracing, counters, exporters.

The reproduction previously had three disjoint telemetry islands (the
``timing.py`` scope timer, ``serve.metrics`` counters, hand-rolled
bench JSON). This package unifies them behind one process-global
tracer + counter registry and two exporters, so a request can be
followed end-to-end (submit → queue-wait → bucket-formation → stage →
dispatch → device-execute → materialise → resolve) and a fleet scraper
or a human in Perfetto can consume the numbers without reading our
code.

* :mod:`~spfft_tpu.obs.trace` — :class:`Tracer` / :class:`Span` /
  :class:`RequestTrace`; off by default (``SPFFT_TPU_TRACE=1`` or
  :func:`enable`), sampled via ``SPFFT_TPU_TRACE_SAMPLE``, bounded
  ring buffer, zero-unclosed-spans lifecycle contract.
* :mod:`~spfft_tpu.obs.counters` — labelled counter/gauge registry
  (always on; a dict update per record).
* :mod:`~spfft_tpu.obs.exporters` — :func:`export_trace` (Chrome
  trace-event JSON for Perfetto / chrome://tracing) and
  :func:`prometheus_text` (text exposition over ServeMetrics,
  PlanRegistry, timing.GlobalTimer and the obs counters), plus the
  validating :func:`parse_prometheus_text`.
* :mod:`~spfft_tpu.obs.http` — :class:`MetricsServer`, the opt-in
  stdlib HTTP scrape endpoint (``/metrics`` Prometheus text,
  ``/healthz`` readiness JSON, ``/configz`` live knob values); enable
  via ``serve.bench --metrics-port`` or ``SPFFT_TPU_METRICS_PORT``.
* ``python -m spfft_tpu.obs`` — CLI: ``demo`` records a small traced
  serving run and writes both artifacts; ``validate`` structurally
  checks a trace JSON; ``prom`` prints/validates exposition text.

The recorder helpers below are the integration seams the rest of the
codebase calls (plan builds, registry builds, prewarms, distributed
exchange accounting, HLO collective counts). Counter recording is
always on; span recording only when tracing is enabled.

See docs/observability.md for the span taxonomy, exporter formats,
sampling knob and measured overhead.
"""

from __future__ import annotations

import time
from typing import Optional

from .counters import GLOBAL_COUNTERS, Counters
from .exporters import (export_trace, parse_prometheus_text,
                        prometheus_text, trace_events)
from .http import METRICS_PORT_ENV, MetricsServer, port_from_env
from .recorder import (BUNDLE_VERSION, EVENT_SPECS, GLOBAL_JOURNAL,
                       build_incident_bundle, capture_incident,
                       disable_recorder, enable_recorder, flag_trace,
                       maybe_auto_capture, merge_pod_bundle,
                       overhead_probe, record_event, recorder_active,
                       recorder_from_env, recorder_stats,
                       reset_recorder, retained_traces,
                       set_health_provider, set_incident_capturer,
                       set_latency_source, validate_bundle,
                       write_bundle)
from .trace import (GLOBAL_TRACER, RequestTrace, Span, TraceContext,
                    Tracer, active, disable, enable, span_context)

__all__ = [
    "Tracer", "Span", "RequestTrace", "GLOBAL_TRACER",
    "TraceContext", "span_context",
    "Counters", "GLOBAL_COUNTERS",
    "active", "enable", "disable",
    "export_trace", "trace_events", "prometheus_text",
    "parse_prometheus_text",
    "MetricsServer", "METRICS_PORT_ENV", "port_from_env",
    "compile_span",
    "record_compile", "record_plan_build", "record_exchange_plan",
    "record_hlo_counts", "record_plan_fallback", "record_fused_geometry",
    "record_store",
    "record_store_aot_skip",
    # flight recorder (obs.recorder)
    "EVENT_SPECS", "GLOBAL_JOURNAL", "BUNDLE_VERSION",
    "record_event", "enable_recorder", "disable_recorder",
    "recorder_active", "recorder_from_env", "recorder_stats",
    "reset_recorder", "flag_trace", "retained_traces",
    "build_incident_bundle", "capture_incident", "write_bundle",
    "maybe_auto_capture", "merge_pod_bundle", "validate_bundle",
    "set_health_provider", "set_incident_capturer",
    "set_latency_source", "overhead_probe",
]


def record_store(event: str, reason: Optional[str] = None) -> None:
    """One plan-artifact-store outcome (``hit`` / ``miss`` / ``spill``
    / ``evict`` / ``reject`` / ``manifest_refresh``; rejects carry
    their typed reason label). Counters always
    (``spfft_store_{hits,misses,spills,evictions,rejects,
    manifest_refreshes}_total``); a ``store`` instant on the compile
    track when tracing is on — next to the ``compile.store_load`` /
    ``compile.store_spill`` spans the store records, so Perfetto shows
    load-vs-build decisions inline with the compile timeline."""
    name = {"hit": "spfft_store_hits_total",
            "miss": "spfft_store_misses_total",
            "spill": "spfft_store_spills_total",
            "evict": "spfft_store_evictions_total",
            "reject": "spfft_store_rejects_total",
            "manifest_refresh":
                "spfft_store_manifest_refreshes_total"}[event]
    labels = {"reason": reason} if event == "reject" else {}
    GLOBAL_COUNTERS.inc(name, 1,
                        help="Plan-artifact store outcomes.", **labels)
    if active():
        args = {"event": event}
        if reason:
            args["reason"] = reason
        GLOBAL_TRACER.instant("store." + event, cat="compile",
                              track="compile", args=args)


def record_store_aot_skip(reason: str) -> None:
    """One non-fatal AOT executable skip (export or deserialize failed,
    platform mismatch) — the artifact/plan is fine, only the
    ahead-of-time executable is absent."""
    GLOBAL_COUNTERS.inc("spfft_store_aot_skipped_total", 1,
                        help="AOT executables skipped (non-fatal) by "
                             "reason.",
                        reason=reason)


def record_plan_fallback(stage: str, reason: str) -> None:
    """One plan-time Pallas fallback decision — a compression stage or
    a fused compression+DFT direction routed to the slower path, with
    why. Counter always (``spfft_plan_pallas_fallback_total`` by
    {stage, reason} — scrapeable fleet-wide via the /metrics endpoint),
    plus an instant span annotation on the compile track when tracing
    is on."""
    GLOBAL_COUNTERS.inc("spfft_plan_pallas_fallback_total", 1,
                        help="Plan-time Pallas fallback decisions by "
                             "stage and reason.",
                        stage=stage, reason=reason)
    if active():
        GLOBAL_TRACER.instant("plan.pallas_fallback", cat="compile",
                              track="compile",
                              args={"stage": stage, "reason": reason})


def record_fused_geometry(direction: str, geometry: str) -> None:
    """The kernel geometry one fused compression+z-DFT direction of a
    plan got (``super_tile`` or ``narrow``, ops/fused_kernel.py):
    ``spfft_plan_fused_geometry_total`` by {direction, geometry}."""
    GLOBAL_COUNTERS.inc("spfft_plan_fused_geometry_total", 1,
                        help="Fused z-kernel geometry chosen per plan "
                             "direction.",
                        direction=direction, geometry=geometry)


def _count_compile(kind: str, seconds: float) -> None:
    GLOBAL_COUNTERS.inc("spfft_compile_events_total", 1,
                        help="Compile-path events by kind.", kind=kind)
    GLOBAL_COUNTERS.inc("spfft_compile_seconds_total", seconds,
                        help="Compile-path seconds by kind.", kind=kind)


def record_compile(what: str, seconds: float, t0: Optional[float] = None,
                   **info) -> None:
    """One compile-ish event (registry build, prewarm, pin prewarm,
    batch-ladder compile): counters always, a ``compile`` track span
    when tracing is on. ``t0`` is the ``time.perf_counter()`` start
    when the caller measured a real interval; omitted, the span is
    recorded at now-minus-``seconds``."""
    _count_compile(what, seconds)
    if active():
        t1 = (t0 + seconds) if t0 is not None else time.perf_counter()
        args = {k: v for k, v in info.items()
                if isinstance(v, (str, int, float, bool))}
        GLOBAL_TRACER.complete(f"compile.{what}", t1 - seconds, t1,
                               cat="compile", track="compile",
                               args=args or None)


class compile_span:
    """One stage of a plan build, as a context manager: a real
    ``compile.<kind>`` span on the ``compile`` track while tracing is on
    (mirrored into the profiler, see :meth:`Tracer.span`), and, always,
    its seconds and one event under ``kind`` in
    ``spfft_compile_{seconds,events}_total``, failed or not. ``parent``
    (a span or None) gives the span its parent link and trace id; without
    one it takes a new trace id. ``__enter__`` returns the span, or None
    with tracing off."""

    __slots__ = ("kind", "parent", "args", "_t0", "_scope")

    def __init__(self, kind: str, parent: Optional[Span] = None,
                 args: Optional[dict] = None):
        self.kind = kind
        self.parent = parent
        self.args = args
        self._scope = None

    def __enter__(self) -> Optional[Span]:
        self._t0 = time.perf_counter()
        if not active():
            return None
        trace_id = self.parent.trace_id if self.parent is not None \
            else GLOBAL_TRACER.new_trace_id()
        self._scope = GLOBAL_TRACER.span(
            f"compile.{self.kind}", cat="compile", track="compile",
            trace_id=trace_id, parent=self.parent, args=self.args)
        return self._scope.__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._scope is not None:
            self._scope.__exit__(exc_type, exc, tb)
        _count_compile(self.kind, time.perf_counter() - self._t0)


def record_plan_build(plan, seconds: float,
                      t0: Optional[float] = None) -> None:
    """Called by ``TransformPlan.__init__`` (kind=local) and the
    distributed plan (kind=distributed) with the measured construction
    time: the foreground of the build, which for a local plan ends where
    its background compression-table build starts (that build counts
    under ``kind="compression_tables"`` in the compile counters, and the
    time a caller waits for it under ``kind="plan_join"``). A local plan
    records its ``compile.plan_build`` span itself, as it builds; here
    only a distributed plan gets one, after the fact."""
    kind = ("distributed" if hasattr(plan, "dist_plan") else "local")
    GLOBAL_COUNTERS.inc("spfft_plan_builds_total", 1,
                        help="Transform plans constructed.", kind=kind)
    GLOBAL_COUNTERS.inc("spfft_plan_build_seconds_total", seconds,
                        help="Seconds spent constructing plans.",
                        kind=kind)
    if kind == "distributed" and active():
        t1 = (t0 + seconds) if t0 is not None else time.perf_counter()
        try:
            args = {"kind": kind, "precision": plan.precision,
                    "dims": f"{plan.dim_x}x{plan.dim_y}x{plan.dim_z}"}
        except Exception:
            args = {"kind": kind}
        GLOBAL_TRACER.complete("compile.plan_build", t1 - seconds, t1,
                               cat="compile", track="compile", args=args)


def record_exchange_plan(plan, seconds: float,
                         t0: Optional[float] = None) -> None:
    """Surface a ``DistributedTransformPlan``'s exact exchange
    accounting — total/busiest-link wire bytes and, when the overlap
    pipeline is active, the per-chunk split from ``OverlapSchedule`` —
    as counters plus (when tracing) an ``exchange`` track span and a
    per-chunk counter series. Called at plan construction; distributed
    rounds stop hand-rolling these numbers into bench JSON."""
    labels = {"exchange": plan.exchange.value,
              "shards": str(plan.dist_plan.num_shards),
              "chunks": str(plan.overlap_chunks)}
    wire = int(plan.exchange_wire_bytes())
    busiest = int(plan.exchange_busiest_link_bytes())
    GLOBAL_COUNTERS.inc("spfft_exchange_plans_total", 1,
                        help="Distributed plans constructed.", **labels)
    GLOBAL_COUNTERS.set("spfft_exchange_wire_bytes", wire,
                        help="Exact off-shard bytes per exchange of the "
                             "most recent plan.", **labels)
    GLOBAL_COUNTERS.set("spfft_exchange_busiest_link_bytes", busiest,
                        help="Bottleneck-link bytes per exchange of the "
                             "most recent plan.", **labels)
    GLOBAL_COUNTERS.set("spfft_wire_rung",
                        float(getattr(plan, "wire_rung", 0)),
                        help="Resolved wire-compression rung of the most "
                             "recent distributed plan (0=full, 1=f32, "
                             "2=bf16, 3=int8).", **labels)
    if not active():
        return
    ov = getattr(plan, "_overlap", None)
    per_chunk = []
    if ov is not None:
        elem = plan._wire_elem_bytes()
        # int8 rung: each chunk also carries its scale sidecar — one f32
        # per (slot, quant row) over the chunk's stick/plane slice
        int8 = getattr(plan, "wire_rung", 0) == 3
        dp = plan.dist_plan
        links = dp.num_shards * (dp.num_shards - 1)
        for c in range(ov.num_chunks):
            sc_b = (links * ov.chunk_scale_rows(c) * 4) if int8 else 0
            sc_f = (links * ov.chunk_scale_rows(c, forward=True) * 4
                    ) if int8 else 0
            per_chunk.append({
                "bwd_bytes": ov.chunk_wire_elements(c) * elem + sc_b,
                "fwd_bytes": ov.chunk_wire_elements(c, forward=True)
                * elem + sc_f,
                "busiest_link_bytes":
                    ov.chunk_busiest_link_elements(c) * elem,
            })
            GLOBAL_TRACER.counter(
                "exchange.chunk_wire_bytes",
                {"bwd": per_chunk[-1]["bwd_bytes"],
                 "fwd": per_chunk[-1]["fwd_bytes"]},
                cat="exchange", track="exchange")
    t1 = (t0 + seconds) if t0 is not None else time.perf_counter()
    args = dict(labels)
    args.update({"wire_bytes": wire, "busiest_link_bytes": busiest})
    if per_chunk:
        args["per_chunk"] = per_chunk
    GLOBAL_TRACER.complete("exchange.plan_build", t1 - seconds, t1,
                           cat="exchange", track="exchange", args=args)


def record_hlo_counts(label: str, lowered_text: Optional[str] = None,
                      compiled_text: Optional[str] = None) -> dict:
    """Surface ``utils.hlo_inspect`` collective counts (lowered
    StableHLO) and async start/done split evidence (compiled HLO) as
    metrics + an instant event. Returns the recorded dict."""
    from ..utils.hlo_inspect import collective_async_split, \
        count_collectives
    out: dict = {"label": label}
    if lowered_text is not None:
        counts = count_collectives(lowered_text)
        out["collectives"] = counts
        for op, n in counts.items():
            if n:
                GLOBAL_COUNTERS.set(
                    "spfft_hlo_collectives", n,
                    help="Collective launches in the most recently "
                         "inspected lowered module.",
                    label=label, op=op)
    if compiled_text is not None:
        split = collective_async_split(compiled_text)
        out["async_split"] = split
        GLOBAL_COUNTERS.set("spfft_hlo_async_starts", split["starts"],
                            help="Async collective starts in the most "
                                 "recently inspected compiled module.",
                            label=label)
        GLOBAL_COUNTERS.set("spfft_hlo_async_dones", split["dones"],
                            help="Async collective dones in the most "
                                 "recently inspected compiled module.",
                            label=label)
    if active():
        args = {"label": label}
        if "collectives" in out:
            args.update({f"collectives_{k}": v
                         for k, v in out["collectives"].items() if v})
        if "async_split" in out:
            args["async_starts"] = out["async_split"]["starts"]
            args["async_dones"] = out["async_split"]["dones"]
        GLOBAL_TRACER.instant("exchange.hlo_counts", cat="exchange",
                              track="exchange", args=args)
    return out
