"""The plan and execute layers' spans and counters (``spfft_tpu.obs``):
one trace id per plan build with parent links, compile counters by kind,
one ``execute.<entry>`` span per public call mirrored into the
profiler, and nothing recorded with tracing off."""

import threading

import jax
import numpy as np
import pytest

from spfft_tpu import Scaling, TransformType, make_local_plan, obs
from spfft_tpu.errors import InvalidParameterError
from spfft_tpu.obs import counters as obs_counters
from spfft_tpu.ops import gather_kernel as gk
from spfft_tpu.utils.workloads import spherical_cutoff_triplets

N = 32


@pytest.fixture
def fresh(monkeypatch):
    """Tracing on, an empty tracer and a counter registry of the test's
    own."""
    counters = obs_counters.Counters()
    monkeypatch.setattr(obs, "GLOBAL_COUNTERS", counters)
    obs.GLOBAL_TRACER.reset()
    obs.enable()
    yield counters
    obs.disable()
    obs.GLOBAL_TRACER.reset()


@pytest.fixture
def annotations(monkeypatch):
    """The names of the profiler annotations entered and exited."""
    seen = {"entered": [], "exited": []}

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen["entered"].append(self.name)
            return self

        def __exit__(self, *exc):
            seen["exited"].append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    return seen


def _triplets():
    return spherical_cutoff_triplets(N)


def _spans():
    return [e for e in obs.GLOBAL_TRACER.events()
            if isinstance(e, obs.Span)]


def test_plan_build_is_one_trace_with_parent_links(fresh, annotations):
    plan = make_local_plan(TransformType.C2C, N, N, N, _triplets(),
                           use_pallas=True)
    plan.check_build(wait=True)
    by_name = {s.name: s for s in _spans()}
    for name in ("compile.index_plan", "compile.plan_build",
                 "compile.compression_tables", "compile.gather_tables",
                 "compile.fused_tables"):
        assert by_name[name].status == "ok", name
        assert by_name[name].track == "compile"
    build = by_name["compile.plan_build"]
    tables = by_name["compile.compression_tables"]
    assert len({s.trace_id for s in by_name.values()}) == 1
    assert build.trace_id is not None
    assert by_name["compile.index_plan"].parent_id is None
    assert build.parent_id is None
    assert tables.parent_id == build.span_id
    assert by_name["compile.gather_tables"].parent_id == tables.span_id
    assert by_name["compile.fused_tables"].parent_id == tables.span_id
    assert obs.GLOBAL_TRACER.open_count() == 0
    # the foreground spans run in turn; the table build starts inside
    # the plan build
    assert by_name["compile.index_plan"].t1 <= build.t0
    assert build.t0 <= tables.t0
    # the same-thread spans land in the profiler too, the table build's
    # on its own thread
    for name in by_name:
        assert name in annotations["entered"]
    assert sorted(annotations["entered"]) == sorted(annotations["exited"])
    for kind in ("index_plan", "compression_tables", "gather_tables",
                 "fused_tables"):
        assert fresh.get("spfft_compile_events_total", kind=kind) == 1
        assert fresh.get("spfft_compile_seconds_total", kind=kind) > 0
    assert fresh.get("spfft_plan_builds_total", kind="local") == 1
    assert fresh.get("spfft_plan_build_seconds_total", kind="local") \
        == pytest.approx(build.duration, abs=0.01)
    plan.close()


def test_fused_tables_span_carries_geometry(fresh, monkeypatch):
    """``compile.fused_tables`` carries each fused direction's kernel
    geometry and grid steps per transform, as the plan reports them,
    and ``spfft_plan_fused_geometry_total`` counts them."""
    monkeypatch.setenv("SPFFT_TPU_FORCE_MATMUL_DFT", "1")
    monkeypatch.setenv("SPFFT_TPU_FUSED_INTERPRET", "1")
    tr = np.asarray([(x, y, z) for x in range(4) for y in range(4)
                     for z in range(0, 128, 2)], np.int32)
    plan = make_local_plan(TransformType.C2C, 4, 4, 128, tr,
                           precision="single", use_pallas=True)
    plan.check_build(wait=True)
    span = {s.name: s for s in _spans()}["compile.fused_tables"]
    steps = plan.fused_grid_steps
    for d in ("backward", "forward"):
        assert plan.fused_geometry[d] == "super_tile"
        assert span.args[f"{d}_geometry"] == "super_tile"
        assert span.args[f"{d}_grid_steps"] == steps[d] >= 1
        assert fresh.get("spfft_plan_fused_geometry_total", direction=d,
                         geometry="super_tile") == 1
    plan.close()


def test_plan_join_span_covers_the_wait(fresh, monkeypatch):
    """A caller that reaches the table build while it still runs waits
    in a ``compile.plan_join`` span under the plan build."""
    release = threading.Event()
    inputs = gk.compression_gather_inputs

    def held(*args, **kwargs):
        release.wait(10)
        return inputs(*args, **kwargs)

    monkeypatch.setattr(gk, "compression_gather_inputs", held)
    plan = make_local_plan(TransformType.C2C, N, N, N, _triplets(),
                           use_pallas=True)
    timer = threading.Timer(0.05, release.set)
    timer.start()
    plan.check_build(wait=True)
    timer.join(10)
    by_name = {s.name: s for s in _spans()}
    join = by_name["compile.plan_join"]
    assert join.parent_id == by_name["compile.plan_build"].span_id
    assert join.trace_id == by_name["compile.plan_build"].trace_id
    assert join.duration >= 0.03
    assert fresh.get("spfft_compile_events_total", kind="plan_join") == 1
    # a second wait finds the build joined and records nothing
    plan.check_build(wait=True)
    assert fresh.get("spfft_compile_events_total", kind="plan_join") == 1
    plan.close()


@pytest.mark.parametrize("calls", [1, 3])
def test_execute_spans_one_per_call(fresh, annotations, calls):
    plan = make_local_plan(TransformType.C2C, N, N, N, _triplets())
    values = np.ones(plan.index_plan.num_values, np.complex64)
    obs.GLOBAL_TRACER.reset()
    annotations["entered"].clear()
    for _ in range(calls):
        space = plan.backward(values)
    plan.forward(space, Scaling.FULL)
    spans = _spans()
    assert [s.name for s in spans] == ["execute.backward"] * calls \
        + ["execute.forward"]
    assert all(s.status == "ok" and s.t1 is not None
               and s.track == "execute" for s in spans)
    assert annotations["entered"] == [s.name for s in spans]
    assert annotations["exited"][-len(spans):] == [s.name for s in spans]
    assert obs.GLOBAL_TRACER.open_count() == 0
    plan.close()


def test_execute_span_closes_with_error(fresh):
    plan = make_local_plan(TransformType.C2C, N, N, N, _triplets())
    obs.GLOBAL_TRACER.reset()
    with pytest.raises(InvalidParameterError):
        plan.backward(np.ones(3, np.complex64))
    (span,) = _spans()
    assert span.name == "execute.backward"
    assert span.status == "error" and span.error == "InvalidParameterError"
    assert obs.GLOBAL_TRACER.open_count() == 0
    plan.close()


def test_nothing_recorded_with_tracing_off(annotations):
    obs.disable()
    obs.GLOBAL_TRACER.reset()
    before = obs.GLOBAL_TRACER.stats()
    plan = make_local_plan(TransformType.R2C, N, N, N,
                           _triplets()[_triplets()[:, 0] >= 0],
                           use_pallas=True)
    values = np.ones(plan.index_plan.num_values, np.complex64)
    plan.forward(plan.backward(values))
    plan.forward_batched(plan.backward_batched([values, values]))
    plan.close()
    assert obs.GLOBAL_TRACER.events() == []
    assert obs.GLOBAL_TRACER.stats() == before
    assert annotations["entered"] == []
