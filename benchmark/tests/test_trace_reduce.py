"""The trace reduction, on plain lists and on a trace recorded on a TPU v5e.

``data/density_window.xplane.pb.gz`` is the profiler's trace of a 30 ms
window of 10 backward+forward pairs of a 256^3 C2C transform (8782782
values in a sphere), recorded with ``benchmark.run``'s traced path on one
TPU v5 lite. The checkout's path and the host's name inside it are
replaced by placeholders of the same length.
"""

import gzip
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_merge_intervals_unions_and_clips():
    merged = tr.merge_intervals([(8, 12), (0, 2), (1, 3), (5, 6), (6, 7)],
                                0, 10)
    assert merged == [[0, 3], [5, 7], [8, 10]]
    assert tr.gaps_between(merged, 0, 10) == [(3, 5), (7, 8)]
    assert tr.gaps_between([], 2, 4) == [(2, 4)]


def test_gap_namer_takes_the_most_overlap_then_the_shorter():
    spans = [tr.Span(tr.WINDOW_SPAN, 0, 100),
             tr.Span("bench.sync", 10, 10),
             tr.Span("bench.backward", 18, 4),
             tr.Span("bench.forward", 40, 2),
             tr.Span("bench.pointwise", 40, 1)]
    name = tr.GapNamer(spans)
    assert name((11, 19)) == "bench.sync"
    assert name((19, 23)) == "bench.backward"
    assert name((40, 41)) == "bench.pointwise"
    assert name((60, 70)) == "outside"


def test_stage_of_prefers_the_innermost_scope_then_frames():
    rules = tr.load_stage_rules()
    assert tr.stage_of("jit(f)/spfft.xy_backward/spfft.unpack/gather", (),
                       rules) == "spfft.unpack"
    frames = ("TransformPlan._backward_impl", "TransformPlan._backward_after_z",
              "pdft2_minor", "pdft2", "_run2")
    assert tr.stage_of("jit(f)/pallas_call", frames, rules) == "spfft.xy"
    assert tr.stage_of("jit(f)/pallas_call",
                       ("TransformPlan._backward_rest_tp", "pdft_last_opt"),
                       rules) == "spfft.z"
    assert tr.stage_of("jit(f)/add", ("TransformPlan._backward_impl",),
                       rules) is None


def test_hlo_instructions_resolve_stack_frames():
    _, hlo_pb2 = tr.protos()
    module = hlo_pb2.HloModuleProto(name="jit_f")
    sfi = module.stack_frame_index
    # functions 1 outer, 2 inner; locations 1 -> outer, 2 -> inner;
    # frame 1 = outer, frame 2 = inner with parent 1
    sfi.file_names.append("plan.py")
    sfi.function_names.extend(["outer", "inner"])
    sfi.file_locations.add(file_name_id=1, function_name_id=1)
    sfi.file_locations.add(file_name_id=1, function_name_id=2)
    sfi.stack_frames.add(file_location_id=1)
    sfi.stack_frames.add(file_location_id=2, parent_frame_id=1)
    comp = module.computations.add(name="main")
    inst = comp.instructions.add(name="fusion.3", opcode="fusion")
    inst.metadata.op_name = "jit(f)/spfft.pack/gather"
    inst.metadata.stack_frame_id = 2
    comp.instructions.add(name="copy.1", opcode="copy")
    insts = tr.hlo_instructions(
        hlo_pb2.HloModuleProto.FromString(module.SerializeToString()))
    assert insts["fusion.3"] == ("jit(f)/spfft.pack/gather",
                                 ("outer", "inner"))
    assert insts["copy.1"] == ("", ())


def test_protos_load_without_the_tensorflow_package():
    # in a fresh process: the generated modules load, and neither the
    # tensorflow package nor its runtime is left imported
    code = ("import sys; from benchmark import trace_reduce as tr; "
            "tr.protos(); print(sorted(k for k in sys.modules "
            "if k.split('.')[0] == 'tensorflow'))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300, check=True,
                         cwd=Path(tr.__file__).resolve().parent.parent)
    left = eval(out.stdout.strip().splitlines()[-1])
    assert "tensorflow" not in left and "tensorflow.python" not in left
    assert set(left) <= {"tensorflow.tsl.profiler.protobuf.xplane_pb2",
                         "tensorflow.compiler.xla.service.hlo_pb2",
                         "tensorflow.compiler.xla.service.metrics_pb2",
                         "tensorflow.compiler.xla.xla_data_pb2"}


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "window.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "density_window.xplane.pb.gz").read_bytes()))
    return path


def _raw_ops(path):
    """(start, end) of every "XLA Ops" event, read independently of
    ``trace_reduce.load``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    out += [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    return out


def test_recorded_busy_and_idle(recorded):
    trace = tr.load(recorded)
    red = tr.reduce(trace)
    lo, hi = tr.window_of(trace)
    ops = [(max(s, lo), min(e, hi)) for s, e in _raw_ops(recorded)
           if e > lo and s < hi]
    assert len(ops) >= 690
    # a plain union by sweeping the sorted intervals
    busy, end = 0.0, lo
    for s, e in sorted(ops):
        if e > end:
            busy += e - max(s, end)
            end = e
    assert red["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9, rel=1e-12)
    idle = sum(red["idle_by_span"].values())
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)
    assert 0 < red["busy_s"] < red["window_s"]


def test_recorded_ops_map_to_stages(recorded):
    red = tr.reduce(tr.load(recorded))
    assert red["programs_mapped"] >= 2
    labels = dict(red["top_ops"])
    assert "jit__backward_impl/_backward_impl.2 [spfft.decompress_z]" in labels
    assert "jit__backward_impl/_backward_impl.3 [spfft.xy]" in labels
    assert "jit__unknown/_unknown_.2 [spfft.xy]" in labels
    assert "jit__unknown/_unknown_.3 [spfft.z_compress]" in labels
    assert "jit__backward_impl/copy.4 [spfft.unpack]" in labels
    # ops do not overlap on the one "XLA Ops" line
    assert red["op_total_s"] == pytest.approx(red["busy_s"], rel=1e-9)
    assert sum(red["stage_s"].values()) == pytest.approx(red["op_total_s"])


def test_recorded_gaps_are_named_by_an_overlapping_span(recorded):
    trace = tr.load(recorded)
    lo, hi = tr.window_of(trace)
    merged = tr.merge_intervals(_raw_ops(recorded), lo, hi)
    name = tr.GapNamer(trace.spans)
    gaps = tr.gaps_between(merged, lo, hi)
    assert gaps
    for a, b in gaps:
        label = name((a, b))
        assert label == "outside" or any(
            s.name == label and s.start_ns < b and s.start_ns + s.dur_ns > a
            for s in trace.spans)
    # the window opens with the host dispatching the first step while
    # the device waits: the longest gap, inside a host span
    red = tr.reduce(trace)
    assert red["gaps"][0][0].startswith("bench.")
