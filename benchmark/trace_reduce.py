"""Reduce a profiler trace of one measured window to device metrics.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the
device ops of each TPU (line "XLA Ops"), the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` events named ``bench.*``), and the
optimised HLO of every program that ran, which the profiler keeps in the
``/host:metadata`` plane. ``reduce`` then gives, inside the window span:

- busy time: the union of the device op intervals, averaged over chips;
- op time: the summed op durations, by op and by pipeline stage. The
  stage of an op comes from its HLO instruction: the ``spfft.*`` named
  scope in its ``op_name``, or else the program function its stack frames
  pass through (``stages.json``);
- idle gaps: each interval of the window in which no op ran, named by
  the host span that overlaps it most.

The functions below ``load`` take plain lists, so the arithmetic is
tested apart from the file format.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import importlib
import importlib.util
import json
import re
import sys
import types
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class DeviceOp:
    start_ns: float
    dur_ns: float
    module: str
    op: str


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    dur_ns: float


@dataclasses.dataclass
class Trace:
    #: one list of ops per device plane, each sorted by start
    devices: list
    #: the benchmark's host spans
    spans: list
    #: program (``jit_f(12)``, and ``jit_f``) -> {instruction name ->
    #: (op_name, frame function names from the outermost frame inward)}
    hlo: dict


# -- the HLO of each program ------------------------------------------------

#: packages of the generated modules of the XSpace and HloProto schemas
_PROTO_PACKAGES = ("tensorflow", "tensorflow.tsl", "tensorflow.tsl.profiler",
                   "tensorflow.tsl.profiler.protobuf", "tensorflow.compiler",
                   "tensorflow.compiler.xla", "tensorflow.compiler.xla.service")


@functools.lru_cache(maxsize=None)
def protos() -> tuple:
    """``(xplane_pb2, hlo_pb2)``: the generated classes of the profiler's
    XSpace and of XLA's HloProto. They ship inside the tensorflow package,
    whose own import loads its runtime into the process; they are loaded
    from its files under bare packages instead, taken away again after."""
    spec = importlib.util.find_spec("tensorflow")
    if spec is None or not spec.submodule_search_locations:
        raise ImportError("the generated xplane and HLO protobuf modules "
                          "come with the tensorflow package, not installed")
    root = Path(next(iter(spec.submodule_search_locations)))
    added = []
    try:
        for name in _PROTO_PACKAGES:
            if name not in sys.modules:
                package = types.ModuleType(name)
                package.__path__ = [str(root.joinpath(*name.split(".")[1:]))]
                sys.modules[name] = package
                added.append(name)
        return (importlib.import_module(
                    "tensorflow.tsl.profiler.protobuf.xplane_pb2"),
                importlib.import_module(
                    "tensorflow.compiler.xla.service.hlo_pb2"))
    finally:
        for name in added:
            sys.modules.pop(name, None)


def hlo_instructions(module) -> dict:
    """{instruction name: (op_name, frame function names from the
    outermost frame inward)} of one ``HloModuleProto`` (frame, location
    and function ids count from 1)."""
    sfi = module.stack_frame_index

    def chain(frame_id: int) -> tuple:
        out, seen = [], set()
        while 0 < frame_id <= len(sfi.stack_frames) and frame_id not in seen:
            seen.add(frame_id)
            frame = sfi.stack_frames[frame_id - 1]
            loc = frame.file_location_id
            if 0 < loc <= len(sfi.file_locations):
                fn = sfi.file_locations[loc - 1].function_name_id
                if 0 < fn <= len(sfi.function_names):
                    out.append(sfi.function_names[fn - 1])
            frame_id = frame.parent_frame_id
        return tuple(reversed(out))

    return {inst.name: (inst.metadata.op_name,
                        chain(inst.metadata.stack_frame_id))
            for comp in module.computations for inst in comp.instructions}


def hlo_programs(space) -> dict:
    """Program -> instruction map, from the HloProtos that the metadata
    plane's event metadata carries. Each program is keyed by its event
    name, ``jit_f(12)`` with the program id, and by its module name."""
    _, hlo_pb2 = protos()
    out = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        for meta in plane.event_metadata.values():
            for stat in meta.stats:
                if plane.stat_metadata[stat.metadata_id].name != HLO_STAT:
                    continue
                module = hlo_pb2.HloProto.FromString(
                    stat.bytes_value).hlo_module
                insts = hlo_instructions(module)
                out[module.name] = insts
                if meta.name:
                    out[meta.name] = insts
    return out


# -- loading ----------------------------------------------------------------

def _stat_dict(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


_OP_TEXT = re.compile(r"^%?([^\s=%]+)\s*=")


def _op_name(event_name: str) -> str:
    """The instruction name of a device op event, whose name is either
    the name alone or the instruction's text, ``%fusion.2 = ...``."""
    m = _OP_TEXT.match(event_name)
    return m.group(1) if m else event_name


def _module_name(name: str) -> str:
    """``jit_f(12)`` -> ``jit_f``."""
    return re.sub(r"\(\d+\)$", "", name)


def load(path) -> Trace:
    """Read one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    xplane_pb2, _ = protos()
    hlo = hlo_programs(xplane_pb2.XSpace.FromString(raw))
    data = ProfileData.from_serialized_xspace(raw)
    devices, spans = [], []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if DEVICE_PLANE.match(plane.name) and OPS_LINE in lines:
            modules = sorted(
                (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                for ev in (lines[MODULES_LINE].events
                           if MODULES_LINE in lines else ()))
            ops = []
            for ev in lines[OPS_LINE].events:
                st = _stat_dict(ev)
                module = st.get("hlo_module")
                if module and st.get("program_id") is not None:
                    module = f"{module}({st['program_id']})"
                if not module:
                    module = _containing(modules, ev.start_ns)
                ops.append(DeviceOp(float(ev.start_ns),
                                    float(ev.duration_ns),
                                    str(module or ""),
                                    _op_name(ev.name)))
            ops.sort(key=lambda o: o.start_ns)
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append(Span(ev.name, float(ev.start_ns),
                                          float(ev.duration_ns)))
    return Trace(devices=devices, spans=spans, hlo=hlo)


def _containing(intervals: list, t: float) -> Optional[str]:
    """Name of the (start, end, name) interval that holds ``t``."""
    lo, hi = 0, len(intervals)
    while lo < hi:
        mid = (lo + hi) // 2
        if intervals[mid][0] <= t:
            lo = mid + 1
        else:
            hi = mid
    for k in (lo - 1, lo - 2):
        if 0 <= k < len(intervals) and intervals[k][0] <= t <= intervals[k][1]:
            return intervals[k][2]
    return None


# -- reduction --------------------------------------------------------------

def load_stage_rules(path=HERE / "stages.json") -> dict:
    rules = json.loads(Path(path).read_text())
    return {"scope": re.compile(rules["scope"]),
            "functions": dict(rules["functions"])}


def stage_of(op_name: str, frames: tuple, rules: dict) -> Optional[str]:
    """The stage of one HLO instruction, or None."""
    scopes = rules["scope"].findall(op_name or "")
    if scopes:
        return scopes[-1]
    for fn in frames:
        short = fn.rsplit(".", 1)[-1]
        if short in rules["functions"]:
            return rules["functions"][short]
    return None


def merge_intervals(intervals, lo: float, hi: float) -> list:
    """Union of (start, end) intervals clipped to [lo, hi], sorted."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps_between(merged: list, lo: float, hi: float) -> list:
    """The (start, end) intervals of [lo, hi] that ``merged`` leaves
    uncovered."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class GapNamer:
    """Names idle gaps by the host span that overlaps each most (the
    shorter one on a tie), or ``"outside"``. The window span itself names
    nothing."""

    def __init__(self, spans: list):
        own = sorted((s for s in spans if s.name != WINDOW_SPAN),
                     key=lambda s: s.start_ns)
        self._spans = own
        self._starts = [s.start_ns for s in own]
        self._longest = max((s.dur_ns for s in own), default=0.0)

    def __call__(self, gap: tuple) -> str:
        a, b = gap
        best, best_key = "outside", None
        k = bisect.bisect_left(self._starts, b) - 1
        while k >= 0 and self._starts[k] >= a - self._longest:
            sp = self._spans[k]
            k -= 1
            ov = min(b, sp.start_ns + sp.dur_ns) - max(a, sp.start_ns)
            if ov <= 0:
                continue
            key = (ov, -sp.dur_ns)
            if best_key is None or key > best_key:
                best, best_key = sp.name, key
        return best


def window_of(trace: Trace) -> tuple:
    """(start, end) of the window span, or of all device ops."""
    win = [s for s in trace.spans if s.name == WINDOW_SPAN]
    if win:
        w = max(win, key=lambda s: s.dur_ns)
        return w.start_ns, w.start_ns + w.dur_ns
    ops = [o for dev in trace.devices for o in dev]
    if not ops:
        return 0.0, 0.0
    return (min(o.start_ns for o in ops),
            max(o.start_ns + o.dur_ns for o in ops))


def reduce(trace: Trace, rules: Optional[dict] = None, top: int = 10) -> dict:
    """Busy, op and idle times of the trace's window, in seconds."""
    rules = rules or load_stage_rules()
    lo, hi = window_of(trace)
    window_s = (hi - lo) * 1e-9
    ndev = max(len(trace.devices), 1)
    busy_ns = 0.0
    op_ns, stage_ns, gap_list = {}, {}, []
    labels = {}
    namer = GapNamer(trace.spans)
    for ops in trace.devices:
        inside = [o for o in ops if o.start_ns + o.dur_ns > lo
                  and o.start_ns < hi]
        merged = merge_intervals(
            ((o.start_ns, o.start_ns + o.dur_ns) for o in inside), lo, hi)
        busy_ns += sum(e - s for s, e in merged)
        for o in inside:
            key = (o.module, o.op)
            if key not in labels:
                insts = trace.hlo.get(o.module) \
                    or trace.hlo.get(_module_name(o.module), {})
                op_name, frames = insts.get(o.op, ("", ()))
                labels[key] = stage_of(op_name, frames, rules)
            dur = min(o.start_ns + o.dur_ns, hi) - max(o.start_ns, lo)
            stage = labels[key]
            name = f"{_module_name(o.module)}/{o.op}" \
                + (f" [{stage}]" if stage else "")
            op_ns[name] = op_ns.get(name, 0.0) + dur
            skey = stage or "unassigned"
            stage_ns[skey] = stage_ns.get(skey, 0.0) + dur
        for g in gaps_between(merged, lo, hi):
            gap_list.append((namer(g), (g[1] - g[0])))
    gap_list.sort(key=lambda x: -x[1])
    idle_by_span = {}
    for name, ns in gap_list:
        idle_by_span[name] = idle_by_span.get(name, 0.0) + ns * 1e-9 / ndev
    return {
        "devices": len(trace.devices),
        "window_s": window_s,
        "busy_s": busy_ns * 1e-9 / ndev,
        "op_total_s": sum(op_ns.values()) * 1e-9 / ndev,
        "stage_s": {k: v * 1e-9 / ndev for k, v in stage_ns.items()},
        "top_ops": [[k, v * 1e-9 / ndev] for k, v in
                    sorted(op_ns.items(), key=lambda kv: -kv[1])[:top]],
        "gaps": [[name, ns * 1e-9] for name, ns in gap_list[:top]],
        "idle_by_span": idle_by_span,
        "programs_mapped": len(trace.hlo),
    }
