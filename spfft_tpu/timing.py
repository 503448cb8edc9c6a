"""Hierarchical scope timing — the rt_graph equivalent.

Reimplements the semantics of the reference's self-contained scope timer
(reference: src/timing/rt_graph.hpp:106-177 ``Timer``, :83-102
``TimingResult``; hooked in via HOST_TIMING_* macros, src/timing/timing.hpp:44-62):
nested named scopes accumulate start/stop timestamps, ``process()``
reconstructs the call tree, and the result prints a Count/Total/%/Parent%/
Median/Min/Max table or exports JSON — the same stats the reference benchmark
dumps (tests/programs/benchmark.cpp:276-308).

TPU caveat, stated honestly: jitted work is dispatched asynchronously, so a
host-side scope around a jitted call measures dispatch unless the scope blocks.
``timed_transform(label)`` yields a box; assigning the produced arrays to
``box.value`` inside the scope makes the measurement ``block_until_ready`` on
them, so enabled timing measures real wall-clock. Device-side phase
attribution comes from ``jax.profiler`` traces instead — the pipeline stages
are wrapped in ``jax.named_scope`` so XLA profiles show z/exchange/xy phases
by name.

Timing is off by default (the reference compiles the macros out unless
SPFFT_TIMING, CMakeLists.txt:181-184); enable with ``enable()`` or the
SPFFT_TPU_TIMING=1 env var.
"""

from __future__ import annotations

import contextlib
import json as _json
import os
import statistics
import threading
import time
from typing import Any, Dict, List, Optional

import jax

from .obs import trace as _obs_trace


class _Node:
    __slots__ = ("label", "times", "children")

    def __init__(self, label: str):
        self.label = label
        self.times: List[float] = []
        self.children: "Dict[str, _Node]" = {}


class TimingResult:
    """Processed call tree with per-scope statistics
    (reference: rt_graph.hpp:83-102)."""

    def __init__(self, root: _Node):
        self._root = root

    def _rows(self):
        rows = []
        total_all = sum(sum(c.times) for c in self._root.children.values())

        def visit(node: _Node, depth: int, parent_total: float):
            total = sum(node.times)
            rows.append({
                "label": node.label, "depth": depth,
                "count": len(node.times), "total": total,
                "pct": 100.0 * total / total_all if total_all else 0.0,
                "parent_pct": (100.0 * total / parent_total
                               if parent_total else 100.0),
                "median": statistics.median(node.times) if node.times else 0.0,
                "min": min(node.times) if node.times else 0.0,
                "max": max(node.times) if node.times else 0.0,
            })
            for child in node.children.values():
                visit(child, depth + 1, total)

        for child in self._root.children.values():
            visit(child, 0, total_all)
        return rows

    def print(self) -> None:
        """Print the stats table (reference: TimingResult::print)."""
        rows = self._rows()
        if not rows:
            print("(no timings recorded)")
            return
        hdr = (f"{'scope':<40}{'count':>7}{'total[s]':>12}{'%':>8}"
               f"{'parent%':>9}{'median[s]':>12}{'min[s]':>12}{'max[s]':>12}")
        print(hdr)
        print("-" * len(hdr))
        for r in rows:
            label = "  " * r["depth"] + r["label"]
            print(f"{label:<40}{r['count']:>7}{r['total']:>12.6f}"
                  f"{r['pct']:>8.2f}{r['parent_pct']:>9.2f}"
                  f"{r['median']:>12.6f}{r['min']:>12.6f}{r['max']:>12.6f}")

    def json(self) -> str:
        """JSON export (reference: TimingResult::json)."""

        def dump(node: _Node) -> Dict[str, Any]:
            return {
                "label": node.label,
                "count": len(node.times),
                "total": sum(node.times),
                "times": node.times,
                "sub": [dump(c) for c in node.children.values()],
            }

        return _json.dumps(
            {"timings": [dump(c) for c in self._root.children.values()]})


class Timer:
    """Nested scope timer (reference: rt_graph.hpp:106-155).

    THREAD-SAFE since the obs round: the scope stack is THREAD-LOCAL
    (each thread nests its own scopes from the shared root — the
    serving executor's dispatcher, prewarm and submitter threads can
    all enter ``timed_transform`` scopes concurrently without
    corrupting each other's call paths), while the tree itself (child
    creation, sample appends, ``record``) mutates under one lock. A
    ``reset`` mid-scope on another thread orphans that thread's
    in-flight scope (its sample lands in the discarded tree) — callers
    quiesce before resetting, same contract as ``ServeMetrics.reset``.
    """

    def __init__(self):
        self._record_lock = threading.Lock()
        self._root = _Node("<root>")      #: guarded by _record_lock
        self._tls = threading.local()     #: guarded by _record_lock

    def reset(self) -> None:
        with self._record_lock:
            self._root = _Node("<root>")
            self._tls = threading.local()

    def _stack(self) -> List[_Node]:
        """This thread's scope stack, rooted at the CURRENT root (a
        stale stack from before a reset is discarded)."""
        # lock: waived(lock-free fast path by design - thread-local handle read)
        tls = self._tls
        stack = getattr(tls, "stack", None)
        # lock: waived(identity check against the current root - a racing reset just rebuilds this stack)
        if stack is None or stack[0] is not self._root:
            stack = tls.stack = [self._root]  # lock: waived(rebuild against whichever root the race left current)
        return stack

    def record(self, label: str, seconds: float) -> None:
        """Append one pre-measured duration under a ROOT-LEVEL scope
        named ``label``. Cross-thread safe (the serving layer records
        request latencies from its dispatcher thread); never touches
        any scope stack."""
        with self._record_lock:
            node = self._root.children.get(label)
            if node is None:
                node = self._root.children[label] = _Node(label)
            node.times.append(seconds)

    @contextlib.contextmanager
    def scoped(self, label: str, block: Any = None):
        """Time a scope; if ``block`` is given, ``block_until_ready`` it
        before closing the measurement (for async device work).
        Nesting is per-thread (thread-local stack); tree mutation is
        locked."""
        stack = self._stack()
        parent = stack[-1]
        with self._record_lock:
            node = parent.children.get(label)
            if node is None:
                node = parent.children[label] = _Node(label)
        stack.append(node)
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if block is not None:
                jax.block_until_ready(block)
            dt = time.perf_counter() - t0
            with self._record_lock:
                node.times.append(dt)
            stack.pop()

    def process(self) -> TimingResult:
        # lock: waived(read-side snapshot by design - wraps the live tree)
        return TimingResult(self._root)


#: Global timer, mirroring the reference's GlobalTimer singleton
#: (reference: src/timing/timing.cpp:36).
GlobalTimer = Timer()

_enabled = os.environ.get("SPFFT_TPU_TIMING") == "1"


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


@contextlib.contextmanager
def suppressed():
    """Temporarily disable timing inside a scope. Used by the batched
    multi-transform API so per-transform timing does not serialise the batch
    (blocking between dispatches would destroy the compute/comm overlap the
    batching exists for)."""
    global _enabled
    prev = _enabled
    _enabled = False
    try:
        yield
    finally:
        _enabled = prev


class _ResultBox:
    """Mutable late-binding holder so a scope can block on a result produced
    inside it."""

    def __init__(self):
        self.value: Optional[Any] = None


class timed_transform:
    """The one instrumentation point of a public transform call, as a
    context manager yielding a box: ``box.value = <result>`` inside it.

    - With ``spfft_tpu.obs`` tracing on, the call is an
      ``execute.<label>`` span (mirrored into the profiler, see
      ``obs.Tracer.span``). It measures host time in the call: input
      coercion, the wait for the plan's table build, the AOT or jit
      dispatch. Not device time: the dispatch returns before the device
      has run.
    - With timing on (``enable()`` / ``SPFFT_TPU_TIMING=1``) the call is
      a ``label`` scope of :data:`GlobalTimer`, which waits on
      ``box.value`` before it closes, so it measures the device work too.
    - With both off it reads two module-global booleans."""

    __slots__ = ("label", "_box", "_span", "_scope")

    def __init__(self, label: str):
        self.label = label

    def __enter__(self) -> _ResultBox:
        self._box = box = _ResultBox()
        self._scope = self._span = None
        if _enabled:
            self._scope = GlobalTimer.scoped(self.label)
            self._scope.__enter__()
        if _obs_trace._enabled:
            self._span = _obs_trace.GLOBAL_TRACER.span(
                "execute." + self.label, cat="execute", track="execute")
            self._span.__enter__()
        return box

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._span is not None:
            self._span.__exit__(exc_type, exc, tb)
        if self._scope is not None:
            try:
                if self._box.value is not None:
                    jax.block_until_ready(self._box.value)
            finally:
                self._scope.__exit__(exc_type, exc, tb)
