"""The sync-cancelling wall-clock estimator shared by every benchmark.

Any "time N pipelined calls then sync once" number includes sync_cost/N
of readback latency. The difference of two group sizes cancels the
constant:

    per_call = (T(g2) - T(g1)) / (g2 - g1)

with each T(g) = g pipelined calls ending in ONE hard sync.

The estimator samples each group size ``trials`` times and differences
the MEDIANS, so a sync cost that varies from group to group cancels at
its majority value instead of pairing a slow sync with a fast one.
``minimum`` (min over per-trial diffs, an older statistic) is kept for
comparison with older recorded numbers; it is downward-biased and must
not be used for decisions or headlines. The estimator was built for a
device whose sync cost ~85-130 ms; on a locally attached v5e,
``block_until_ready`` waits for the work (chip_smoke.py), and which
estimator the benchmark keeps is the benchmark's choice.

Used by bench.py, scripts/sweep.py and scripts/measure_batch.py so every
number recorded in BENCHMARKS.md comes from the same estimator.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Callable


@dataclasses.dataclass(frozen=True)
class DiffEstimate:
    """Result of :func:`diff_estimate_seconds`. ``label`` describes the
    methodology that ACTUALLY produced ``seconds`` (so benchmark logs
    cannot silently diverge from the estimator). ``seconds`` is the
    sync-robust median-difference statistic; ``median`` aliases it for
    callers that were already using the robust companion for threshold
    tuning (round-3 advisor finding). ``minimum`` is the legacy
    min-of-single-diffs value — downward-biased by sync-mode mismatch
    (see module docstring), reported only for continuity."""

    seconds: float
    spread: float
    fallback: bool
    label: str
    median: float = math.nan
    minimum: float = math.nan

    def __iter__(self):  # (seconds, spread, fallback) unpacking
        return iter((self.seconds, self.spread, self.fallback))


def diff_estimate_seconds(run_group: Callable[[int], float],
                          reps: int = 30,
                          trials: int = 4) -> DiffEstimate:
    """Estimate seconds per call from pipelined groups.

    Args:
      run_group: ``run_group(g)`` runs g pipelined calls, ends with ONE
        hard sync, and returns the wall seconds for the whole group.
      reps: sizing knob — group sizes are ``g1 = max(1, reps // 6)`` and
        ``g2 = max(g1 + 1, reps - g1)``.
      trials: samples per group size; the estimate is
        ``(median T(g2) - median T(g1)) / (g2 - g1)``.

    Returns:
      A :class:`DiffEstimate` (iterates as ``(seconds, spread,
      fallback)``). When the median difference is non-positive (the
      per-call time is below the sync-cost noise — tiny workloads),
      falls back to the plain pipelined mean of one g2 group, which
      re-includes sync_cost/g2; ``fallback`` is True and ``label`` says
      so.
    """
    g1 = max(1, reps // 6)
    g2 = max(g1 + 1, reps - g1)
    # alternate sizes so slow drift (if any) hits both groups equally
    t1s, t2s = [], []
    for _ in range(trials):
        t2s.append(run_group(g2))
        t1s.append(run_group(g1))
    # median_high, not median: with an even sample count a plain median
    # AVERAGES the two middle samples — a 2-2 fast/slow sync split would
    # put one group's median between the modes while the other sits on a
    # mode, re-introducing the mismatch bias. median_high is always a
    # real sample and lands on the majority (slow) mode whenever at
    # least half the samples do, so both group medians cancel exactly.
    med = (statistics.median_high(t2s)
           - statistics.median_high(t1s)) / (g2 - g1)
    diffs = [(t2 - t1) / (g2 - g1) for t1, t2 in zip(t1s, t2s)]
    positive = [d for d in diffs if d > 0]
    minimum = min(positive) if positive else math.nan
    if med > 0:
        spread = ((max(positive) - min(positive)) / med
                  if len(positive) > 1 else 0.0)
        return DiffEstimate(
            med, spread, False,
            f"sync-robust median estimator ((medT({g2})-medT({g1}))/"
            f"{g2 - g1}, {trials} samples/size, per-trial spread "
            f"{spread * 100:.1f}%)", med, minimum)
    # below the sync noise floor: the per-call time is smaller than the
    # sync jitter. Reuse the samples already collected (no fresh group —
    # it would cost another ~100 ms sync for ONE unreplicated sample).
    t = statistics.median_high(t2s) / g2
    return DiffEstimate(t, math.nan, True,
                        f"pipelined median of {trials}x{g2} "
                        f"(diff estimator below noise)", t, minimum)
