"""The shared wall-clock estimator (utils/benchtime.py).

The regression locked in here: when the hard-sync cost is bimodal and
CONSTANT per group, a min-of-single-diffs statistic fabricates fast
readings when the two group sizes catch mismatched sync modes. The
median-differencing estimator must be immune to it.
"""
import math

import pytest

from spfft_tpu.utils.benchtime import diff_estimate_seconds

PER_CALL = 0.0125
SLOW_SYNC = 0.128
FAST_SYNC = 0.088


def make_run_group(sync_sequence):
    syncs = iter(sync_sequence)

    def run_group(g):
        return g * PER_CALL + next(syncs)
    return run_group


def test_median_diff_cancels_constant_sync():
    est = diff_estimate_seconds(make_run_group([SLOW_SYNC] * 8), reps=20)
    assert not est.fallback
    assert est.seconds == pytest.approx(PER_CALL, rel=1e-9)
    assert est.median == est.seconds
    assert "sync-robust median" in est.label


def test_mismatched_sync_mode_does_not_bias_the_estimate():
    # trial 2's large group catches the fast sync while its small group
    # does not: the legacy per-trial min is biased ~3 ms/call low, the
    # median estimate is exact. Call order is g2 then g1 per trial.
    syncs = [SLOW_SYNC, SLOW_SYNC,   # trial 0: g2, g1
             SLOW_SYNC, SLOW_SYNC,   # trial 1
             FAST_SYNC, SLOW_SYNC,   # trial 2: mismatched pairing
             SLOW_SYNC, SLOW_SYNC]   # trial 3
    est = diff_estimate_seconds(make_run_group(syncs), reps=20)
    assert est.seconds == pytest.approx(PER_CALL, rel=1e-9)
    # the legacy statistic WOULD have reported the artifact:
    g1, g2 = 3, 17
    biased = PER_CALL - (SLOW_SYNC - FAST_SYNC) / (g2 - g1)
    assert est.minimum == pytest.approx(biased, rel=1e-9)
    assert est.minimum < 0.8 * est.seconds


def test_fallback_when_below_sync_noise():
    # per-call time of zero: every difference is the sync jitter, the
    # median diff is non-positive -> fallback reusing the collected g2
    # samples (NO extra group run — the iterator has exactly 8 entries)
    syncs = [SLOW_SYNC, SLOW_SYNC, FAST_SYNC, SLOW_SYNC,
             SLOW_SYNC, FAST_SYNC, SLOW_SYNC, SLOW_SYNC]

    def run_group(g):
        return next(it)
    it = iter(syncs)
    est = diff_estimate_seconds(run_group, reps=20)
    assert est.fallback
    assert math.isfinite(est.seconds)
    assert "pipelined median" in est.label
    assert est.seconds == pytest.approx(SLOW_SYNC / 17, rel=1e-9)


def test_even_split_stays_on_majority_mode():
    # 2-2 fast/slow split inside the g2 samples: a plain median would
    # average the modes and skew the estimate ~1.4 ms/call at bench
    # sizes; median_high is a real slow-mode sample, so the difference
    # still cancels exactly (review r5 finding).
    syncs = [FAST_SYNC, SLOW_SYNC,   # trial 0: g2, g1
             SLOW_SYNC, SLOW_SYNC,   # trial 1
             FAST_SYNC, SLOW_SYNC,   # trial 2
             SLOW_SYNC, SLOW_SYNC]   # trial 3
    est = diff_estimate_seconds(make_run_group(syncs), reps=20)
    assert est.seconds == pytest.approx(PER_CALL, rel=1e-9)


def test_unpacking_protocol_preserved():
    sec, spread, fallback = diff_estimate_seconds(
        make_run_group([SLOW_SYNC] * 8), reps=20)
    assert sec == pytest.approx(PER_CALL, rel=1e-9)
    assert spread == 0.0
    assert fallback is False
