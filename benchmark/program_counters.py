"""The program's own always-on counters (``spfft_tpu.obs``), read in the
run's process once the window and the check are over."""

from __future__ import annotations

from typing import Optional


def compile_seconds_per_plan(record: dict, kind: str) -> Optional[float]:
    """Seconds the program counted under compile kind ``kind``
    (``spfft_compile_seconds_total``) per local plan it built
    (``spfft_plan_builds_total{kind="local"}``); None where the run's
    system built no plan (``record`` holds no ``plan_build_s``), or the
    program counted no such seconds or no plan."""
    if record.get("plan_build_s") is None:
        return None
    try:
        from spfft_tpu.obs import GLOBAL_COUNTERS
    except ImportError:
        return None
    plans = GLOBAL_COUNTERS.get("spfft_plan_builds_total", kind="local")
    seconds = GLOBAL_COUNTERS.get("spfft_compile_seconds_total", kind=kind)
    if plans <= 0 or seconds <= 0:
        return None
    return seconds / plans
