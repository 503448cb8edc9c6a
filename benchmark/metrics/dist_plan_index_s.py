"""Seconds of the per-shard index plans (``build_index_plan`` of every
shard inside ``make_distributed_plan``, the program's
``compile.index_plan`` span) per distributed plan built, from the
program's counters."""

from typing import Optional

#: the compile kinds of a distributed build's per-shard tables
TABLES = ("exchange_tables", "gather_tables", "fused_tables")


def seconds_per_build(record: dict, kinds) -> Optional[float]:
    """Seconds the program counted under the compile kinds ``kinds``
    (``spfft_compile_seconds_total``) per distributed plan it built
    (``spfft_plan_builds_total{kind="distributed"}``); None where the
    run's system built no plan, or the program counted no such seconds
    or no distributed plan."""
    if record.get("plan_build_s") is None:
        return None
    try:
        from spfft_tpu.obs import GLOBAL_COUNTERS
    except ImportError:
        return None
    plans = GLOBAL_COUNTERS.get("spfft_plan_builds_total",
                                kind="distributed")
    seconds = sum(GLOBAL_COUNTERS.get("spfft_compile_seconds_total", kind=k)
                  for k in kinds)
    if plans <= 0 or seconds <= 0:
        return None
    return seconds / plans


def read(record):
    return seconds_per_build(record, ("index_plan",))
