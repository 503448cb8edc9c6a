"""Seconds of a distributed plan's per-shard tables (the program's
``compile.exchange_tables``, ``gather_tables`` and ``fused_tables``
spans: the slot, column and plane maps, the gather kernel's tables and
the fused kernels' tables) per distributed plan built, from the
program's counters."""

from benchmark.metrics.dist_plan_index_s import TABLES, seconds_per_build


def read(record):
    return seconds_per_build(record, TABLES)
