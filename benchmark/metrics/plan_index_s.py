"""Seconds of the index plan (``build_index_plan`` inside
``make_local_plan``, the first part of the plan build) per local plan
built, from the program's counters."""

from benchmark.program_counters import compile_seconds_per_plan


def read(record):
    return compile_seconds_per_plan(record, "index_plan")
