"""Seconds of the plan's compression-table build (gather and fused-kernel
tables, on a thread of its own) per local plan built, from the program's
counters: its whole length, the part the foreground build and the
caller's work hide included."""

from benchmark.program_counters import compile_seconds_per_plan


def read(record):
    return compile_seconds_per_plan(record, "compression_tables")
