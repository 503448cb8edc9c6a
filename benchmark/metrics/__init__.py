"""One reader per metric, found by the metric's name in BENCHMARK.json.

Each module defines ``read(record) -> float | None``. ``record`` is what
one run gathered (see ``benchmark.run.run_cell``): ``setup_s`` and
``plan_build_s`` on the host clock, ``window`` (seconds, steps and
transforms of the measured window), ``trace`` (``trace_reduce.reduce`` of
the traced window, absent without ``--trace 1``), ``work`` (bytes and
operations of one transform) and ``peak`` (the chip's row of
``peaks.json``). A reader with nothing to read returns None and the
metric is left out of the result.
"""
