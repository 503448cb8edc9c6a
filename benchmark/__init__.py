"""The chip benchmark of spfft_tpu.

One cell (a configuration under a traffic mix, both named in
``BENCHMARK.json`` at the repository root) runs once per process::

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that measures lives in this package: the stick sets
(``sticks/``), the drivers of the traffic (``drivers/``), the plain
reference (``references/``), the trace reduction, the table of peaks and
the work of one transform. The program under test is imported only as
``spfft_tpu`` from the same checkout, by the systems (``systems/``).
"""
