"""Bytes a chip sends in one exchange over the device op time of that
exchange's collective, in GB/s: the plan's
``spfft_exchange_busiest_link_bytes`` (read from the program's counters
as ``program_counters`` reads them), the most any shard sends or
receives to and from all its peers together, over ``spfft.exchange``
per transform, averaged over the chips. A chip's egress over all its
links, not one link's rate, and not a share of a peak: no peak of the
chip's interconnect is in ``peaks.json``."""

from benchmark.metrics import exchange_ms_per_transform


def egress_bytes(record):
    """The counter of the run's one distributed plan; None where the
    run's system built no plan or the program counts no such bytes."""
    if record.get("plan_build_s") is None:
        return None
    try:
        from spfft_tpu.obs import GLOBAL_COUNTERS
    except ImportError:
        return None
    family = GLOBAL_COUNTERS.snapshot().get(
        "spfft_exchange_busiest_link_bytes")
    samples = list(family["samples"].values()) if family else []
    if len(samples) != 1 or samples[0] <= 0:
        return None
    return float(samples[0])


def read(record):
    ms = exchange_ms_per_transform.read(record)
    sent = egress_bytes(record)
    if ms is None or sent is None:
        return None
    return sent / (ms * 1e-3) / 1e9
