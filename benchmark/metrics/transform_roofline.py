"""The least time one transform can take on the chip (the larger of its
byte floor over HBM bandwidth and its operations over peak FLOP/s, see
``benchmark/work.py``; at these sizes bytes bound it) over the device
time per transform in the traced window, in percent."""

from benchmark.work import roofline_seconds


def read(record):
    tr = record.get("trace")
    if not tr or not tr.get("transforms") or tr["busy_s"] <= 0:
        return None
    floor_s, _ = roofline_seconds(record["work"], record["peak"])
    return 100.0 * floor_s / (tr["busy_s"] / tr["transforms"])
