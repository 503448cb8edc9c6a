"""Transforms completed in the measured window over its whole length
(host clock). A transform is one backward or one forward of one field."""


def read(record):
    w = record.get("window")
    if not w or w["window_s"] <= 0:
        return None
    return w["transforms"] / w["window_s"]
