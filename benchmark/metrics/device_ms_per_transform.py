"""Device busy time in the traced window (union of op intervals) per
transform completed in it, in milliseconds."""


def read(record):
    tr = record.get("trace")
    if not tr or not tr.get("transforms") or tr["busy_s"] <= 0:
        return None
    return 1e3 * tr["busy_s"] / tr["transforms"]
