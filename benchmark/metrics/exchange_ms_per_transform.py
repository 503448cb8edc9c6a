"""Device op time of the distributed exchange's collective (stage label
``spfft.exchange``) in the traced window per transform, averaged over
the chips, in milliseconds. Each transform makes one exchange."""

STAGE = "spfft.exchange"


def read(record):
    tr = record.get("trace")
    if not tr or not tr.get("transforms"):
        return None
    seconds = tr["stage_s"].get(STAGE, 0.0)
    if seconds <= 0:
        return None
    return 1e3 * seconds / tr["transforms"]
