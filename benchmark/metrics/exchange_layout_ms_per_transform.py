"""Device op time of the exchange's layout work (stage label
``spfft.exchange_layout``: the send blocks built, the received blocks
placed) in the traced window per transform, averaged over the chips, in
milliseconds."""

STAGE = "spfft.exchange_layout"


def read(record):
    tr = record.get("trace")
    if not tr or not tr.get("transforms"):
        return None
    seconds = tr["stage_s"].get(STAGE, 0.0)
    if seconds <= 0:
        return None
    return 1e3 * seconds / tr["transforms"]
