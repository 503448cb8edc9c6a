"""Process start to the first timed step (host clock): imports, plan
build, inputs made on the device, and every executable compiled or
loaded from the persistent cache."""


def read(record):
    return record.get("setup_s")
