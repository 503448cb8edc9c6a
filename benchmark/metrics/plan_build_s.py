"""Host-clock seconds of ``make_local_plan`` until its background table
build has joined (``check_build(wait=True)``), timed from outside."""


def read(record):
    return record.get("plan_build_s")
