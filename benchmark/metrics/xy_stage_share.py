"""Share of all device op time in the traced window taken by ops of the
xy stage (stage label ``spfft.xy*``: the ``spfft.xy_backward`` /
``spfft.xy_forward`` scopes, or the xy DFT entry points of
``spfft_tpu/ops/dft.py`` in the op's HLO stack frames), in percent."""


def read(record):
    tr = record.get("trace")
    if not tr or tr["op_total_s"] <= 0:
        return None
    xy = sum(s for k, s in tr["stage_s"].items() if k.startswith("spfft.xy"))
    if xy <= 0:
        return None
    return 100.0 * xy / tr["op_total_s"]
