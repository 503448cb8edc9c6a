"""Device op time of the compression and z stages in the traced window
per transform, in milliseconds: the fused kernels (``spfft.decompress_z``,
``spfft.z_compress``) or, on the unfused path, the gathers and z-DFTs
apart, so the number follows the z stage whatever kernel runs it."""

Z_STAGES = ("spfft.decompress_z", "spfft.z_compress", "spfft.decompress",
            "spfft.compress", "spfft.gather", "spfft.z", "spfft.z_backward",
            "spfft.z_forward")


def read(record):
    tr = record.get("trace")
    if not tr or not tr.get("transforms"):
        return None
    z = sum(tr["stage_s"].get(stage, 0.0) for stage in Z_STAGES)
    if z <= 0:
        return None
    return 1e3 * z / tr["transforms"]
