"""Bytes and operations of one sparse 3D transform, from its shapes.

One transform is one backward (sparse set to grid) or one forward (grid
to sparse set) of one field. Its byte floor is the sparse values read or written
once (8 bytes each, complex64) and the dense grid written or read once
(8 bytes a point for C2C, 4 for the real R2C grid). Its operation count is
5 M log2 M per complex line of length M that the sparse algorithm must
transform (half that for a real line): the z-lines of the sticks that hold
values, the y-lines of the x-columns that hold sticks in every z-plane,
and every x-line of every z-plane.
"""

from __future__ import annotations

import math

import numpy as np


def _line_flops(m: int) -> float:
    return 5.0 * m * math.log2(m) if m > 1 else 0.0


def transform_work(config: dict, triplets: np.ndarray) -> dict:
    """``{"bytes", "flops"}`` of one transform of ``config`` over the
    sparse set ``triplets`` (centered (x, y, z) rows)."""
    nx, ny, nz = (int(n) for n in config["grid"])
    r2c = config["transform"] == "r2c"
    t = np.asarray(triplets)
    num_values = len(t)
    sticks = np.unique(t[:, 0].astype(np.int64) * ny + (t[:, 1] % ny))
    x_columns = len(np.unique(t[:, 0]))
    grid_bytes = (4 if r2c else 8) * nx * ny * nz
    flops = (len(sticks) * _line_flops(nz)
             + nz * x_columns * _line_flops(ny)
             + nz * ny * _line_flops(nx) * (0.5 if r2c else 1.0))
    return {"bytes": 8.0 * num_values + grid_bytes, "flops": flops}


def roofline_seconds(work: dict, peak: dict) -> tuple:
    """The least time one transform can take on a chip of ``peak``, and
    which bound sets it ("bytes" or "flops")."""
    t_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    t_flops = work["flops"] / peak["flops_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
