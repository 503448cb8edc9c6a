"""The control: the configuration's plain reference in the program's
place, one precision step down (the reference module's ``LowPrecision``),
with the local plan's entry points. ``benchmark.calibrate`` and the
tests put it where a configuration's system would be; no configuration
names it."""

from __future__ import annotations

import importlib
import time

import numpy as np


class System:
    def __init__(self, config: dict, triplets: np.ndarray):
        ref = importlib.import_module(
            f"benchmark.references.{config['reference']}")
        nx, ny, nz = config["grid"]
        idx = ref.SparseIndex(triplets, config["grid"],
                              config["transform"] == "r2c")
        self.scale = 1.0 / (nx * ny * nz)
        t0 = time.perf_counter()
        self.ref = ref.LowPrecision(idx)
        self.build_s = time.perf_counter() - t0

    def backward(self, values):
        return self.ref.backward(values)

    def forward(self, space):
        return self.ref.forward(space, self.scale)

    def close(self):
        self.ref = None
