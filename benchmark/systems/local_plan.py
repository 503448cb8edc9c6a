"""The system under test on one chip: one ``spfft_tpu`` local plan
(``make_local_plan``), built and driven through its public entry points.
A configuration with ``"batch"`` above 1 calls ``backward_batched`` and
``forward_batched``; forward scaling is always full (1 / grid size), so
that a step's output has its input's scale."""

from __future__ import annotations

import time

import numpy as np


class System:
    def __init__(self, config: dict, triplets: np.ndarray):
        from spfft_tpu import Scaling, TransformType, make_local_plan

        nx, ny, nz = config["grid"]
        kind = {"c2c": TransformType.C2C,
                "r2c": TransformType.R2C}[config["transform"]]
        self.scaling = Scaling.FULL
        self.batched = int(config["batch"]) > 1
        t0 = time.perf_counter()
        self.plan = make_local_plan(kind, nx, ny, nz, triplets,
                                    precision=config["precision"])
        # the table build runs on a thread of its own; the plan is ready
        # when it has joined
        self.plan.check_build(wait=True)
        self.build_s = time.perf_counter() - t0

    def backward(self, values):
        if self.batched:
            return self.plan.backward_batched(values)
        return self.plan.backward(values)

    def forward(self, space):
        if self.batched:
            return self.plan.forward_batched(space, self.scaling)
        return self.plan.forward(space, self.scaling)

    def close(self):
        self.plan.close()
        self.plan = None
