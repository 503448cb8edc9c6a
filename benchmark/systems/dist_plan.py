"""The system under test over the chips of one host: one ``spfft_tpu``
distributed plan (``make_distributed_plan``), built and driven through
its public entry points. The configuration states the shard count
(``shards``), how sticks go to shards (``distribution``), each shard's
z-planes (``planes``), the exchange and the wire rung; forward scaling is
always full (1 / grid size).

Where each value and each z-plane lives is the benchmark's own
(``benchmark/sharding.py``). ``backward`` and ``forward`` take and
return the plan's sharded arrays in that layout.
"""

from __future__ import annotations

import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.sharding import layout_of


class System:
    def __init__(self, config: dict, triplets: np.ndarray):
        from jax.sharding import NamedSharding, PartitionSpec

        from spfft_tpu import (ExchangeType, Scaling, TransformType,
                               make_distributed_plan)

        nx, ny, nz = config["grid"]
        kind = {"c2c": TransformType.C2C,
                "r2c": TransformType.R2C}[config["transform"]]
        self.scaling = Scaling.FULL
        self.layout = layout_of(config, triplets)
        with ThreadPoolExecutor(self.layout.shards) as pool:
            parts = list(pool.map(lambda rows: triplets[rows],
                                  self.layout.rows))
        rung = int(config["wire_rung"])
        t0 = time.perf_counter()
        self.plan = make_distributed_plan(
            kind, nx, ny, nz, parts, self.layout.planes,
            precision=config["precision"],
            exchange=ExchangeType(config["exchange"]),
            overlap_chunks=int(config["overlap_chunks"]),
            wire_precision=rung)
        # the tables go to the devices asynchronously; the plan is ready
        # when they are there (a program without the wait leaves it to
        # the first step)
        wait = getattr(self.plan, "check_build", None)
        if wait is not None:
            wait(wait=True)
        self.build_s = time.perf_counter() - t0
        del parts
        # the guarantee the configuration states: the wire at the
        # transform's own precision
        got = getattr(self.plan, "wire_rung", 0)
        if got != rung:
            raise RuntimeError(f"the plan resolved wire rung {got}, the "
                               f"configuration states {rung}")
        self.sharding = NamedSharding(self.plan.mesh,
                                      PartitionSpec(self.plan.axis_name))
        print(f"plan_build_parts: {_build_parts()}", file=sys.stderr)

    def backward(self, values):
        return self.plan.backward(values)

    def forward(self, space):
        return self.plan.forward(space, self.scaling)

    def close(self):
        self.plan = None


def _build_parts() -> dict:
    """Seconds the program counted by compile kind (its ``compile.*``
    spans) so far: the split of the plan build. Empty where the program
    counts none."""
    try:
        from spfft_tpu.obs import GLOBAL_COUNTERS
    except ImportError:
        return {}
    family = GLOBAL_COUNTERS.snapshot().get("spfft_compile_seconds_total")
    if not family:
        return {}
    return {dict(k).get("kind", "?"): round(v, 3)
            for k, v in family["samples"].items()}
