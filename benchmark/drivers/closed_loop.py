"""The closed-loop driver: one loop of steps, shaped by a mix's data file.

A configuration (``configs/<name>.json``) fixes the grid, the stick set,
the precision, how many fields live on the chip (``fields``) and how many
go through one call (``batch``). A mix (``traffic/<name>.json``) with
``"driver": "closed_loop"`` fixes what the loop does with them:

- ``pointwise``: ``none``, or ``potential``, a real V(r) made from the
  seed that multiplies the space grid between backward and forward;
- ``inflight``: how many steps may be dispatched before the host waits
  for the oldest, so that a host stall shorter than that much device work
  leaves the chip busy;
- ``check``: what the comparison with the reference reads (see
  ``compare``).

A step is one block of ``batch`` fields: backward, the pointwise
operator, forward. Blocks are taken in turn, and each step reads its
block's fixed input, as the reference benchmark's repeated pairs do
(benchmark.cpp:84-96). Outputs are not fed back: a chain of round trips
converges on its own fixed point, where an output left stale would read
as correct. Inputs and the potential are made on the device from the
seed in one jitted call each; an R2C input's x = 0 plane is made
conjugate-symmetric wherever the set holds both values of a pair.

After the window, ``fetch`` reads every block's latest step back and
``compare`` holds it against the configuration's plain reference
(``references/<name>.py``) in float64 on the host. A block is read as
one combination of its fields, sum_k c_k field_k, with real weights c_k
of magnitude 0.5 to 1.5 and random sign drawn from the seed: the step is
linear, so the combination of its outputs has to equal the reference
step of the same combination of its inputs, and a fault in any field of
any block shows, at the cost of one reference step a block.

- ``check: stages`` (no pointwise operator): the backward output against
  the reference backward of the input (``bwd_rel_l2``), and the forward
  output against the reference forward of the grid the forward was given
  (``fwd_rel_l2``);
- ``check: composite``: the forward output against the reference of the
  whole step, backward, potential and forward (``step_rel_l2``).

Each number is the largest relative l2 error over the blocks.
"""

from __future__ import annotations

import importlib
import time
from collections import deque

import numpy as np


def reference(config: dict):
    """The plain reference module a configuration names."""
    return importlib.import_module(
        f"benchmark.references.{config['reference']}")


def _key(seed: int):
    import jax
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def weights(seed: int, block: int, batch: int) -> np.ndarray:
    """The real weights of one block's fields in the combination read."""
    seed = int(seed)
    rng = np.random.default_rng([seed & 0xFFFFFFFF,
                                 (seed >> 32) & 0xFFFFFFFF, block])
    return rng.uniform(0.5, 1.5, batch) * rng.choice((-1.0, 1.0), batch)


def _make_values(key, rows, partners, *, nblocks, batch, num_values):
    """Normal values (re, im); where ``rows`` is given, each of those
    values becomes the mean of itself and its partner's conjugate."""
    import jax
    import jax.numpy as jnp
    shape = (nblocks, batch, num_values, 2)
    v = jax.random.normal(key, shape, jnp.float32)
    if rows is not None:
        conj = jnp.asarray([1.0, -1.0], jnp.float32)
        v = v.at[:, :, rows].set(0.5 * (v[:, :, rows]
                                        + v[:, :, partners] * conj))
    if batch == 1:
        return tuple(v[b, 0] for b in range(nblocks))
    return tuple(v[b] for b in range(nblocks))


def _make_potential(key, *, shape):
    import jax
    import jax.numpy as jnp
    return 1.0 + 0.5 * jax.random.uniform(key, shape, jnp.float32,
                                          -1.0, 1.0)


def _apply_potential(space, potential):
    """V(r) times a space grid, with or without a batch axis before the
    grid axes and an interleaved (re, im) axis after them."""
    trailing = space.ndim - _leading(space, potential) - potential.ndim
    return space * potential.reshape(potential.shape + (1,) * trailing)


def _leading(space, potential) -> int:
    """Axes of ``space`` before the grid axes (the batch axis)."""
    for lead in (0, 1):
        if space.shape[lead:lead + 3] == potential.shape:
            return lead
    raise ValueError(f"space {space.shape} does not hold grid "
                     f"{potential.shape}")


class Cell:
    """One configuration under one mix, with its inputs made from
    ``seed``. ``system`` is the system under test; several cells may
    share one (the calibration reads many seeds from one plan)."""

    def __init__(self, config: dict, mix: dict, seed: int, system,
                 triplets: np.ndarray):
        import jax
        import jax.numpy as jnp

        self.mix, self.seed, self.sut = mix, int(seed), system
        self.batch = int(config["batch"])
        fields = int(config["fields"])
        if fields % self.batch:
            raise ValueError("fields must be a multiple of batch")
        self.nblocks = fields // self.batch
        self.transforms_per_step = 2 * self.batch
        self.inflight = int(mix["inflight"])
        self.ref = reference(config)
        self.idx = self.ref.SparseIndex(triplets, config["grid"],
                                        config["transform"] == "r2c")
        nx, ny, nz = config["grid"]
        self.scale = 1.0 / (nx * ny * nz)
        key_values, key_potential = jax.random.split(_key(seed))
        rows = np.where(self.idx.mirror >= 0)[0]
        make = jax.jit(_make_values, static_argnames=(
            "nblocks", "batch", "num_values"))
        self.inputs = make(
            key_values,
            jnp.asarray(rows, jnp.int32) if len(rows) else None,
            jnp.asarray(self.idx.mirror[rows], jnp.int32) if len(rows)
            else None,
            nblocks=self.nblocks, batch=self.batch,
            num_values=len(triplets))
        pointwise = mix["pointwise"]
        if pointwise == "potential":
            self.potential = jax.jit(_make_potential, static_argnames=(
                "shape",))(key_potential, shape=(nz, ny, nx))
            self._pointwise = jax.jit(_apply_potential, donate_argnums=0)
        elif pointwise == "none":
            self.potential = None
            self._pointwise = None
        else:
            raise ValueError(f"unknown pointwise operator {pointwise!r}")
        #: block -> (input, backward output, forward output) of the
        #: block's latest step; the backward output is kept only for the
        #: ``stages`` check, which reads each direction on its own
        self.last = {}
        self._keep_space = mix["check"] == "stages"
        if self._keep_space and self._pointwise is not None:
            raise ValueError("the stages check reads the backward output, "
                             "which a pointwise operator consumes")
        if mix["check"] not in ("stages", "composite"):
            raise ValueError(f"unknown check {mix['check']!r}")
        self.steps = 0

    def step(self):
        """Dispatch the next step; returns its output (not waited on)."""
        import jax
        b = self.steps % self.nblocks
        self.steps += 1
        x = self.inputs[b]
        with jax.profiler.TraceAnnotation("bench.backward"):
            s = self.sut.backward(x)
        if self._pointwise is not None:
            with jax.profiler.TraceAnnotation("bench.pointwise"):
                s = self._pointwise(s, self.potential)
        with jax.profiler.TraceAnnotation("bench.forward"):
            y = self.sut.forward(s)
        self.last[b] = (x, s if self._keep_space else None, y)
        return y

    def warm(self):
        """One step of every block, each waited on before the next is
        dispatched (a deeper queue would hold every block's space grids
        at once): every executable the window runs is compiled or
        loaded here."""
        for _ in range(self.nblocks):
            self.step().block_until_ready()

    def run_window(self, seconds: float) -> dict:
        """Dispatch steps until ``seconds`` have passed, at most
        ``inflight`` of them unwaited, then wait for all. The rate is
        every transform completed over the whole window."""
        import jax
        queue = deque()
        done = 0
        syncs = []
        steps0 = self.steps
        with jax.profiler.TraceAnnotation("bench.window"):
            t0 = time.perf_counter()
            deadline = t0 + seconds
            while time.perf_counter() < deadline:
                queue.append(self.step())
                if len(queue) > self.inflight:
                    with jax.profiler.TraceAnnotation("bench.sync"):
                        queue.popleft().block_until_ready()
                    done += 1
                    syncs.append((time.perf_counter() - t0, done))
            with jax.profiler.TraceAnnotation("bench.sync"):
                while queue:
                    queue.popleft().block_until_ready()
                    done += 1
                    syncs.append((time.perf_counter() - t0, done))
            t1 = time.perf_counter()
        steps = self.steps - steps0
        return {"window_s": t1 - t0, "steps": steps,
                "transforms": steps * self.transforms_per_step,
                "per_second": per_second(syncs, t1 - t0,
                                         self.transforms_per_step)}

    def fetch(self) -> dict:
        """Host copies of what ``compare`` reads, each block already
        combined; call before the system's device state is freed."""
        rows = []
        for b in range(self.nblocks):
            c = weights(self.seed, b, self.batch)
            rows.append(tuple(None if a is None else _combine(c, a)
                              for a in self.last[b]))
        return {"check": self.mix["check"], "ref": self.ref, "idx": self.idx,
                "scale": self.scale, "rows": rows,
                "potential": (None if self.potential is None
                              else np.asarray(self.potential, np.float64))}


def _combine(c: np.ndarray, a) -> np.ndarray:
    """sum_k c_k a[k] in float64 of a device array whose first axis is
    the batch (an unbatched array is one field)."""
    a = np.asarray(a)
    if len(c) == 1 and a.ndim >= 1:
        return c[0] * np.asarray(a, np.float64)
    out = np.zeros(a.shape[1:], np.float64)
    for k, ck in enumerate(c):
        out += ck * a[k].astype(np.float64)
    return out


def per_second(syncs: list, window_s: float, per_step: int) -> list:
    """Transforms completed in each whole second of the window (the last
    entry holds the rest), from the (time, steps done) of each wait."""
    n = max(1, int(window_s))
    out = [0] * n
    prev = 0
    for t, done in syncs:
        out[min(int(t), n - 1)] += (done - prev) * per_step
        prev = done
    return out


def _complex(a: np.ndarray) -> np.ndarray:
    return a[..., 0] + 1j * a[..., 1]


def compare(host: dict) -> dict:
    """Check name -> largest relative l2 error over the blocks, in
    float64 against the configuration's reference."""
    ref, idx, scale = host["ref"], host["idx"], host["scale"]
    out = {}
    if host["check"] == "stages":
        bwd = fwd = 0.0
        for x, s, y in host["rows"]:
            space = s if idx.r2c else _complex(s)
            bwd = max(bwd, ref.rel_l2(space, ref.backward(_complex(x), idx)))
            fwd = max(fwd, ref.rel_l2(_complex(y),
                                      ref.forward(space, idx, scale)))
        out["bwd_rel_l2"] = bwd
        out["fwd_rel_l2"] = fwd
    else:
        worst = 0.0
        pot = host["potential"]
        for x, _, y in host["rows"]:
            space = ref.backward(_complex(x), idx)
            if pot is not None:
                space = space * pot
            worst = max(worst, ref.rel_l2(_complex(y),
                                          ref.forward(space, idx, scale)))
        out["step_rel_l2"] = worst
    return out
