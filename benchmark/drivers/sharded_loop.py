"""The closed loop over the chips of one host: ``closed_loop`` with every
array in the sharded layout of the configuration (``benchmark/sharding.py``
``Layout``).

A mix with ``"driver": "sharded_loop"`` runs the same steps, warm-up
and window as ``closed_loop`` (a subclass; its ``weights`` too): each
step is a backward and a forward of one field's fixed input, fields in
turn. What differs is where the arrays live. Each field's input is made
from the seed on the chips, already as the system takes it, ``(shards,
max_values, 2)`` with shard s's values first and zeros after, so nothing
crosses chips or reaches the host inside the window. After the window
``fetch`` copies each shard's values and its slab to the host, the
values in shard order beside the global row of each, the slabs as the
planes of the global grid, and ``compare`` makes ``closed_loop``'s
``stages`` check slab by slab against the configuration's reference
(``references/sparse_fft_slabs.py``), so that no whole complex128 grid
is held: at 768^3 one is 7.25 GB.

The sharding is the system's own where it states one (``sharding``),
else one over the first ``shards`` devices, in order. Only C2C sets, one
field a step, no pointwise operator and the ``stages`` check are made.
"""

from __future__ import annotations

import numpy as np

from benchmark.drivers import closed_loop
from benchmark.drivers.closed_loop import weights
from benchmark.sharding import layout_of


def _make_block(key, counts, *, shards: int, max_values: int):
    """Normal values (re, im) of one field, zero past each shard's count."""
    import jax
    import jax.numpy as jnp
    v = jax.random.normal(key, (shards, max_values, 2), jnp.float32)
    row = jnp.arange(max_values, dtype=jnp.int32)[None, :, None]
    return jnp.where(row < counts[:, None, None], v, jnp.float32(0.0))


#: what cells made from their stick set, kept while the next cell has the
#: same one: the cells of one calibration share it, and at 768^3 each
#: piece takes seconds
_MADE = {}


def _made(what: str, triplets: np.ndarray, make):
    """``make()``, or what it gave the last cell of this stick set."""
    if _MADE.get("triplets") is not triplets:
        _MADE.clear()
        _MADE["triplets"] = triplets
    if what not in _MADE:
        _MADE[what] = make()
    return _MADE[what]


def default_sharding(shards: int):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    mesh = Mesh(np.asarray(jax.devices()[:shards]), ("shards",))
    return NamedSharding(mesh, PartitionSpec("shards"))


class Cell(closed_loop.Cell):
    def __init__(self, config: dict, mix: dict, seed: int, system,
                 triplets: np.ndarray):
        import jax
        import jax.numpy as jnp

        if config["transform"] != "c2c":
            raise ValueError("the sharded loop makes C2C inputs only")
        if int(config["batch"]) != 1:
            raise ValueError("the sharded loop takes one field a step")
        if mix["pointwise"] != "none" or mix["check"] != "stages":
            raise ValueError("the sharded loop runs the stages check "
                             "with no pointwise operator")
        self.mix, self.seed, self.sut = mix, int(seed), system
        self.batch = 1
        self.nblocks = int(config["fields"])
        self.transforms_per_step = 2
        self.inflight = int(mix["inflight"])
        self.ref = closed_loop.reference(config)
        grid = tuple(int(n) for n in config["grid"])
        self.idx = _made(f"index {self.ref.__name__} {grid}", triplets,
                         lambda: self.ref.SparseIndex(triplets, grid, False))
        nx, ny, nz = config["grid"]
        self.scale = 1.0 / (nx * ny * nz)
        self.layout = (getattr(system, "layout", None)
                       or _made(f"layout {grid} {config['shards']} "
                                f"{config['planes']}", triplets,
                                lambda: layout_of(config, triplets)))
        sharding = (getattr(system, "sharding", None)
                    or default_sharding(self.layout.shards))
        make = jax.jit(_make_block, static_argnames=("shards", "max_values"),
                       out_shardings=sharding)
        counts = jnp.asarray(self.layout.counts, jnp.int32)
        key = closed_loop._key(seed)
        self.inputs = tuple(
            make(jax.random.fold_in(key, b), counts,
                 shards=self.layout.shards,
                 max_values=self.layout.max_values)
            for b in range(self.nblocks))
        self.potential = None
        self._pointwise = None
        self.last = {}
        self._keep_space = True
        self.steps = 0

    def fetch(self) -> dict:
        """Host copies of what ``compare`` reads, each field times its
        weight: values in shard order (``order`` gives their global
        rows), slabs as one grid; call before the system's device state
        is freed."""
        rows = []
        lay = self.layout
        for b in range(self.nblocks):
            c = float(weights(self.seed, b, self.batch)[0])
            x, s, y = self.last[b]
            rows.append((Values(lay.host_values(x), c),
                         Grid(lay, lay.host_slabs(s), c),
                         Values(lay.host_values(y), c)))
        return {"check": self.mix["check"], "ref": self.ref, "idx": self.idx,
                "scale": self.scale, "rows": rows, "potential": None,
                "order": lay.order}


class Values:
    """Values ``(M, 2)`` (re, im) times ``weight``, as complex128 a range
    at a time: ``values[a:b]``."""

    def __init__(self, values: np.ndarray, weight: float):
        self._values = values
        self._weight = weight

    def __len__(self) -> int:
        return len(self._values)

    def __getitem__(self, rows: slice) -> np.ndarray:
        v = self._values[rows]
        out = np.empty(len(v), np.complex128)
        np.multiply(v[:, 0], self._weight, out=out.real)
        np.multiply(v[:, 1], self._weight, out=out.imag)
        return out


class Grid:
    """The shards' slabs ``{shard: (planes, ny, nx, 2)}`` as one
    ``(nz, ny, nx)`` complex128 grid times ``weight``, made a z-range at
    a time: ``grid[z0:z1]``."""

    def __init__(self, layout, slabs: dict, weight: float):
        nx, ny, nz = layout.dims
        self.shape = (nz, ny, nx)
        self._parts = [(layout.offsets[s], slabs[s]) for s in sorted(slabs)]
        self._weight = weight

    def __getitem__(self, planes: slice) -> np.ndarray:
        z0, z1, _ = planes.indices(self.shape[0])
        out = np.empty((z1 - z0,) + self.shape[1:], np.complex128)
        for o, slab in self._parts:
            a, b = max(z0, o), min(z1, o + len(slab))
            if a < b:
                out.real[a - z0:b - z0] = slab[a - o:b - o, ..., 0]
                out.imag[a - z0:b - z0] = slab[a - o:b - o, ..., 1]
        out *= self._weight
        return out


def compare(host: dict) -> dict:
    """The ``stages`` check of ``closed_loop.compare``, slab by slab:
    ``bwd_rel_l2``, the backward output against the reference backward
    of the input, and ``fwd_rel_l2``, the forward output against the
    reference forward of the grid it was given; each the largest over
    the fields. Both numbers of a field come from one reference
    transform (the reference's ``stages_rel_l2``)."""
    ref, idx, scale = host["ref"], host["idx"], host["scale"]
    bwd = fwd = 0.0
    # each field's host copies go as soon as they are read
    rows = host.pop("rows")
    while rows:
        x, grid, y = rows.pop(0)
        b, f = ref.stages_rel_l2(x, grid, y, idx, scale, host["order"])
        bwd, fwd = max(bwd, b), max(fwd, f)
    return {"bwd_rel_l2": bwd, "fwd_rel_l2": fwd}
