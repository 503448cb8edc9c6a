"""Plain reference of the sparse 3D transform, slab by slab over z.

The same definition as ``sparse_fft.py`` (SpFFT's details.rst "Transform
Definition" and "Normalization"), in float64 with SciPy FFTs and nothing
of the program, for grids whose dense complex128 copies the host cannot
hold several of at once (768^3 is 7.25 GB); ``SparseIndex`` is
``sparse_fft.py``'s. Backward: the values are placed on their z-sticks,
each stick takes its inverse z-DFT, and the grid is made slab by slab:
the sticks' rows of a slab of z-planes placed in the compact (y, x)
plane that holds only the occupied x columns, the inverse DFT over y
there, then over x on the whole plane. Forward: the DFT over x of each
slab, over y on its occupied x columns, the sticks read out, then the
z-DFT of every stick, read at the values. Beside the output the host
holds the stick array (dim_z x sticks) and the slabs in work; slabs,
and the gathers, scatters and sums over all values, run on as many
threads as the host has cores.

``stages_rel_l2`` makes the ``stages`` check of one C2C step (the
backward output against the reference backward of its input, the
forward output against the reference forward of that grid) from one
reference transform, the forward of the grid, without holding a
reference grid: the backward error is read in frequency space, where
the DFT keeps the l2 norm (times the grid size). A grid handed to
``forward`` or ``stages_rel_l2`` may be an array or any object whose
``[z0:z1]`` gives those planes as an array (the sharded driver's view
of the program's slabs).

``LowPrecision``, the control, is the same arithmetic on the chips with
every product in three bfloat16 passes, in the sharded layout of
``benchmark/sharding.py`` (C2C sets): each shard's values go to its
sticks and take the z-DFT on the shard's chip, each chip receives every
stick's columns of its own z-planes and takes the 2D DFT of its slab,
and back. The shard count comes from the arrays it is given and the
planes are split evenly.
"""

from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmark.references import sparse_fft
from benchmark.references.sparse_fft import _dft_mats, _mm3
from benchmark.sharding import Layout, unique_inverse

__all__ = ["SparseIndex", "backward", "forward", "stages_rel_l2",
           "LowPrecision"]

try:
    import scipy.fft as _fft
except ImportError:  # pragma: no cover - scipy is installed where this runs
    _fft = np.fft

#: z-planes transformed at a time, on one thread each
SLAB_PLANES = 16
THREADS = max(1, os.cpu_count() or 1)


class SparseIndex(sparse_fft.SparseIndex):
    """``sparse_fft.SparseIndex``; a C2C set's positions are kept in
    int32 and its ``mirror`` (all -1) is not stored, which at 768^3 saves
    tens of seconds of int64 copies."""

    def __init__(self, triplets, dims, r2c: bool):
        if r2c:
            super().__init__(triplets, dims, r2c)
            return
        t = np.asarray(triplets)
        self.dims = tuple(int(n) for n in dims)
        self.r2c = False
        self.nx_freq = self.dims[0]
        self.ix, self.iy, self.iz = (
            np.mod(t[:, k], n, dtype=np.int32)
            for k, n in enumerate(self.dims))
        self.partner = None
        self.mirror = np.broadcast_to(np.int64(-1), (len(t),))


class _Sticks:
    """The z-sticks of a set. The z-DFT runs on a (sticks, dim_z) array in
    the order of the sticks' keys, where ``slot`` is the flat place
    (stick * dim_z + z) of each value and, for R2C, ``partner`` the place
    of each x = 0 value's conjugate partner. The DFTs over y and x run on
    (dim_z, sticks) rows in the order of the sticks' places in the
    compact plane, the (y, x) plane cut to the occupied x columns ``xs``:
    row entry j is stick ``order[j]`` and lands at ``ccol[j]``."""

    def __init__(self, idx: SparseIndex):
        _, ny, nz = idx.dims
        keys = idx.ix.astype(np.int64) * ny + idx.iy
        if idx.partner is not None:
            pz, py, rows = idx.partner
            keys = np.concatenate([keys, py])   # partners lie at x = 0
        uniq, inv = unique_inverse(keys)
        del keys
        self.count = len(uniq)
        sx, sy = uniq // ny, uniq % ny
        self.xs = np.unique(sx)
        #: the occupied x columns are 0 .. len(xs) - 1
        self.x_prefix = (bool(self.xs[-1] == len(self.xs) - 1)
                         if self.count else True)
        cc = sy * len(self.xs) + np.searchsorted(self.xs, sx)
        self.order = np.argsort(cc, kind="stable")
        self.ccol = cc[self.order]
        #: every place of the compact plane holds a stick, in order
        self.dense = self.count == ny * len(self.xs)
        m = len(idx.ix)
        self.slot = inv[:m] * nz
        self.slot += idx.iz
        self.partner = None
        if idx.partner is not None:
            self.partner = (inv[m:] * nz + pz, rows)

    @staticmethod
    def of(idx: SparseIndex) -> "_Sticks":
        """The sticks of ``idx``, found once and kept with it."""
        st = idx.__dict__.get("_sticks")
        if st is None:
            st = idx.__dict__["_sticks"] = _Sticks(idx)
        return st

    def to_rows(self, sticks: np.ndarray) -> np.ndarray:
        """(sticks, dim_z) in key order -> (dim_z, sticks) rows in place
        order, a tile of sticks at a time."""
        rows = np.empty(sticks.shape[::-1], sticks.dtype)

        def tiles(a, b):
            for t in range(a, b, _TILE):
                u = min(b, t + _TILE)
                rows[:, t:u] = sticks[self.order[t:u]].T

        _in_chunks(tiles, self.count)
        return rows

    def to_sticks(self, rows: np.ndarray) -> np.ndarray:
        """The inverse of ``to_rows``."""
        sticks = np.empty(rows.shape[::-1], rows.dtype)

        def tiles(a, b):
            for t in range(a, b, _TILE):
                u = min(b, t + _TILE)
                sticks[self.order[t:u]] = rows[:, t:u].T

        _in_chunks(tiles, self.count)
        return sticks


#: sticks moved between the two layouts at a time: a tile of every z of
#: them stays in a core's cache
_TILE = 64


def _each_slab(fn, nz: int) -> list:
    """``fn(z0, z1)`` for every slab of ``SLAB_PLANES`` planes, on
    ``THREADS`` threads; the results in order."""
    bounds = [(z0, min(nz, z0 + SLAB_PLANES))
              for z0 in range(0, nz, SLAB_PLANES)]
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(lambda b: fn(*b), bounds))


def _in_chunks(fn, n: int) -> list:
    """``[fn(a, b)]`` over ``THREADS`` chunks ``[a, b)`` of ``range(n)``,
    on as many threads (NumPy's gathers, scatters and reductions run
    outside the GIL)."""
    bounds = np.linspace(0, n, THREADS + 1).astype(np.int64)
    with ThreadPoolExecutor(THREADS) as pool:
        return list(pool.map(lambda k: fn(bounds[k], bounds[k + 1]),
                             range(THREADS)))


def _sq(a: np.ndarray) -> float:
    """sum |a|^2 in NumPy's own loop (a BLAS call would start threads of
    its own under the pool's)."""
    if np.iscomplexobj(a):
        try:
            a = a.view(np.float64)
        except ValueError:   # the last axis is strided
            a = np.ascontiguousarray(a).view(np.float64)
    axes = "abcdefgh"[:a.ndim]
    return float(np.einsum(f"{axes},{axes}->", a, a))


def _backward_sticks(values, idx: SparseIndex):
    """The sticks of ``values`` after the inverse z-DFT, as rows:
    (dim_z, sticks)."""
    _, _, nz = idx.dims
    st = _Sticks.of(idx)
    values = np.asarray(values, np.complex128)
    sticks = np.zeros(st.count * nz, np.complex128)
    if st.partner is not None:
        pslot, rows = st.partner
        sticks[pslot] = np.conj(values[rows])

    def put(a, b):
        sticks[st.slot[a:b]] = values[a:b]

    _in_chunks(put, len(values))
    # unnormalised inverse DFTs (norm="forward" puts the 1/n on the
    # forward transform)
    sticks = _fft.ifft(sticks.reshape(st.count, nz), axis=1,
                       overwrite_x=True, norm="forward", workers=THREADS)
    return st.to_rows(sticks)


def _backward_slab(sticks, idx: SparseIndex, z0: int, z1: int):
    """Planes [z0, z1) of the reference backward: the sticks' rows of
    those planes in the compact plane, the inverse DFT over y there, then
    over x on the whole plane."""
    nx, ny, _ = idx.dims
    st = _Sticks.of(idx)
    p, nxo = z1 - z0, len(st.xs)
    if st.dense:
        plane = _fft.ifft(sticks[z0:z1].reshape(p, ny, nxo), axis=1,
                          norm="forward")
    else:
        plane = np.zeros((p, ny * nxo), np.complex128)
        plane[:, st.ccol] = sticks[z0:z1]
        plane = _fft.ifft(plane.reshape(p, ny, nxo), axis=1,
                          overwrite_x=True, norm="forward")
    slab = np.zeros((p, ny, idx.nx_freq), np.complex128)
    if st.x_prefix:
        slab[..., :nxo] = plane
    else:
        slab[..., st.xs] = plane
    if idx.r2c:
        return _fft.irfft(slab, n=nx, axis=2, norm="forward")
    return _fft.ifft(slab, axis=2, overwrite_x=True, norm="forward")


def backward(values: np.ndarray, idx: SparseIndex) -> np.ndarray:
    """Unnormalised inverse DFT of the sparse ``values`` (complex,
    shape (M,)): (nz, ny, nx) complex128, or float64 for R2C."""
    nx, ny, nz = idx.dims
    sticks = _backward_sticks(values, idx)
    out = np.empty((nz, ny, nx), np.float64 if idx.r2c else np.complex128)

    def one(z0, z1):
        out[z0:z1] = _backward_slab(sticks, idx, z0, z1)

    _each_slab(one, nz)
    return out


def _stick_rows(space, idx: SparseIndex, rows, z0: int, z1: int,
                energy: bool = False) -> float:
    """Rows [z0, z1) of ``rows`` (dim_z, sticks): the 2D DFT of those
    planes of ``space`` at the stick columns. With ``energy``, also the
    2D DFT's squared norm at every other column of those planes (C2C),
    else 0: the x columns no stick lies in by Parseval over y (they take
    no DFT over y), the rest directly."""
    _, ny, _ = idx.dims
    st = _Sticks.of(idx)
    nxo = len(st.xs)
    if idx.r2c:
        slab = _fft.rfft(np.asarray(space[z0:z1], np.float64), axis=2)
    else:
        slab = _fft.fft(np.asarray(space[z0:z1], np.complex128), axis=2)
    plane = slab[..., :nxo] if st.x_prefix else slab[..., st.xs]
    off = 0.0
    if energy:
        rest = (slab[..., nxo:] if st.x_prefix
                else np.delete(slab, st.xs, axis=2))
        off = ny * _sq(rest)
    plane = _fft.fft(plane, axis=1).reshape(z1 - z0, ny * nxo)
    if st.dense:
        rows[z0:z1] = plane
    else:
        rows[z0:z1] = plane[:, st.ccol]
        if energy:
            off += _sq(np.delete(plane, st.ccol, axis=1))
    return off


def forward(space, idx: SparseIndex, scale: float) -> np.ndarray:
    """DFT of ``space`` (nz, ny, nx) read at the sparse positions, times
    ``scale``: (M,) complex128."""
    _, _, nz = idx.dims
    st = _Sticks.of(idx)
    rows = np.empty((nz, st.count), np.complex128)
    _each_slab(lambda z0, z1: _stick_rows(space, idx, rows, z0, z1), nz)
    sticks = _fft.fft(st.to_sticks(rows), axis=1, overwrite_x=True,
                      workers=THREADS).reshape(-1)
    out = np.empty(len(st.slot), np.complex128)

    def take(a, b):
        np.multiply(sticks[st.slot[a:b]], scale, out=out[a:b])

    _in_chunks(take, len(out))
    return out


def stages_rel_l2(values, space, out, idx: SparseIndex, scale: float,
                  order=None) -> tuple:
    """(``bwd_rel_l2``, ``fwd_rel_l2``) of one C2C step: ``space``, the
    backward output of ``values``, against ``backward(values, idx)``, and
    ``out``, the forward output of ``space``, against ``forward(space,
    idx, scale)``. ``values[k]`` and ``out[k]`` are complex and belong to
    the set's value ``order[k]`` (by default k); either may be an array
    or any object whose ``[a:b]`` gives those values as an array.

    Both come from one reference transform F, the DFT of ``space``. The
    reference backward g has DFT(g) = n x at the set and 0 elsewhere (n
    the grid size), and the DFT keeps the l2 norm times sqrt(n), so
    ||space - g||^2 = (||F - n x||^2 at the set + ||F||^2 off it) / n
    and ||g||^2 = n ||x||^2. The norm off the set is summed where F is
    made, from nothing that cancels: the columns of each plane that hold
    no stick (times dim_z: Parseval over z), and the stick places that
    hold no value."""
    if idx.r2c:
        raise ValueError("stages_rel_l2 checks C2C sets")
    nx, ny, nz = idx.dims
    n = float(nx) * ny * nz
    st = _Sticks.of(idx)
    rows = np.empty((nz, st.count), np.complex128)
    off = nz * sum(_each_slab(
        lambda z0, z1: _stick_rows(space, idx, rows, z0, z1, True), nz))
    sticks = _fft.fft(st.to_sticks(rows), axis=1, overwrite_x=True,
                      workers=THREADS).reshape(-1)
    del rows
    if sticks.size > len(st.slot):
        free = np.ones(sticks.size, bool)
        free[st.slot] = False
        off += _sq(sticks[free])

    def part(a, b):
        sums = np.zeros(4)
        for c in range(a, b, _PART):
            d = min(b, c + _PART)
            at = st.slot[c:d] if order is None else st.slot[order[c:d]]
            f = sticks[at]
            x = np.asarray(values[c:d], np.complex128)
            db = f - n * x
            f *= scale
            y = np.asarray(out[c:d], np.complex128) - f
            sums += (_sq(db), _sq(x), _sq(y), _sq(f))
        return sums

    num_b, norm_x, num_f, norm_f = np.sum(_in_chunks(part, len(st.slot)),
                                          axis=0)
    bwd = np.sqrt(num_b + off) / max(n * np.sqrt(norm_x), 1e-300)
    fwd = np.sqrt(num_f) / max(np.sqrt(norm_f), 1e-300)
    return float(bwd), float(fwd)


#: values compared at a time on one thread
_PART = 1 << 18


# -- the control: the same transform at the precision one step down --------

class LowPrecision:
    """The reference on the chips with every matmul in three bfloat16
    passes, taking and giving the sharded layouts of
    ``benchmark/sharding.py``: values (shards, max_values, 2), slabs
    (shards, max_planes, ny, nx, 2), each a jax array sharded over its
    leading axis. C2C sets only."""

    def __init__(self, idx: SparseIndex):
        if idx.r2c:
            raise ValueError("the sharded control transforms C2C sets")
        self.idx = idx
        self._plans = {}

    def _plan(self, shards: int):
        if shards not in self._plans:
            self._plans[shards] = _ShardedControl(self.idx, shards)
        return self._plans[shards]

    def backward(self, values):
        return self._plan(values.shape[0]).backward(values)

    def forward(self, space, scale: float):
        return self._plan(space.shape[0]).forward(space, float(scale))


class _ShardedControl:
    """Tables and the two programs of the control for one shard count:
    one ``shard_map`` a direction over the mesh of the arrays it is
    given, the stick/plane transpose an ``all_to_all``."""

    def __init__(self, idx: SparseIndex, shards: int):
        nx, ny, nz = idx.dims
        if nz % shards:
            raise ValueError("the sharded control splits z evenly")
        p = nz // shards
        lay = Layout(idx.ix, idx.iy, idx.dims, shards, [p] * shards)
        self.layout, self.planes = lay, p
        keys = idx.ix.astype(np.int64) * ny + idx.iy
        sticks = [unique_inverse(keys[rows]) for rows in lay.rows]
        self.max_sticks = ms = max(len(u) for u, _ in sticks)
        #: per shard: the (stick, z) slot of each value (ms * nz: none),
        #: and the value of each slot (max_values: none)
        value_slot = np.full((shards, lay.max_values), ms * nz, np.int32)
        slot_value = np.full((shards, ms * nz), lay.max_values, np.int32)
        #: the (y, x) plane column of every shard's sticks (ny * nx: none)
        col_of_stick = np.full((shards, ms), ny * nx, np.int32)
        for s, (rows, (uniq, inv)) in enumerate(zip(lay.rows, sticks)):
            slot = inv * nz + idx.iz[rows]
            value_slot[s, :len(rows)] = slot
            slot_value[s, slot] = np.arange(len(rows))
            col_of_stick[s, :len(uniq)] = (uniq % ny) * nx + uniq // ny
        col_of_stick = col_of_stick.reshape(-1)
        #: the stick (shard * ms + i) of every plane column (S * ms: none)
        stick_of_col = np.full(ny * nx, shards * ms, np.int32)
        real = col_of_stick < ny * nx
        stick_of_col[col_of_stick[real]] = np.flatnonzero(real)
        self.tables = {"value_slot": value_slot, "slot_value": slot_value,
                       "col_of_stick": col_of_stick,
                       "stick_of_col": stick_of_col}
        self.mats = {(n, sign): _dft_mats(n, sign)
                     for n in {nx, ny, nz} for sign in (1, -1)}
        self._programs = {}

    def _program(self, direction: str, mesh, scale: float = 1.0):
        import functools

        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        key = (direction, mesh, scale)
        if key in self._programs:
            return self._programs[key]
        axis = mesh.axis_names[0]
        nx, ny, nz = self.layout.dims
        shards, p, ms = self.layout.shards, self.planes, self.max_sticks
        sharded, whole = NamedSharding(mesh, P(axis)), NamedSharding(mesh, P())
        t = {k: jax.device_put(v, sharded if v.ndim == 2 else whole)
             for k, v in self.tables.items()}
        sign = 1 if direction == "backward" else -1
        mats = [jax.device_put(m, whole) for n in (nz, ny, nx)
                for m in self.mats[(n, sign)]]
        geom = dict(axis=axis, shards=shards, planes=p, sticks=ms,
                    ny=ny, nx=nx, nz=nz)
        if direction == "backward":
            body = functools.partial(_backward_shard, **geom)
            tabs = (t["slot_value"], t["stick_of_col"])
            specs = (P(axis), P(axis), P())
        else:
            body = functools.partial(_forward_shard, scale=scale, **geom)
            tabs = (t["col_of_stick"], t["value_slot"])
            specs = (P(axis), P(), P(axis))
        run = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=specs + (P(),) * 6,
                                    out_specs=P(axis)))
        self._programs[key] = prog = (run, tabs, mats)
        return prog

    def backward(self, values):
        run, tabs, mats = self._program("backward", values.sharding.mesh)
        return run(values, *tabs, *mats)

    def forward(self, space, scale: float):
        run, tabs, mats = self._program("forward", space.sharding.mesh,
                                        scale)
        return run(space, *tabs, *mats)


def _cdft(re, im, c, s):
    """Complex DFT along the last axis with the (cos, sin) matrices, each
    product in three bfloat16 passes."""
    return _mm3(re, c) - _mm3(im, s), _mm3(re, s) + _mm3(im, c)


def _backward_shard(v, slot_value, stick_of_col, cz, sz, cy, sy, cx, sx,
                    *, axis, shards, planes, sticks, ny, nx, nz):
    """One shard: its values (1, max_values, 2) on its sticks, z-DFT,
    every shard's sticks at this shard's planes (all_to_all), placed in
    the (y, x) planes, DFT over y then x: (1, planes, ny, nx, 2)."""
    import jax
    import jax.numpy as jnp

    def on_sticks(lane):
        return jnp.take(v[0, :, lane], slot_value[0], mode="fill",
                        fill_value=0.0).reshape(sticks, nz)

    re, im = _cdft(on_sticks(0), on_sticks(1), cz, sz)

    def to_planes(a):
        blocks = a.reshape(sticks, shards, planes).transpose(1, 0, 2)
        blocks = jax.lax.all_to_all(blocks, axis, 0, 0, tiled=True)
        grid = jnp.take(blocks.reshape(shards * sticks, planes),
                        stick_of_col, axis=0, mode="fill", fill_value=0.0)
        return grid.T.reshape(planes, ny, nx)

    re, im = to_planes(re), to_planes(im)
    re, im = _cdft(jnp.swapaxes(re, 1, 2), jnp.swapaxes(im, 1, 2), cy, sy)
    re, im = _cdft(jnp.swapaxes(re, 1, 2), jnp.swapaxes(im, 1, 2), cx, sx)
    return jnp.stack([re, im], axis=-1)[None]


def _forward_shard(slab, col_of_stick, value_slot, cz, sz, cy, sy, cx, sx,
                   *, scale, axis, shards, planes, sticks, ny, nx, nz):
    """One shard: DFT of its slab (1, planes, ny, nx, 2) over x then y,
    every shard's stick columns sent to it (all_to_all), z-DFT of its own
    sticks, read at its values: (1, max_values, 2) times ``scale``."""
    import jax
    import jax.numpy as jnp
    re, im = _cdft(slab[0, ..., 0], slab[0, ..., 1], cx, sx)
    re, im = _cdft(jnp.swapaxes(re, 1, 2), jnp.swapaxes(im, 1, 2), cy, sy)
    re, im = jnp.swapaxes(re, 1, 2), jnp.swapaxes(im, 1, 2)

    def to_sticks(a):
        cols = jnp.take(a.reshape(planes, ny * nx), col_of_stick, axis=1,
                        mode="fill", fill_value=0.0)
        blocks = cols.reshape(planes, shards, sticks).transpose(1, 2, 0)
        blocks = jax.lax.all_to_all(blocks, axis, 0, 0, tiled=True)
        return blocks.transpose(1, 0, 2).reshape(sticks, nz)

    re, im = _cdft(to_sticks(re), to_sticks(im), cz, sz)

    def at_values(a):
        return jnp.take(a.reshape(-1), value_slot[0], mode="fill",
                        fill_value=0.0)

    return (jnp.stack([at_values(re), at_values(im)], axis=-1)
            * scale)[None]
