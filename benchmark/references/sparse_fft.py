"""Plain reference of the sparse 3D transform.

Backward: scatter the sparse values into the dense frequency grid (for
R2C the half grid, with the x = 0 plane's missing half filled by complex
conjugation) and take the unnormalised inverse DFT. Forward: DFT of the
space grid, read at the sparse positions, times ``scale``. This is the
definition in SpFFT's documentation (details.rst "Transform Definition"
and "Normalization"); nothing here imports the program.

Two implementations of the same arithmetic:

- ``backward`` / ``forward``: NumPy (or SciPy) FFTs in float64 on the
  host, the reference each run's outputs are compared with;
- ``LowPrecision``: dense DFT matrices applied axis by axis on the device,
  every product taken in three bfloat16 passes (what ``Precision.HIGH``
  does to a float32 matmul on a TPU), written out explicitly so that it
  computes the same on any backend. It stands in the program's place as
  the control that the comparison has to fail.

Space grids are (dim_z, dim_y, dim_x); the program's device layouts are
interleaved (..., 2) float32 for complex arrays.
"""

from __future__ import annotations

import os

import numpy as np

try:
    import scipy.fft as _fft

    _KW = {"workers": max(1, min(8, os.cpu_count() or 1))}
except ImportError:  # pragma: no cover - scipy is installed where this runs
    _fft = np.fft
    _KW = {}


class SparseIndex:
    """Grid positions of a sparse set. ``triplets`` are (x, y, z) rows,
    centered or in storage order; for R2C they hold x >= 0 only."""

    def __init__(self, triplets, dims, r2c: bool):
        t = np.asarray(triplets, np.int64)
        self.dims = tuple(int(n) for n in dims)
        nx, ny, nz = self.dims
        self.r2c = bool(r2c)
        self.nx_freq = nx // 2 + 1 if r2c else nx
        self.ix = t[:, 0] % nx
        self.iy = t[:, 1] % ny
        self.iz = t[:, 2] % nz
        if r2c:
            if (t[:, 0] < 0).any() or (t[:, 0] >= nx - nx // 2).any():
                raise ValueError("R2C sets hold 0 <= x < dim_x - dim_x // 2")
            # the x = 0 plane's conjugate partners: (0, -y, -z)
            rows = np.where(self.ix == 0)[0]
            py, pz = (-self.iy[rows]) % ny, (-self.iz[rows]) % nz
            self.partner = (pz, py, rows)
            #: row of each x = 0 value's partner where the set holds it
            #: (its own row for a value that is its own partner), else -1;
            #: such a pair of values has to be conjugate
            self.mirror = np.full(len(t), -1, np.int64)
            if len(rows):
                keys = self.iy[rows] * nz + self.iz[rows]
                order = np.argsort(keys)
                want = py * nz + pz
                pos = np.minimum(np.searchsorted(keys[order], want),
                                 len(rows) - 1)
                found = keys[order][pos] == want
                self.mirror[rows[found]] = rows[order[pos[found]]]
        else:
            self.partner = None
            self.mirror = np.full(len(t), -1, np.int64)

    @property
    def size(self) -> int:
        nx, ny, nz = self.dims
        return nx * ny * nz


def scatter(values: np.ndarray, idx: SparseIndex) -> np.ndarray:
    """Dense frequency grid (nz, ny, nx_freq) complex128 of ``values``."""
    _, ny, nz = idx.dims
    grid = np.zeros((nz, ny, idx.nx_freq), np.complex128)
    if idx.partner is not None:
        pz, py, rows = idx.partner
        grid[pz, py, 0] = np.conj(values[rows])
    grid[idx.iz, idx.iy, idx.ix] = values
    return grid


def backward(values: np.ndarray, idx: SparseIndex) -> np.ndarray:
    """Unnormalised inverse DFT of the sparse ``values`` (complex,
    shape (M,)): (nz, ny, nx) complex128, or float64 for R2C."""
    grid = scatter(np.asarray(values, np.complex128), idx)
    nx, ny, nz = idx.dims
    if idx.r2c:
        return _fft.irfftn(grid, s=(nz, ny, nx), axes=(0, 1, 2),
                           **_KW) * idx.size
    return _fft.ifftn(grid, axes=(0, 1, 2), **_KW) * idx.size


def forward(space: np.ndarray, idx: SparseIndex, scale: float) -> np.ndarray:
    """DFT of ``space`` (nz, ny, nx) read at the sparse positions, times
    ``scale``: (M,) complex128."""
    if idx.r2c:
        grid = _fft.rfftn(np.asarray(space, np.float64), axes=(0, 1, 2),
                          **_KW)
    else:
        grid = _fft.fftn(np.asarray(space, np.complex128), axes=(0, 1, 2),
                         **_KW)
    return grid[idx.iz, idx.iy, idx.ix] * scale


def rel_l2(got, want) -> float:
    """||got - want|| / ||want||."""
    want = np.asarray(want)
    den = float(np.linalg.norm(want))
    return float(np.linalg.norm(np.asarray(got) - want) / max(den, 1e-300))


# -- the control: the same transform at the precision one step down --------

class LowPrecision:
    """The reference on the device with every matmul in three bfloat16
    passes, taking and giving the program's device layouts: values
    (..., M, 2), space (..., nz, ny, nx, 2) for C2C or (..., nz, ny, nx)
    for R2C. A leading batch axis is mapped over one field at a time."""

    def __init__(self, idx: SparseIndex):
        import jax
        import jax.numpy as jnp

        self.idx = idx
        nx, ny, nz = idx.dims
        self._iz = jnp.asarray(idx.iz, jnp.int32)
        self._iy = jnp.asarray(idx.iy, jnp.int32)
        self._ix = jnp.asarray(idx.ix, jnp.int32)
        if idx.partner is not None:
            pz, py, rows = idx.partner
            self._pz = jnp.asarray(pz, jnp.int32)
            self._py = jnp.asarray(py, jnp.int32)
            self._prows = jnp.asarray(rows, jnp.int32)
        self._mats = {}
        for n in {nx, ny, nz}:
            for sign in (1, -1):
                self._mats[(n, sign)] = _dft_mats(n, sign)
        if idx.r2c:
            self._c2r = _c2r_mats(nx)
            self._r2c = _r2c_mats(nx)
        self._bwd = jax.jit(self._backward_batch)
        self._fwd = jax.jit(self._forward_batch, static_argnums=1)

    # planar complex DFT along ``axis`` of (re, im)
    def _dft(self, re, im, axis, sign):
        import jax.numpy as jnp
        c, s = self._mats[(re.shape[axis], sign)]
        re, im = jnp.moveaxis(re, axis, -1), jnp.moveaxis(im, axis, -1)
        out_re = _mm3(re, c) - _mm3(im, s)
        out_im = _mm3(re, s) + _mm3(im, c)
        return jnp.moveaxis(out_re, -1, axis), jnp.moveaxis(out_im, -1, axis)

    def _backward_one(self, v):
        import jax.numpy as jnp
        idx = self.idx
        _, ny, nz = idx.dims
        shape = (nz, ny, idx.nx_freq)
        re = jnp.zeros(shape, jnp.float32)
        im = jnp.zeros(shape, jnp.float32)
        if idx.partner is not None:
            re = re.at[self._pz, self._py, 0].set(v[self._prows, 0])
            im = im.at[self._pz, self._py, 0].set(-v[self._prows, 1])
        re = re.at[self._iz, self._iy, self._ix].set(v[:, 0])
        im = im.at[self._iz, self._iy, self._ix].set(v[:, 1])
        re, im = self._dft(re, im, 0, 1)
        re, im = self._dft(re, im, 1, 1)
        if idx.r2c:
            a, b = self._c2r
            return _mm3(re, a) + _mm3(im, b)
        re, im = self._dft(re, im, 2, 1)
        return jnp.stack([re, im], axis=-1)

    def _forward_one(self, space, scale):
        import jax.numpy as jnp
        if self.idx.r2c:
            c, s = self._r2c
            re, im = _mm3(space, c), _mm3(space, s)
        else:
            re, im = self._dft(space[..., 0], space[..., 1], 2, -1)
        re, im = self._dft(re, im, 1, -1)
        re, im = self._dft(re, im, 0, -1)
        at = (self._iz, self._iy, self._ix)
        return jnp.stack([re[at], im[at]], axis=-1) * scale

    def _backward_batch(self, v):
        import jax
        if v.ndim == 3:
            return jax.lax.map(self._backward_one, v)
        return self._backward_one(v)

    def _forward_batch(self, space, scale):
        import jax
        batched = space.ndim == (4 if self.idx.r2c else 5)
        if batched:
            return jax.lax.map(lambda s: self._forward_one(s, scale), space)
        return self._forward_one(space, scale)

    def backward(self, values):
        return self._bwd(values)

    def forward(self, space, scale: float):
        return self._fwd(space, float(scale))


def _split_bf16(a):
    """``a`` as hi + lo, each exact in bfloat16: hi keeps the top 16 bits
    of the float32 word (a bit mask, which no compiler folds away as it
    may fold a float32 -> bfloat16 -> float32 round trip), lo is the exact
    rest rounded to bfloat16."""
    import jax
    import jax.numpy as jnp
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi.astype(jnp.bfloat16), (a - hi).astype(jnp.bfloat16)


def _mm3(a, b):
    """``a @ b`` of float32 operands in three bfloat16 passes (hi*hi +
    hi*lo + lo*hi, float32 accumulation)."""
    import jax.numpy as jnp
    ah, al = _split_bf16(a)
    bh, bl = _split_bf16(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(ah, bh) + dot(ah, bl) + dot(al, bh)


def _dft_mats(n: int, sign: int):
    """(cos, sin) float32 matrices of exp(sign 2 pi i j k / n)."""
    import jax.numpy as jnp
    jk = np.outer(np.arange(n), np.arange(n)) % n
    ang = 2.0 * np.pi * jk / n
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(sign * np.sin(ang), jnp.float32))


def _c2r_mats(n: int):
    """Half spectrum (k = 0 .. n//2) to a real line of length n: the
    weights count each k != 0, n/2 twice (its conjugate twin)."""
    import jax.numpy as jnp
    k = np.arange(n // 2 + 1)
    w = np.where((k == 0) | (2 * k == n), 1.0, 2.0)
    ang = 2.0 * np.pi * np.outer(k, np.arange(n)) / n
    return (jnp.asarray(w[:, None] * np.cos(ang), jnp.float32),
            jnp.asarray(-w[:, None] * np.sin(ang), jnp.float32))


def _r2c_mats(n: int):
    """Real line of length n to its half spectrum (k = 0 .. n//2)."""
    import jax.numpy as jnp
    ang = 2.0 * np.pi * np.outer(np.arange(n), np.arange(n // 2 + 1)) / n
    return (jnp.asarray(np.cos(ang), jnp.float32),
            jnp.asarray(-np.sin(ang), jnp.float32))
