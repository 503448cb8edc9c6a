"""The reference benchmark's stick set.

SpFFT's benchmark (tests/programs/benchmark.cpp:176-205) transforms every
(x, y) z-stick with ``x < dim_x_freq * sparsity``, each stick holding all
``dim_z`` values; ``dim_x_freq`` is ``dim_x`` for C2C and ``dim_x // 2 + 1``
for R2C. Indices are in storage order (0 .. n - 1), values ordered by x,
then y, then z. The program's port of the same set
(``spfft_tpu.benchmark.cutoff_stick_triplets``) rounds the bound instead;
at the configured sparsities the two agree, and a test holds them equal.
The set lives here so that no change to the program moves the
benchmark's inputs.

A configuration selects it with ``"sticks": {"kind": "cutoff",
"sparsity": s}``.
"""

from __future__ import annotations

import numpy as np


def num_x_columns(dim_x_freq: int, sparsity: float) -> int:
    """How many x columns satisfy ``x < dim_x_freq * sparsity``."""
    bound = dim_x_freq * float(sparsity)
    return int(min(dim_x_freq, np.ceil(bound)))


def cutoff_triplets(dims, sparsity: float, r2c: bool) -> np.ndarray:
    """(x, y, z) rows of every stick with ``x < dim_x_freq * sparsity``."""
    nx, ny, nz = (int(n) for n in dims)
    dim_x_freq = nx // 2 + 1 if r2c else nx
    x = np.arange(num_x_columns(dim_x_freq, sparsity), dtype=np.int32)
    X, Y, Z = np.meshgrid(x, np.arange(ny, dtype=np.int32),
                          np.arange(nz, dtype=np.int32), indexing="ij")
    return np.stack([X.ravel(), Y.ravel(), Z.ravel()], axis=1)


def triplets(config: dict) -> np.ndarray:
    """The stick set a configuration file describes."""
    return cutoff_triplets(config["grid"], config["sticks"]["sparsity"],
                           config["transform"] == "r2c")
