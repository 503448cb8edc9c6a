"""Sparse frequency sets, one module per kind, found by a configuration's
``"sticks": {"kind": <module>, ...}``.

Each module defines ``triplets(config) -> np.ndarray``: the (x, y, z) rows
of the set, in the order the values are stored.
"""
