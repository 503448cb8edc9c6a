"""Systems a cell can drive, one module per system, found by a
configuration's ``"system"``.

Each module defines ``System(config, triplets)``: built in set-up, with
``build_s`` (host seconds its construction took, or None), ``close()``,
and the entry points the cell's driver calls (the closed loop calls
``backward(values)`` and ``forward(space)``).
"""
