"""Drivers of the traffic, one module per driver, found by a mix's
``"driver"``.

Each module defines:

- ``Cell(config, mix, seed, system, triplets)``: the inputs made from
  ``seed``, with ``warm()`` (every shape the window uses, counted as
  set-up), ``run_window(seconds) -> dict`` (``window_s``, ``steps``,
  ``transforms`` completed in the window, ``per_second``) and ``fetch()``
  (host copies of what the check reads, taken before the system is
  closed);
- ``compare(host) -> {name: number}``: the numbers held against
  ``limits/<cell>.json``, from what ``fetch`` returned.

A mix whose traffic the generic closed loop cannot drive (an open loop of
arrivals, a served executor) brings a driver of its own; a mix made of
data alone names an existing driver.
"""
