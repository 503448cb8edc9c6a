"""The comparison that decides ``correct``, at a size a test run holds.

Each cell is run through ``run.run_cell`` (everything of a run but the
look for a chip) on a 32^3 grid with its own stick rule, mix and limits:
the program passes; the control (the reference one precision step down)
fails; and so does the program broken underneath the timed path, once for
each fault the cell can have: a step that leaves its output unchanged,
half of a batch left out, an answer altered where it is produced, in every
field or in one position of each batch. (No cell spans chips, so no
exchange can be left out.)
"""

import time

import numpy as np
import pytest

from benchmark import run
from benchmark.systems.control import System as ControlSUT
from benchmark.systems.local_plan import System as ProgramSUT

SMALL = {"c2c-256-s0.5.pair": {"grid": [32, 32, 32], "fields": 2,
                               "batch": 1},
         "r2c-256-s0.1-m32.vpsi": {"grid": [32, 32, 32], "fields": 8,
                                   "batch": 4}}


def small_spec(workload):
    spec = run.load_spec(workload)
    s, c = SMALL[workload], spec["config"]
    spec["config"] = dict(c, grid=s["grid"], fields=s["fields"],
                          batch=s["batch"])
    return spec


def run_small(workload, sut_factory, seed=2147483659):
    return run.run_cell(small_spec(workload), seed, 0.3, trace=False,
                        device={"platform": "cpu", "kind": "cpu",
                                "count": 1},
                        peak={}, sut_factory=sut_factory,
                        t_start=time.perf_counter(), log=lambda *a: None)


class Stale(ProgramSUT):
    """Returns the first output of a direction forever: the step leaves
    its output as it was."""

    def __init__(self, *a, direction="backward"):
        super().__init__(*a)
        self._direction, self._first = direction, None

    def _stale(self, out):
        if self._first is None:
            self._first = out.copy()
        # a fresh buffer each time: the pointwise operator donates its input
        return self._first.copy()

    def backward(self, values):
        out = super().backward(values)
        return self._stale(out) if self._direction == "backward" else out

    def forward(self, space):
        out = super().forward(space)
        return self._stale(out) if self._direction == "forward" else out


class HalfBatch(ProgramSUT):
    """Computes only the first half of each batch and copies it over the
    second half."""

    def forward(self, space):
        out = super().forward(space)
        half = out.shape[0] // 2
        return out.at[half:2 * half].set(out[:half])


class Altered(ProgramSUT):
    """Adds 1 to the first value of every field the forward produces."""

    def forward(self, space):
        out = super().forward(space)
        return out.at[..., 0, 0].add(1.0)


class OneSlot(ProgramSUT):
    """Adds 1 to the first value of one position of every batch only."""

    def __init__(self, *a, slot=-1):
        super().__init__(*a)
        self._slot = slot

    def forward(self, space):
        out = super().forward(space)
        return out.at[self._slot, 0, 0].add(1.0)


def _factory(cls, **kw):
    return lambda *a: cls(*a, **kw)


FAULTS = {
    "c2c-256-s0.5.pair": {
        "stale_backward": _factory(Stale, direction="backward"),
        "stale_forward": _factory(Stale, direction="forward"),
        "altered": Altered},
    "r2c-256-s0.1-m32.vpsi": {
        "stale_backward": _factory(Stale, direction="backward"),
        "stale_forward": _factory(Stale, direction="forward"),
        "half_batch": HalfBatch,
        "altered": Altered,
        "first_slot": _factory(OneSlot, slot=0),
        "last_slot": _factory(OneSlot, slot=-1)},
}


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_program_is_correct(workload):
    res = run_small(workload, None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] < c["limit"] / 5


@pytest.mark.parametrize("workload", sorted(SMALL))
def test_control_is_not_correct(workload):
    res = run_small(workload, ControlSUT)
    assert not res["correct"], res["checks"]
    assert any(c["value"] > 3 * c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("workload,fault",
                         [(w, f) for w in sorted(FAULTS) for f in FAULTS[w]])
def test_fault_is_not_correct(workload, fault):
    res = run_small(workload, FAULTS[workload][fault])
    assert not res["correct"], res["checks"]


def test_same_seed_same_inputs_and_large_seeds():
    from benchmark.drivers.closed_loop import _key
    import jax
    for seed in (0, 2**31 + 7, 2**33 + 5):
        a = jax.random.key_data(_key(seed))
        b = jax.random.key_data(_key(seed))
        assert np.array_equal(a, b)
    assert not np.array_equal(jax.random.key_data(_key(2**33 + 5)),
                              jax.random.key_data(_key(5)))


def test_r2c_inputs_are_conjugate_on_the_x0_plane():
    from benchmark.drivers.closed_loop import Cell
    spec = small_spec("r2c-256-s0.1-m32.vpsi")
    config = spec["config"]
    triplets = run.plugin("sticks", "cutoff").triplets(config)
    cell = Cell(config, spec["mix"], 2147483659, None, triplets)
    mirror = cell.idx.mirror
    rows = np.where(mirror >= 0)[0]
    # the whole x = 0 plane: every value's partner is in the set
    assert len(rows) == (triplets[:, 0] == 0).sum() == 32 * 32
    v = np.asarray(cell.inputs[0])
    z = v[..., 0] + 1j * v[..., 1]
    assert np.allclose(z[:, rows], np.conj(z[:, mirror[rows]]))
    assert not np.allclose(z[:, rows].imag, 0)
