"""The stick set, the reference's conjugate partners and the work of one
transform."""

import json
import math
from pathlib import Path

import numpy as np
import pytest

from benchmark import work
from benchmark.references.sparse_fft import SparseIndex
from benchmark.sticks import cutoff

ROOT = Path(__file__).resolve().parents[2]


def _configs():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    return [json.loads((ROOT / c["file"]).read_text())
            for c in bench["configs"]]


@pytest.mark.parametrize("config", _configs(), ids=lambda c: c["name"])
def test_configured_sticks_match_the_programs_port(config):
    from spfft_tpu.benchmark import cutoff_stick_triplets
    nx, ny, nz = config["grid"]
    s = config["sticks"]["sparsity"]
    r2c = config["transform"] == "r2c"
    for dims in ((nx, ny, nz), (32, 32, 32)):
        assert np.array_equal(cutoff.cutoff_triplets(dims, s, r2c),
                              cutoff_stick_triplets(*dims, s, r2c))


def test_cutoff_keeps_x_below_the_bound_and_whole_sticks():
    # R2C 16^3: dim_x_freq 9, x < 9 * 0.25 = 2.25 keeps x = 0, 1, 2
    t = cutoff.cutoff_triplets((16, 16, 16), 0.25, r2c=True)
    assert sorted(set(t[:, 0])) == [0, 1, 2]
    assert len(t) == 3 * 16 * 16
    # C2C: x < 16 * 0.5 = 8 keeps x = 0 .. 7
    t = cutoff.cutoff_triplets((16, 16, 16), 0.5, r2c=False)
    assert sorted(set(t[:, 0])) == list(range(8))
    assert (t[:16, 2] == np.arange(16)).all()


def test_reference_pairs_the_x0_plane_with_its_conjugates():
    t = cutoff.cutoff_triplets((8, 6, 4), 0.4, r2c=True)
    idx = SparseIndex(t, (8, 6, 4), r2c=True)
    on = np.where(t[:, 0] == 0)[0]
    assert (idx.mirror[on] >= 0).all() and (idx.mirror[t[:, 0] > 0] < 0).all()
    m = idx.mirror[on]
    assert (idx.mirror[m] == on).all()
    assert ((t[m, 1] + t[on, 1]) % 6 == 0).all()
    assert ((t[m, 2] + t[on, 2]) % 4 == 0).all()
    # (0, 0, 0), (0, 3, 0), (0, 0, 2) and (0, 3, 2) are their own partners
    assert (m == on).sum() == 4


def test_transform_work_counts_bytes_and_lines():
    # two sticks, (0, 0) and (1, 0), of four values each on a 4^3 grid
    t = np.array([(x, 0, z) for x in (0, 1) for z in range(4)])
    c2c = work.transform_work({"grid": [4, 4, 4], "transform": "c2c"}, t)
    assert c2c["bytes"] == 8 * 8 + 8 * 64
    line = 5 * 4 * math.log2(4)
    # 2 z-lines, 2 x-columns of y-lines in each of 4 planes, 16 x-lines
    assert c2c["flops"] == pytest.approx(2 * line + 4 * 2 * line
                                         + 16 * line)
    r2c = work.transform_work({"grid": [4, 4, 4], "transform": "r2c"}, t)
    assert r2c["bytes"] == 8 * 8 + 4 * 64
    assert r2c["flops"] == pytest.approx(2 * line + 4 * 2 * line
                                         + 16 * line / 2)


def test_roofline_names_its_bound():
    peak = {"hbm_bytes_per_s": 819e9, "flops_per_s": 197e12}
    # the C2C cell's byte floor: 8388608 values and a 256^3 C2C grid
    config = {"grid": [256, 256, 256], "transform": "c2c"}
    w = work.transform_work(config, cutoff.cutoff_triplets(
        config["grid"], 0.5, r2c=False))
    assert w["bytes"] == 8.0 * 8388608 + 8 * 256 ** 3
    t, bound = work.roofline_seconds(w, peak)
    assert bound == "bytes"
    assert t == pytest.approx(201.33e6 / 819e9, rel=1e-4)
