"""The benchmark's readers of the plan's counters and of the z stage's
device time (``benchmark/metrics``), on synthetic records and on a
counter registry of the test's own."""

import pytest

from benchmark import run
from spfft_tpu import obs
from spfft_tpu.obs import counters as obs_counters


@pytest.fixture
def counters(monkeypatch):
    fresh = obs_counters.Counters()
    monkeypatch.setattr(obs, "GLOBAL_COUNTERS", fresh)
    return fresh


#: what a run that built a plan records
BUILT = {"plan_build_s": 7.0}


def _trace(stage_s, transforms=100):
    return {"trace": {"stage_s": stage_s, "transforms": transforms}}


@pytest.mark.parametrize("name", ["plan_index_s", "plan_tables_s"])
def test_plan_readers_none_without_counts(counters, name):
    assert run.read_metric(name, BUILT) is None
    # seconds without a plan built, or a plan without the seconds
    counters.inc("spfft_compile_seconds_total", 2.0, kind="index_plan")
    counters.inc("spfft_compile_seconds_total", 2.0,
                 kind="compression_tables")
    assert run.read_metric(name, BUILT) is None
    counters.reset()
    counters.inc("spfft_plan_builds_total", 1, kind="local")
    assert run.read_metric(name, BUILT) is None
    # counts, but a run whose system built no plan
    counters.inc("spfft_compile_seconds_total", 2.0, kind="index_plan")
    counters.inc("spfft_compile_seconds_total", 2.0,
                 kind="compression_tables")
    assert run.read_metric(name, BUILT) == pytest.approx(2.0)
    assert run.read_metric(name, {}) is None


@pytest.mark.parametrize("name,kind", [("plan_index_s", "index_plan"),
                                       ("plan_tables_s",
                                        "compression_tables")])
def test_plan_readers_per_local_plan(counters, name, kind):
    counters.inc("spfft_plan_builds_total", 2, kind="local")
    counters.inc("spfft_plan_builds_total", 5, kind="distributed")
    counters.inc("spfft_compile_seconds_total", 3.0, kind=kind)
    counters.inc("spfft_compile_seconds_total", 7.0, kind="plan_join")
    assert run.read_metric(name, BUILT) == pytest.approx(1.5)


def test_plan_readers_read_a_real_build(counters):
    """A plan built through the public API feeds both readers."""
    from spfft_tpu import TransformType, make_local_plan
    from spfft_tpu.utils.workloads import spherical_cutoff_triplets
    plan = make_local_plan(TransformType.C2C, 16, 16, 16,
                           spherical_cutoff_triplets(16), use_pallas=True)
    plan.check_build(wait=True)
    plan.close()
    assert run.read_metric("plan_index_s", BUILT) > 0
    assert run.read_metric("plan_tables_s", BUILT) > 0


@pytest.mark.parametrize("record", [
    {},
    _trace({"spfft.xy": 1.0}),
    _trace({"spfft.decompress_z": 1.0}, transforms=0),
    _trace({"unassigned": 1.0, "spfft.layout": 2.0}),
])
def test_z_stage_reader_none(record):
    assert run.read_metric("z_stage_ms_per_transform", record) is None


@pytest.mark.parametrize("stage_s,ms", [
    # fused kernels (the chip's path): 2.851 + 2.788 s over 2232
    ({"spfft.decompress_z": 2.851, "spfft.z_compress": 2.788,
      "spfft.xy": 1.8, "spfft.layout": 0.376, "unassigned": 0.1},
     1e3 * (2.851 + 2.788) / 2232),
    # the unfused path's gathers and z-DFTs, whatever their scope
    ({"spfft.gather": 0.5, "spfft.z": 0.25, "spfft.decompress": 0.1,
      "spfft.compress": 0.1, "spfft.z_backward": 0.02,
      "spfft.z_forward": 0.03, "spfft.pack": 9.0},
     1e3 * 1.0 / 2232),
])
def test_z_stage_reader_sums_the_z_stages(stage_s, ms):
    value = run.read_metric("z_stage_ms_per_transform",
                            _trace(stage_s, transforms=2232))
    assert value == pytest.approx(ms)
