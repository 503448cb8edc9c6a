"""Benchmark CLI smoke tests (reference: tests/programs/benchmark.cpp —
the harness itself is part of the deliverable, SURVEY.md §6)."""

import json
import os

import numpy as np
import pytest

from spfft_tpu.benchmark import cutoff_stick_triplets, main


def test_cutoff_stick_workload_shape():
    t = cutoff_stick_triplets(8, 6, 4, 0.5, hermitian=False)
    # x < 8 * 0.5 = 4 sticks in x, all y, full z
    assert t.shape == (4 * 6 * 4, 3)
    assert t[:, 0].max() == 3
    assert set(np.unique(t[:, 2])) == set(range(4))


def test_cutoff_stick_workload_hermitian():
    t = cutoff_stick_triplets(8, 6, 4, 1.0, hermitian=True)
    assert t[:, 0].max() == 8 // 2  # dim_x_freq - 1


@pytest.mark.parametrize("flags", [
    ["-d", "12", "-r", "2", "-t", "c2c", "-m", "2"],
    ["-d", "8", "10", "12", "-r", "1", "-t", "r2c", "-s", "0.5"],
    ["-d", "16", "-r", "1", "--shards", "4", "-e", "compactFloat"],
    ["-d", "16", "-r", "1", "--shards", "2", "-t", "r2c", "-p", "host"],
    ["-d", "12", "-r", "2", "-m", "3", "--serve"],
])
def test_cli_runs(flags, tmp_path, capsys):
    out = tmp_path / "bench.json"
    assert main(flags + ["-o", str(out)]) == 0
    payload = json.loads(out.read_text())
    assert "parameters" in payload and "timings" in payload
    assert payload["parameters"]["pair_seconds"] > 0
    assert capsys.readouterr().out  # params + tree printed


def test_cli_exchange_all_sweep(tmp_path, capsys):
    """-e all compares every exchange mechanism on one workload with HLO
    wire bytes (reference: benchmark.cpp:138-156)."""
    out = tmp_path / "sweep.json"
    assert main(["-d", "16", "-r", "1", "--shards", "4", "-e", "all",
                 "-o", str(out)]) == 0
    payload = json.loads(out.read_text())
    rows = payload["exchange_sweep"]
    assert [r["exchange"] for r in rows] == [
        "buffered", "bufferedFloat", "compact", "compactFloat",
        "unbuffered"]
    for r in rows:
        assert r["pair_seconds"] > 0
        assert r["wire_total_bytes"] >= r["busiest_link_bytes"] >= 0
    # float wire halves the bytes
    by = {r["exchange"]: r for r in rows}
    assert by["bufferedFloat"]["wire_total_bytes"] \
        == by["buffered"]["wire_total_bytes"] // 2
    assert capsys.readouterr().out


def test_cli_serve_reports_metrics(tmp_path):
    """--serve routes the -m transforms through the serving layer and
    embeds its metrics (fused batches must appear: m same-signature
    submissions per phase bucket together)."""
    out = tmp_path / "serve_bench.json"
    assert main(["-d", "12", "-r", "3", "-m", "4", "--serve",
                 "-o", str(out)]) == 0
    payload = json.loads(out.read_text())
    serve = payload["parameters"]["serve"]
    assert serve["completed"] == 2 * 4 * (3 + 1)  # warmups + repeats
    assert serve["fused_batches"] >= 1
    assert serve["registry"]["plans"] == 1


def test_cli_store_dir_cold_warm_ab(tmp_path):
    """--store-dir (round 13): the JSON must carry the cold/warm pair
    bench_regress.py compares — a true cold start (empty store, one
    build + spill) and a fresh-registry warm boot with zero builds."""
    out = tmp_path / "bench.json"
    store_dir = tmp_path / "store"
    assert main(["-d", "12", "-r", "1", "-s", "0.5",
                 "--store-dir", str(store_dir),
                 "-o", str(out)]) == 0
    params = json.loads(out.read_text())["parameters"]
    assert params["store_was_cold"] is True
    assert params["cold_start_ms"]["value"] > 0
    assert params["cold_start_ms"]["unit"] == "ms"
    assert params["warm_start_ms"]["value"] > 0
    assert params["warm_builds"] == 0
    assert params["warm_store"]["hits"] == 1


def test_cli_store_dir_rejects_shards():
    with pytest.raises(SystemExit):
        main(["-d", "12", "-r", "1", "--shards", "2",
              "--store-dir", "/tmp/x"])


def test_cli_serve_rejects_shards():
    with pytest.raises(SystemExit):
        main(["-d", "12", "--serve", "--shards", "2"])


def test_cli_exchange_all_needs_shards():
    assert main(["-d", "8", "-e", "all"]) == 2


def test_cli_bad_dims():
    assert main(["-d", "4", "4"]) == 2


def test_cli_fused_ab(tmp_path, monkeypatch):
    """--fused / --no-fused: the A/B flag of the fused compression+
    z-DFT Pallas path (docs/kernels.md). --fused must report the fused
    kernels ACTIVE (off-TPU via the forced matmul-DFT pipeline +
    interpret mode), --no-fused must report them off, and main() must
    restore the env knobs it set (the tier-1 suite shares a process)."""
    for var in ("SPFFT_TPU_FUSED_COMPRESS", "SPFFT_TPU_FUSED_INTERPRET",
                "SPFFT_TPU_FORCE_MATMUL_DFT"):
        monkeypatch.delenv(var, raising=False)
    out_on = tmp_path / "fused_on.json"
    assert main(["-d", "8", "6", "128", "-r", "1", "--fused",
                 "-o", str(out_on)]) == 0
    p_on = json.loads(out_on.read_text())["parameters"]
    assert p_on["fused"] is True
    assert p_on["fused_fallback"] == {}
    assert os.environ.get("SPFFT_TPU_FUSED_COMPRESS") is None

    out_off = tmp_path / "fused_off.json"
    assert main(["-d", "8", "6", "128", "-r", "1", "--no-fused",
                 "-o", str(out_off)]) == 0
    p_off = json.loads(out_off.read_text())["parameters"]
    assert p_off["fused"] is False
    assert os.environ.get("SPFFT_TPU_FUSED_COMPRESS") is None


def test_cli_fused_reports_fallback_reason(tmp_path, monkeypatch):
    """--fused on a fused-ineligible workload (dim_z not a multiple of
    128) still runs — two-kernel path — and the JSON carries the
    per-direction gate reasons the obs counter records."""
    for var in ("SPFFT_TPU_FUSED_COMPRESS", "SPFFT_TPU_FUSED_INTERPRET",
                "SPFFT_TPU_FORCE_MATMUL_DFT"):
        monkeypatch.delenv(var, raising=False)
    out = tmp_path / "fused_fb.json"
    assert main(["-d", "8", "6", "96", "-r", "1", "--fused",
                 "-o", str(out)]) == 0
    params = json.loads(out.read_text())["parameters"]
    assert params["fused"] is False
    assert params["fused_fallback"] == {
        "dec": "dimz_not_multiple_128", "cmp": "dimz_not_multiple_128"}
