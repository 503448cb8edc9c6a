"""scripts/bench_regress.py: the machine-checked perf-trajectory guard
(make bench-check). Exercised in-process via runpy-style import of the
script's main(), with synthetic measurement files."""

import json
import importlib.util
import os
import sys

import pytest

_SCRIPT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts", "bench_regress.py")
_spec = importlib.util.spec_from_file_location("bench_regress", _SCRIPT)
bench_regress = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(bench_regress)


def _write(path, value, unit="s", wrap=False, metric="m"):
    payload = {"metric": metric, "value": value, "unit": unit}
    if wrap:
        payload = {"n": 1, "parsed": payload}
    path.write_text(json.dumps(payload))
    return str(path)


def test_within_threshold_passes(tmp_path, capsys):
    fresh = _write(tmp_path / "fresh.json", 0.0110)
    ref = _write(tmp_path / "ref.json", 0.0106)
    rc = bench_regress.main(["--fresh", fresh, "--against", ref,
                             "--threshold", "0.15"])
    assert rc == 0
    verdict = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert verdict["ok"] and verdict["verdict"] == "within-threshold"


def test_seconds_regression_fails(tmp_path, capsys):
    fresh = _write(tmp_path / "fresh.json", 0.020)  # ~2x slower
    ref = _write(tmp_path / "ref.json", 0.0106)
    rc = bench_regress.main(["--fresh", fresh, "--against", ref])
    assert rc == 1
    verdict = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert not verdict["ok"]
    assert verdict["direction"] == "lower-is-better"


def test_rate_unit_direction(tmp_path, capsys):
    # req/s: HIGHER is better — a drop regresses, a gain passes
    ref = _write(tmp_path / "ref.json", 1000.0, unit="req/s")
    worse = _write(tmp_path / "worse.json", 500.0, unit="req/s")
    better = _write(tmp_path / "better.json", 2000.0, unit="req/s")
    assert bench_regress.main(["--fresh", worse, "--against", ref]) == 1
    assert bench_regress.main(["--fresh", better, "--against", ref]) == 0
    capsys.readouterr()


def test_improvement_always_passes(tmp_path):
    fresh = _write(tmp_path / "fresh.json", 0.005)  # 2x faster
    ref = _write(tmp_path / "ref.json", 0.0106)
    assert bench_regress.main(["--fresh", fresh,
                               "--against", ref]) == 0


def test_wrapped_bench_r_format_and_latest_selection(tmp_path, capsys):
    # driver BENCH_r*.json format resolves through "parsed", and the
    # highest-numbered reference wins
    _write(tmp_path / "BENCH_r07.json", 0.020, wrap=True)
    _write(tmp_path / "BENCH_r10.json", 0.010, wrap=True)
    fresh = _write(tmp_path / "fresh.json", 0.0105)
    rc = bench_regress.main(["--fresh", fresh, "--root", str(tmp_path)])
    assert rc == 0
    verdict = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert verdict["reference"] == 0.010
    assert verdict["reference_file"].endswith("BENCH_r10.json")


def test_no_reference_is_not_a_failure(tmp_path, capsys):
    fresh = _write(tmp_path / "fresh.json", 0.010)
    rc = bench_regress.main(["--fresh", fresh, "--root", str(tmp_path)])
    assert rc == 0
    verdict = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert verdict["verdict"] == "no-reference"


def test_usage_errors(tmp_path, capsys):
    fresh = _write(tmp_path / "fresh.json", 0.010)
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert bench_regress.main(["--fresh", str(bad)]) == 2
    other_unit = _write(tmp_path / "o.json", 5.0, unit="req/s")
    assert bench_regress.main(["--fresh", fresh, "--against",
                               other_unit]) == 2
    assert bench_regress.main(["--fresh", fresh, "--against", fresh,
                               "--threshold", "2.0"]) == 2
    capsys.readouterr()


def test_real_repo_reference_resolves():
    """The repo's own BENCH_r*.json trail is a usable reference."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ref = bench_regress.latest_reference(root)
    assert ref is not None and ref.endswith("BENCH_r06.json")
    value, unit, metric = bench_regress.load_measurement(ref)
    assert unit == "s" and value > 0
    # the round-13 cold/warm sub-rows are present and well-formed
    payload = bench_regress.load_payload(ref)
    for row in ("cold_start_ms", "warm_start_ms"):
        v, u, _ = bench_regress.measurement(payload, ref, row=row)
        assert u == "ms" and v > 0
    # the round-15 hermitian-symmetry sub-rows: trimmed wire at half
    # the recorded untrimmed C2C bytes, both fused r2c seams active
    v, u, m = bench_regress.measurement(payload, ref,
                                        row="wire_bytes_r2c")
    assert u == "bytes" and 0 < v <= 0.55 * 92164352
    v, u, _ = bench_regress.measurement(payload, ref, row="fused_r2c")
    assert u == "seams" and v == 2
    # the round-16 fused x overlap composition row: both distributed
    # fused directions active under the K=2 pipeline
    v, u, _ = bench_regress.measurement(payload, ref, row="fused_dist")
    assert u == "directions" and v == 2


def _write_with_fused(path, value, fused_value, unit="s", wrap=False):
    payload = {"metric": "m", "value": value, "unit": unit,
               "fused": {"metric": "m fused", "value": fused_value,
                         "unit": unit}}
    if wrap:
        payload = {"n": 1, "parsed": payload}
    path.write_text(json.dumps(payload))
    return str(path)


def test_fused_row_compared_when_both_sides_carry_it(tmp_path, capsys):
    """A 'fused' sub-row in both files is compared with the same rules
    and regresses the exit code on its own (BENCH_r06.json onward)."""
    ref = _write_with_fused(tmp_path / "BENCH_r06.json", 0.0106, 0.008,
                            wrap=True)
    ok = _write_with_fused(tmp_path / "ok.json", 0.0107, 0.0081)
    assert bench_regress.main(["--fresh", ok, "--against", ref]) == 0
    lines = [json.loads(li) for li in
             capsys.readouterr().out.splitlines()]
    assert [v["row"] for v in lines] == ["primary", "fused"]
    assert all(v["ok"] for v in lines)

    # primary fine, fused 2x slower -> regression from the fused row
    bad = _write_with_fused(tmp_path / "bad.json", 0.0107, 0.016)
    assert bench_regress.main(["--fresh", bad, "--against", ref]) == 1
    lines = [json.loads(li) for li in
             capsys.readouterr().out.splitlines()]
    by_row = {v["row"]: v for v in lines}
    assert by_row["primary"]["ok"]
    assert not by_row["fused"]["ok"]


def test_fused_row_one_sided_is_skipped(tmp_path, capsys):
    """A fused row on only one side (older reference predates it, or a
    fresh run without --fused) is reported and never fails."""
    ref_plain = _write(tmp_path / "ref.json", 0.0106)
    fresh_fused = _write_with_fused(tmp_path / "fresh.json", 0.0107,
                                    0.008)
    assert bench_regress.main(["--fresh", fresh_fused,
                               "--against", ref_plain]) == 0
    lines = [json.loads(li) for li in
             capsys.readouterr().out.splitlines()]
    assert lines[-1]["verdict"] == "row-no-reference"
    assert lines[-1]["row"] == "fused"
    # and the mirror: reference has it, fresh does not
    ref_fused = _write_with_fused(tmp_path / "r.json", 0.0106, 0.008)
    fresh_plain = _write(tmp_path / "f.json", 0.0107)
    assert bench_regress.main(["--fresh", fresh_plain,
                               "--against", ref_fused]) == 0
    capsys.readouterr()


def _write_symmetry(path, value, wire, seams, wrap=False):
    payload = {"metric": "m", "value": value, "unit": "s",
               "wire_bytes_r2c": {"metric": "w", "value": wire,
                                  "unit": "bytes"},
               "fused_r2c": {"metric": "f", "value": seams,
                             "unit": "seams"}}
    if wrap:
        payload = {"n": 1, "parsed": payload}
    path.write_text(json.dumps(payload))
    return str(path)


def test_wire_bytes_row_gates_the_halving(tmp_path, capsys):
    """wire_bytes_r2c is bytes = lower-is-better: the deterministic
    trimmed accounting passes at equality and fails if the exchange
    re-inflates toward the untrimmed byte count."""
    ref = _write_symmetry(tmp_path / "BENCH_r06.json", 0.0106,
                          46084864, 2, wrap=True)
    same = _write_symmetry(tmp_path / "same.json", 0.0106, 46084864, 2)
    assert bench_regress.main(["--fresh", same, "--against", ref]) == 0
    lines = [json.loads(li) for li in
             capsys.readouterr().out.splitlines()]
    by_row = {v["row"]: v for v in lines}
    assert by_row["wire_bytes_r2c"]["direction"] == "lower-is-better"
    assert by_row["fused_r2c"]["direction"] == "higher-is-better"

    untrimmed = _write_symmetry(tmp_path / "bad.json", 0.0106,
                                92164352, 2)
    assert bench_regress.main(["--fresh", untrimmed,
                               "--against", ref]) == 1
    capsys.readouterr()


def test_fused_r2c_row_gates_the_decline(tmp_path, capsys):
    """A fused r2c seam dropping back to declined (2 -> 1 active) trips
    the rate-direction comparison on its own."""
    ref = _write_symmetry(tmp_path / "ref.json", 0.0106, 46084864, 2)
    declined = _write_symmetry(tmp_path / "bad.json", 0.0106,
                               46084864, 1)
    assert bench_regress.main(["--fresh", declined,
                               "--against", ref]) == 1
    by_row = {v["row"]: v for v in
              (json.loads(li) for li in
               capsys.readouterr().out.splitlines())}
    assert not by_row["fused_r2c"]["ok"]


def _write_fused_dist(path, value, directions, wrap=False):
    payload = {"metric": "m", "value": value, "unit": "s",
               "fused_dist": {"metric": "d", "value": directions,
                              "unit": "directions"}}
    if wrap:
        payload = {"n": 1, "parsed": payload}
    path.write_text(json.dumps(payload))
    return str(path)


def test_fused_dist_row_gates_the_composition(tmp_path, capsys):
    """The fused x overlap composition row: a distributed fused
    direction dropping back to declined (2 -> 1) trips the
    rate-direction comparison, and a one-sided row (reference predates
    the composition) stays a skip."""
    ref = _write_fused_dist(tmp_path / "ref.json", 0.0106, 2)
    both = _write_fused_dist(tmp_path / "ok.json", 0.0106, 2)
    assert bench_regress.main(["--fresh", both, "--against", ref]) == 0
    by_row = {v["row"]: v for v in
              (json.loads(li) for li in
               capsys.readouterr().out.splitlines())}
    assert by_row["fused_dist"]["direction"] == "higher-is-better"

    declined = _write_fused_dist(tmp_path / "bad.json", 0.0106, 1)
    assert bench_regress.main(["--fresh", declined,
                               "--against", ref]) == 1
    by_row = {v["row"]: v for v in
              (json.loads(li) for li in
               capsys.readouterr().out.splitlines())}
    assert not by_row["fused_dist"]["ok"]

    # one-sided-skip semantics preserved: an older reference without
    # the row never fails the fresh run that carries it
    old_ref = _write(tmp_path / "old.json", 0.0106)
    assert bench_regress.main(["--fresh", both,
                               "--against", old_ref]) == 0
    lines = [json.loads(li) for li in
             capsys.readouterr().out.splitlines()]
    assert lines[-1] == {"ok": True, "verdict": "row-no-reference",
                         "row": "fused_dist", "missing": "reference"}
