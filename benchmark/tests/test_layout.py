"""Every entry of BENCHMARK.json resolves to its files, and a run without a
TPU, or without the program beside it, fails before printing a result."""

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from benchmark import run

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CHECKS = {"stages": {"bwd_rel_l2", "fwd_rel_l2"},
          "composite": {"step_rel_l2"}}


def test_top_level_and_entry_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
    names = [x["name"] for group in ("configs", "workloads", "end_to_end",
                                     "per_layer") for x in BENCH[group]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) for m in
               BENCH["end_to_end"] + BENCH["per_layer"])
    assert {"setup_s", "transforms_per_s"} <= {m["name"] for m in
                                               BENCH["end_to_end"]}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_its_files(cell):
    spec = run.load_spec(cell["name"], ROOT)
    config, mix = spec["config"], spec["mix"]
    entry = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert entry["file"].startswith("benchmark/configs/")
    assert config["name"] == cell["config"]
    assert config["source"] == entry["source"]
    assert config["reduced"] == entry["reduced"]
    assert config["fields"] % config["batch"] == 0
    assert set(spec["limits"]) == CHECKS[mix["check"]]
    assert all(0 < v < 1 for v in spec["limits"].values())
    assert (ROOT / "benchmark" / "references"
            / f"{config['reference']}.py").is_file()
    # the modules the data files name
    assert callable(run.plugin("sticks", config["sticks"]["kind"]).triplets)
    assert callable(run.plugin("systems", config["system"]).System)
    driver = run.plugin("drivers", mix["driver"])
    assert callable(driver.Cell) and callable(driver.compare)
    for trace in (False, True):
        assert run.metrics_for(spec, trace)


def test_every_metric_has_a_reader_and_moves_an_end_to_end_metric():
    cells = {w["name"] for w in BENCH["workloads"]}
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        path = ROOT / "benchmark" / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location("reader", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert callable(module.read)
        assert module.read({}) is None or m["name"] == "setup_s"
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in end_to_end


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         BENCH["workloads"][0]["name"], "--seed", "2147483653",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(stdout):
    return not any(line.lstrip().startswith("{")
                   for line in stdout.splitlines())


def test_without_a_tpu_it_fails_and_prints_no_result():
    out = _run(ROOT)
    assert out.returncode == 2, out.stderr[-2000:]
    assert _no_result(out.stdout)
    assert "no TPU" in out.stderr


def test_in_a_directory_without_the_program_it_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(tmp_path)
    assert out.returncode != 0
    assert _no_result(out.stdout)
    assert "spfft_tpu is not in this checkout" in out.stderr


def test_plugin_names_are_module_names():
    with pytest.raises(ValueError):
        run.plugin("drivers", "../run")
    with pytest.raises(ModuleNotFoundError):
        run.plugin("drivers", "no_such_driver")
