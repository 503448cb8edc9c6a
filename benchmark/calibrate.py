"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 -m benchmark.calibrate --workload <name> [--seeds <n> ...] \\
        [--control-seeds <n> ...] [--seconds 2]

On the chip, in one process: the configuration's system is built once,
and for every seed of ``--seeds`` the cell's inputs are made, warmed,
driven for a short window at the cell's own load and compared with the
reference, exactly as ``benchmark.run`` does. Then the control
(``systems/control.py``: the reference one precision step down) takes
the program's place for every seed of ``--control-seeds``. Each reading is
one JSON line; the last line gives, for each number compared, the
largest reading of the program and the smallest of the control.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def readings(spec: dict, sut, triplets, seeds, seconds: float,
             who: str) -> list:
    from benchmark.run import plugin

    config, mix = spec["config"], spec["mix"]
    driver = plugin("drivers", mix["driver"])
    out = []
    for seed in seeds:
        cell = driver.Cell(config, mix, seed, sut, triplets)
        cell.warm()
        window = cell.run_window(seconds)
        host = cell.fetch()
        del cell
        values = driver.compare(host)
        row = {"who": who, "seed": seed, "values": values,
               "transforms": window["transforms"]}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    from benchmark import run

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="*", default=[])
    p.add_argument("--control-seeds", type=int, nargs="*", default=[])
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    spec = run.load_spec(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        run.require_devices(int(spec["cell"]["chips"]))
        run.require_program()
    except run.CannotRun as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    run.configure_cache()

    config = spec["config"]
    triplets = run.plugin("sticks", config["sticks"]["kind"]).triplets(config)
    program = run.plugin("systems", config["system"]).System
    control = run.plugin("systems", "control").System
    rows = []
    for who, factory, seeds in (("program", program, args.seeds),
                                ("control", control, args.control_seeds)):
        if not seeds:
            continue
        sut = factory(config, triplets)
        rows += readings(spec, sut, triplets, seeds, args.seconds, who)
        sut.close()
        del sut
    summary = {}
    for name in spec["limits"]:
        prog = [r["values"][name] for r in rows if r["who"] == "program"]
        ctl = [r["values"][name] for r in rows if r["who"] == "control"]
        summary[name] = {"program_max": max(prog, default=None),
                         "control_min": min(ctl, default=None),
                         "limit": spec["limits"][name]}
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
