"""Run one benchmark cell once and print its result line.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell (a configuration under a traffic
mix) is looked up in ``BENCHMARK.json``; its parts are found by name:
``configs/<config>.json``, ``traffic/<mix>.json``, ``limits/<cell>.json``
and ``metrics/<metric>.py``, and the modules the data files name: the
stick set (``sticks/<kind>.py``), the system under test
(``systems/<system>.py``) and the driver of the traffic
(``drivers/<driver>.py``). Set-up (stick set, system built, inputs made on
the device from the seed, every executable compiled or loaded) is timed
from process start; then the driver runs steps for ``--seconds`` and
every transform completed in that window is counted. With ``--trace 1``
the window runs under the profiler and the per-layer metrics are read
from its trace; otherwise the end-to-end metrics are reported. After the
window the driver's outputs are compared with the plain reference.

Standard output ends with one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (with ``--trace 1`` also
``breakdown``) and, last, ``checks``: each number compared beside its
limit, which standard error repeats on its last lines. Without a TPU, or
with fewer chips than the cell asks for, it exits with code 2 and prints
no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"


class CannotRun(RuntimeError):
    """The cell cannot run here: no TPU, fewer chips than it needs, or no
    program beside the benchmark."""


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def load_spec(workload: str, root: Path = ROOT) -> dict:
    """Everything the data files say about one cell."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    limits = json.loads((HERE / "limits" / f"{workload}.json").read_text())
    return {"bench": bench, "cell": cell, "config": config, "mix": mix,
            "limits": limits}


def metrics_for(spec: dict, trace: bool) -> list:
    """The metric entries this cell reports in this mode."""
    name = spec["cell"]["name"]
    group = spec["bench"]["per_layer" if trace else "end_to_end"]
    return [m for m in group
            if "workloads" not in m or name in m["workloads"]]


_MODULE = re.compile(r"^[a-z_][a-z0-9_]*$")


def plugin(package: str, name: str):
    """The module ``benchmark.<package>.<name>`` a data file names."""
    if not _MODULE.match(str(name)):
        raise ValueError(f"{package} module name {name!r} is not a Python "
                         f"identifier")
    return importlib.import_module(f"benchmark.{package}.{name}")


def judge(values: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}): every limit has a finite
    value at or under it."""
    import math
    checks, ok = {}, True
    for name, limit in limits.items():
        v = values.get(name)
        checks[name] = {"value": v, "limit": limit}
        if v is None or not math.isfinite(v) or v > limit:
            ok = False
    return ok, checks


def read_metric(name: str, record: dict):
    path = HERE / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"benchmark.metrics.{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module.read(record)


def require_devices(chips: int) -> dict:
    """The devices as JAX reports them; CannotRun without enough TPUs."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise CannotRun(f"JAX finds no devices: {e}") from e
    if not devices or devices[0].platform != "tpu":
        raise CannotRun(f"no TPU: JAX runs on "
                       f"{devices[0].platform if devices else 'nothing'}")
    if len(devices) < chips:
        raise CannotRun(f"the cell needs {chips} chips, JAX finds "
                       f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_program(root: Path = ROOT) -> None:
    """The program under test is the checkout's own ``spfft_tpu``."""
    try:
        import spfft_tpu
    except ImportError as e:
        raise CannotRun(f"spfft_tpu is not in this checkout: {e}") from e
    where = Path(spfft_tpu.__file__).resolve().parent.parent
    if where != root.resolve():
        raise CannotRun(f"spfft_tpu comes from {where}, not from {root}")


def configure_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    every executable cached however fast it compiled."""
    import jax
    CACHE_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts JAX's compile-path events (tracing, lowering, backend
    compiles, cache reads) while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.events = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.active and ("compil" in event or "trace" in event
                            or "lower" in event):
            self.events[event] = self.events.get(event, 0) + 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


def memory_peak_bytes() -> int:
    import jax
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             device: dict, peak: dict, sut_factory=None,
             t_start: float = T_START, log=print, parts=None) -> dict:
    """Set up, warm, measure, check; the result object. ``sut_factory``
    replaces the configuration's system (the control, or a program broken
    on purpose); ``parts`` holds the set-up seconds spent before the
    call."""
    import jax

    from benchmark import trace_reduce
    from benchmark.work import transform_work

    config, mix = spec["config"], spec["mix"]
    parts = dict(parts or {})
    parts["before_cell_s"] = time.perf_counter() - t_start
    t = time.perf_counter()
    triplets = plugin("sticks", config["sticks"]["kind"]).triplets(config)
    parts["sticks_s"] = time.perf_counter() - t
    t = time.perf_counter()
    system = (sut_factory or plugin("systems", config["system"]).System)(
        config, triplets)
    parts["system_s"] = time.perf_counter() - t
    t = time.perf_counter()
    driver = plugin("drivers", mix["driver"])
    cell = driver.Cell(config, mix, seed, system, triplets)
    parts["inputs_s"] = time.perf_counter() - t
    counter = CompileCounter()
    t = time.perf_counter()
    cell.warm()
    parts["warm_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - t_start
    log(f"setup_parts: {json.dumps(parts)}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        if trace:
            jax.profiler.start_trace(trace_dir)
        gc.collect()
        gc.freeze()
        gc.disable()
        counter.active = True
        try:
            window = cell.run_window(seconds)
        finally:
            counter.active = False
            gc.enable()
            gc.unfreeze()
            if trace:
                jax.profiler.stop_trace()
        counter.close()
        peak_bytes = memory_peak_bytes()
        log(f"per_second_transforms: {json.dumps(window['per_second'])}")
        log(f"window: {window['steps']} steps, {window['transforms']} "
            f"transforms in {window['window_s']!r} s")
        log(f"compile_events_in_window: {json.dumps(counter.events)}")

        # the reference runs once the system's device state is freed
        t_check = time.perf_counter()
        host = cell.fetch()
        transforms = window["transforms"]
        plan_build_s = getattr(system, "build_s", None)
        del cell
        system.close()
        del system
        correct, checks = judge(driver.compare(host), spec["limits"])
        log(f"check_s: {time.perf_counter() - t_check!r}")

        record = {"setup_s": setup_s, "plan_build_s": plan_build_s,
                  "window": window, "work": transform_work(config, triplets),
                  "peak": peak}
        out_device = dict(device, memory_peak_bytes=peak_bytes)
        breakdown = None
        files = sorted(Path(trace_dir).rglob("*.xplane.pb")) if trace else []
        if files:
            reduced = trace_reduce.reduce(trace_reduce.load(files[-1]))
            reduced["transforms"] = transforms
            record["trace"] = reduced
            out_device["busy_s"] = reduced["busy_s"]
            out_device["window_s"] = reduced["window_s"]
            breakdown = {"device_ops": reduced["top_ops"],
                         "idle_gaps": reduced["gaps"]}
            log(f"stage_seconds: {json.dumps(reduced['stage_s'])}")
            log(f"idle_by_span: {json.dumps(reduced['idle_by_span'])}")
            log(f"programs_mapped: {reduced['programs_mapped']}")
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in metrics_for(spec, trace):
        v = read_metric(m["name"], record)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": transforms,
              "failed": 0, "metrics": metrics, "device": out_device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    spec = load_spec(args.workload)
    # before JAX is imported: the cache inside the checkout (JAX reads the
    # variable at import, and the program then sets no cache of its own),
    # and no TPU runtime logs at a fixed path outside it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    parts = {}
    try:
        require_program()
        parts["import_s"] = time.perf_counter() - T_START
        device = require_devices(int(spec["cell"]["chips"]))
        parts["devices_s"] = time.perf_counter() - T_START - parts["import_s"]
    except CannotRun as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    peaks = json.loads((HERE / "peaks.json").read_text())
    if device["kind"] not in peaks:
        print(f"benchmark: no peaks for device kind {device['kind']!r} in "
              f"peaks.json", file=sys.stderr)
        return 2
    configure_cache()
    result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                      device, peaks[device["kind"]], parts=parts)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
