#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this process holds.

    python3 benchmarks/chip/run.py --workload gene964.fit --seed 7 \
        --seconds 30 --trace 0

The cell is the ``workloads`` entry of ``BENCHMARK.json`` with that name.
Everything else is found by name under this directory:

* ``configs/<file>``: the deployment (sizes, data generator, fit
  settings), as the cell's configuration entry names it;
* ``traffic/<traffic>.json``: the traffic mix, whose ``job`` field names
  the module ``jobs/<job>.py`` that drives it;
* ``limits/<cell>.json``: the limit of each number the check compares;
* ``metrics/<metric>.py``: one reader per per-layer metric.

A run sets up (data from ``--seed`` on the device, the program's compiled
programs from the persistent cache, one warm-up graph), then lets the job
drive its window of ``--seconds`` seconds. With ``--trace 0`` it reports
the cell's end-to-end metrics, ``setup_s`` and what the job reports for
its window (a metric the cell lists and the job leaves out is an error);
with ``--trace 1`` it records a
device trace of a window of at most the traffic's ``trace_graphs`` graphs
and reports the per-layer metrics read from it.
Either way the graphs made in the window are then checked against the
plain reference (``refcheck.py``), and the last line of standard output
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), with ``checks`` last. A
process that finds no TPU, or fewer chips than the cell asks for, exits
with code 3 before printing a result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))

# Tuned plans may come only from the committed table, and the program's
# own telemetry and profiling stay off (the program reads these when it is
# first imported).
for _var in ("REPRO_TUNE_CACHE", "REPRO_OBS", "REPRO_OBS_PROFILE"):
    os.environ.pop(_var, None)
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

EXIT_NO_DEVICE = 3
EXIT_BAD_CELL = 2


class NoDevice(RuntimeError):
    pass


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(workload: str):
    """(bench, cell, config, traffic, limits) for the named cell."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    conf_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf_entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    with open(os.path.join(HERE, "limits", workload + ".json")) as f:
        limits = json.load(f)
    return bench, cell, config, traffic, limits


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def check_device(chips: int, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoDevice(f"{len(devices)} devices, the cell needs {chips}")
    return devices[:chips]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` points), for every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads (JAX's own
    monitoring events)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_loads = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration_secs, **_):
        if event == self.COMPILE:
            self.compiles += 1
            self.compile_s += duration_secs
        elif event.startswith("/jax/compilation_cache/cache_retrieval"):
            self.cache_loads += 1

    def snapshot(self):
        return (self.compiles, self.cache_loads)


def memory_peak(devices) -> int:
    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in devices
    )


def read_per_layer(bench, cell_name, reduced, work, log=sys.stderr):
    """Each per-layer metric of this cell from its own reader. A reader
    that finds nothing to read (a kernel taken off the path) returns None:
    the metric is left out of the result, and named on ``log``."""
    out = {}
    for metric in bench["per_layer"]:
        if not applies(metric, cell_name):
            continue
        path = os.path.join(HERE, "metrics", metric["name"] + ".py")
        reader = load_module(path, "metric_" + metric["name"].replace(".", "_"))
        value = reader.read(reduced, work)
        if value is None:
            print(f"per-layer {metric['name']}: its reader found nothing to "
                  f"read in this trace; left out", file=log, flush=True)
        else:
            out[metric["name"]] = {"value": value, "unit": metric["unit"]}
    return out


def end_to_end(bench, cell_name, values):
    """The cell's end-to-end metrics from ``values``; a metric that the
    cell lists and the job did not report is an error, not a gap."""
    out = {}
    for metric in bench["end_to_end"]:
        if not applies(metric, cell_name):
            continue
        if metric["name"] not in values:
            raise KeyError(f"the job reported no {metric['name']!r} for "
                           f"{cell_name}; it reported {sorted(values)}")
        out[metric["name"]] = {"value": values[metric["name"]],
                               "unit": metric["unit"]}
    return out


def finite(v: float) -> float:
    """A number JSON can carry: a reading that is not finite prints as
    the largest double (it fails every limit either way)."""
    return v if v == v and abs(v) != float("inf") else 1.7976931348623157e308


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        require_tpu: bool = True, loaded=None, log=sys.stderr):
    """One run of one cell; returns the result object (not printed).
    ``loaded`` stands in for :func:`load_cell`'s files (tests run cells
    at small sizes through it)."""
    bench, cell, config, traffic, limits = loaded or load_cell(workload)
    devices = check_device(cell["chips"], require_tpu)
    import jax

    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    import trace_reduce

    job_mod = load_module(
        os.path.join(HERE, "jobs", traffic["job"] + ".py"),
        "job_" + traffic["job"])
    job = job_mod.Job(config, traffic, seed, limits)
    job.setup()
    setup_s = time.perf_counter() - T_START
    print(f"setup: {setup_s:.3f} s, {counter.compiles} compiles "
          f"({counter.compile_s:.3f} s), {counter.cache_loads} cache loads, "
          f"cache {cache_dir}", file=log, flush=True)
    for k, v in job.work().items():
        print(f"work: {k}={v}", file=log, flush=True)

    before = counter.snapshot()
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(trace_dir, profiler_options=opts):
            graphs, failed, elapsed = job.drive(
                seconds, traffic.get("trace_graphs"),
                span=jax.profiler.TraceAnnotation)
    else:
        graphs, failed, elapsed = job.drive(seconds)
    compiles, loads = (a - b for a, b in zip(counter.snapshot(), before))
    print(f"window: {graphs} graphs, {failed} failed, {elapsed:.6f} s, "
          f"{compiles} compiles, {loads} cache loads inside the window",
          file=log, flush=True)
    peak = memory_peak(devices)

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": False, "attempted": graphs, "failed": failed}
    breakdown = None
    if trace:
        reduced = trace_reduce.reduce_dir(trace_dir, devices=len(devices))
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced["graphs"] = graphs
        metrics = read_per_layer(bench, workload, reduced, job.work(), log)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"][:10],
                     "idle_gaps": reduced["idle_gaps"][:10]}
        print(f"trace: {json.dumps(trace_reduce.brief(reduced))}",
              file=log, flush=True)
    else:
        metrics = end_to_end(bench, workload, {
            "setup_s": setup_s, **job.end_to_end(graphs, elapsed)})

    job.release()
    gc.collect()
    t_check = time.perf_counter()
    table, correct = job.check()
    print(f"check: {time.perf_counter() - t_check:.3f} s", file=log,
          flush=True)
    result["correct"] = bool(correct and failed == 0 and graphs > 0)
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    compared = {n for n, _, _ in table}
    for n, v in sorted(getattr(job, "readings", {}).items()):
        if n not in compared:
            print(f"reading {n}: {v!r} (not compared)", file=log, flush=True)
    table = [(n, finite(v), lim) for n, v, lim in table]
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in table}
    for n, v, lim in table:
        print(f"check {n}: {v!r} (limit {lim!r})", file=log, flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        loaded = load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_BAD_CELL
    try:
        result = run(args.workload, args.seed, args.seconds, bool(args.trace),
                     loaded=loaded)
    except NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return EXIT_NO_DEVICE
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
