#!/usr/bin/env python3
"""Run one benchmark cell on the chip JAX finds and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of `workloads` in BENCHMARK.json. Its configuration is
bench/configs/<config>.json, its traffic bench/traffic/<traffic>.json, and
the traffic names the driver, bench/drivers/<generator>.py, that sets the
cell up, runs its window and checks what the window produced. With
`--trace 1` the window runs under the profiler and each per-layer metric
listed for the cell is read by bench/metrics/<metric>.py.

The run fails, printing no result, without as many TPU chips as the cell
asks for. The last line of standard output is one JSON object; the numbers
the check compared, each beside its limit, are the last lines of standard
error and the last key of that object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    """(BENCHMARK.json, the cell, its configuration, its traffic) by name."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in bench["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    cfg = json.loads((ROOT / conf["file"]).read_text())
    traffic = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return bench, cell, cfg, traffic


def driver_class(traffic: dict):
    """The Driver of the generator the traffic file names."""
    gen = traffic["generator"]
    return load_module(BENCH / "drivers" / f"{gen}.py", f"bench_driver_{gen}").Driver


def start_jax():
    """Put the program on the path and JAX's compile cache in the checkout."""
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def metrics_for(bench: dict, cell: str, key: str) -> list:
    """The metrics of `key` ('end_to_end' or 'per_layer') this cell reports."""
    return [m for m in bench[key]
            if "workloads" not in m or cell in m["workloads"]]


def devices_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"run.py: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform!r} device(s)", file=sys.stderr)
        sys.exit(3)
    return devs[:chips]


class CompileCounter:
    """Counts programs built (compiled, or loaded from the persistent cache)
    while `on` is set, from jax.monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.on, self.n = False, 0
        monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, name, *_a, **_k):
        if self.on and name == self.EVENT:
            self.n += 1


class GcClock:
    """Collections of Python's garbage collector while `on` is set: how
    many, their seconds in all, and the longest."""

    def __init__(self):
        self.on, self.n, self.s, self.longest, self._t = False, 0, 0.0, 0.0, None
        gc.callbacks.append(self._hear)

    def _hear(self, phase, _info):
        now = time.perf_counter()
        if phase == "start":
            self._t = now
        elif self._t is not None and self.on:
            self.n += 1
            self.s += now - self._t
            self.longest = max(self.longest, now - self._t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=[0, 1])
    args = ap.parse_args(argv)

    bench, cell, cfg, traffic = load_cell(args.workload)
    devs = devices_or_exit(cell["chips"])
    start_jax()
    import jax

    from bench import counts

    peaks = counts.load_peaks(devs[0].device_kind)
    compiles = CompileCounter()
    gcs = GcClock()
    driver = driver_class(traffic)(cfg, traffic, args.seed)
    driver.seconds = args.seconds
    driver.setup()
    setup_s = time.perf_counter() - T_START

    trace_dir = ROOT / ".bench" / "trace" / args.workload
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # bench spans only, not every Python call
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    compiles.on = gcs.on = True
    driver.window(args.seconds)
    compiles.on = gcs.on = False
    if args.trace:
        jax.profiler.stop_trace()
    mem_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
    e2e = driver.end_to_end()
    attempted, failed = driver.attempted_failed()

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    metrics, breakdown = {}, None
    if args.trace:
        from bench import trace as tr

        t = tr.load(str(trace_dir))
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = t.window_s
        breakdown = tr.breakdown(t)
        ctx = {"driver": driver, "trace": t, "peaks": peaks, "cfg": cfg,
               "traffic": traffic, "compiles": compiles.n, "counts": counts}
        for m in metrics_for(bench, args.workload, "per_layer"):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name'].replace('.', '_')}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in metrics_for(bench, args.workload, "end_to_end"):
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            else:
                v, unit = e2e[m["name"]]
                metrics[m["name"]] = {"value": v, "unit": unit}
    print(f"window compiles {compiles.n} gc {gcs.n} gc_s {gcs.s:.4f} "
          f"gc_longest_s {gcs.longest:.4f}", file=sys.stderr)
    if hasattr(driver, "notes"):
        print(f"notes {driver.notes()}", file=sys.stderr)
    driver.release()
    checks = driver.check()
    correct = failed == 0 and all(v <= lim for _, v, lim in checks)
    for name, v, lim in checks:
        print(f"check {name} {v!r} limit {lim!r}", file=sys.stderr)
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
