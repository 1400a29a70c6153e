"""Run one cell of BENCHMARK.json once and print the contract's last line.

Everything is found by name: the cell in BENCHMARK.json, its configuration
file, its traffic file (which names its driver under benchmark/drivers/), and
one reader per metric under benchmark/metrics/<metric>.py. A later PR adds a
cell, a mix or a metric by adding files and entries; no file here changes.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"  # the fixed path enable_compile_cache() uses


class CellError(Exception):
    """The cell cannot run here (spec, device or chip count): exit 1, no
    result line."""


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: Path = ROOT) -> SimpleNamespace:
    """The cell `workload` with its configuration, traffic, driver and the
    metrics it reports, all loaded from files named in the spec."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    config = _load_json(root / configs[cell["config"]]["file"])
    traffic = _load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    driver = _load_module(
        HERE / "drivers" / f"{traffic['driver']}.py",
        f"benchmark_driver_{traffic['driver']}",
    )

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    e2e = [m for m in spec["end_to_end"] if applies(m)]
    reported = {m["name"] for m in e2e}
    layer = [
        m for m in spec["per_layer"]
        if applies(m) and m["moves"] in reported
    ]
    return SimpleNamespace(
        name=workload, cell=cell, config=config, traffic=traffic,
        driver=driver, end_to_end=e2e, per_layer=layer,
    )


def reader(name: str):
    """benchmark/metrics/<name>.py: `read(run) -> float | None`."""
    return _load_module(HERE / "metrics" / f"{name}.py",
                        "benchmark_metric_" + name.replace(".", "_")).read


def read_metrics(metrics, run) -> dict:
    out = {}
    for m in metrics:
        value = reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def check_devices(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise CellError(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise CellError(f"cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    """The persistent compile cache at <checkout>/.jax_cache, whatever the
    environment says, caching every program however quick to compile."""
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


class CompileCounter:
    """Counts backend compilations and their seconds, and persistent-cache
    hits and misses (a miss compiles)."""

    def __init__(self):
        import jax

        self.count = self.hits = self.misses = 0
        self.seconds = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"compiles": self.count, "compile_s": self.seconds,
                "cache_hits": self.hits, "cache_misses": self.misses}


class Tracing:
    """The `--trace 1` window: program telemetry and xprof spans on for the
    whole window, the profiler on for its first `trace_seconds`. The driver
    calls `tick(elapsed_s, work)` once per host iteration with the work
    (host ticks, frames) that iteration is about to do."""

    def __init__(self, enabled: bool, trace_seconds: float):
        self.enabled = enabled
        self.trace_seconds = trace_seconds
        self.dir = None
        self.traced = {}  # work counted inside the profiled span
        self.profiling = False
        self.t_start = self.t_stop = None

    def span(self, name: str):
        """The benchmark's own host span around a call into a layer: a
        TraceAnnotation on the profiler's clock in --trace 1 runs."""
        if not self.enabled:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def begin(self) -> None:
        if not self.enabled:
            return
        from ggrs_tpu.obs import GLOBAL_TELEMETRY
        from ggrs_tpu.utils.tracing import GLOBAL_TRACER

        GLOBAL_TELEMETRY.registry.reset()
        GLOBAL_TELEMETRY.enabled = True
        GLOBAL_TRACER.stats.clear()
        GLOBAL_TRACER.enabled = True
        GLOBAL_TRACER.xprof = True

    def tick(self, elapsed_s: float, **work) -> None:
        if not self.enabled:
            return
        if self.t_start is None:
            import jax

            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.profiling = True
            self.t_start = time.perf_counter()
        if self.profiling and elapsed_s >= self.trace_seconds:
            self.stop()
        if self.profiling:
            for k, v in work.items():
                self.traced[k] = self.traced.get(k, 0) + v

    def stop(self, sync=None) -> None:
        if self.profiling:
            import jax

            if sync is not None:
                sync()
            self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            self.profiling = False

    def end(self) -> dict:
        """Turn telemetry off; return the counters of the window."""
        if not self.enabled:
            return {}
        from ggrs_tpu.obs import GLOBAL_TELEMETRY
        from ggrs_tpu.utils.tracing import GLOBAL_TRACER

        counters = GLOBAL_TELEMETRY.registry.snapshot()
        GLOBAL_TELEMETRY.enabled = False
        GLOBAL_TRACER.enabled = False
        return counters

    def reduce(self, devices):
        if self.dir is None:
            return None
        from benchmark import trace_reduce

        try:
            return trace_reduce.reduce_dir(
                self.dir, [d.id for d in devices],
                window_s=self.t_stop - self.t_start,
            )
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run_cell(c: SimpleNamespace, *, seed: int, seconds: float, trace: bool,
             t_process: float, control: bool = False, devices=None,
             sizes=None, log=sys.stderr):
    """Set up, measure and check one cell; returns the result object.
    `devices=None` looks for the chips (the benchmark's own runs); tests
    pass the CPU devices and `sizes` to shrink the cell."""
    if devices is None:
        devices = check_devices(c.cell["chips"])
    enable_cache()
    counter = CompileCounter()
    cell = c.driver.Cell(c.config, c.traffic, seed=seed, devices=devices,
                         sizes=sizes or {})
    t_setup = time.perf_counter()
    cell.setup()
    setup_s = time.perf_counter() - t_process
    in_setup = counter.snapshot()
    print(json.dumps({"setup_s": setup_s,
                      "before_cell_setup_s": t_setup - t_process,
                      "parts": getattr(cell, "setup_parts", None),
                      **in_setup}), flush=True)

    tracing = Tracing(trace, c.traffic.get("trace_seconds", 3.0))
    tracing.begin()
    raw = cell.window(seconds, tracing)
    window_compiles = counter.count - in_setup["compiles"]
    counters = tracing.end()
    reduced = tracing.reduce(devices)
    peak = memory_peak(devices)
    print(json.dumps({"window_compiles": window_compiles,
                      "window_s": raw["window_s"]}), flush=True)

    t_check = time.perf_counter()
    attempted, failed, compared = cell.check(control=control)
    print(json.dumps({"check_s": time.perf_counter() - t_check,
                      "checked": getattr(cell, "checked", None)}), flush=True)
    run = SimpleNamespace(raw=raw, counters=counters, trace=reduced,
                          traced=tracing.traced, setup_s=setup_s,
                          config=c.config, traffic=c.traffic,
                          device_kind=devices[0].device_kind,
                          chips=len(devices))
    metrics = read_metrics(c.per_layer if trace else c.end_to_end, run)
    correct = failed == 0 and all(v <= lim for v, lim in compared.values())
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": peak,
        },
    }
    if trace and reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"][:10],
            "idle_gaps": reduced["idle_gaps"][:10],
        }
    result["compared"] = {
        k: {"value": v, "limit": lim} for k, (v, lim) in compared.items()
    }
    print(f"correct {str(correct).lower()} failed {failed} of {attempted}",
          file=log)
    for k, (v, lim) in compared.items():
        print(f"compared {k} {v} limit {lim}", file=log)
    log.flush()
    return result


def main(argv=None, t_process=None) -> int:
    import argparse

    t_process = t_process if t_process is not None else time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="1: judge the control (the reference at int16) in "
                         "the program's place; never used by the driver")
    args = ap.parse_args(argv)
    try:
        c = resolve(load_spec(), args.workload)
        result = run_cell(c, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_process=t_process,
                          control=bool(args.control))
    except CellError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0
