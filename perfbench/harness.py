"""One run of one cell: set up, measure a window, check, report.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration file it names, the traffic file
``perfbench/traffic/<traffic>.json``, the driver that traffic names
(``perfbench/drivers/<driver>.py``), and one reader
``perfbench/metrics/<metric>.py`` per per-layer metric. A later PR adds a
cell or a metric by adding such files and entries.
"""

from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import List, Optional, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    """Progress lines go to standard error; standard output carries only
    the result line."""
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``BENCHMARK.json``'s ``workloads`` and what it names."""

    def __init__(self, root: str, workload: str):
        bench = load_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json; "
                           f"have {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        cfg = {c["name"]: c for c in bench["configs"]}[self.entry["config"]]
        self.config = load_json(os.path.join(root, cfg["file"]))
        self.traffic = load_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if workload in m.get("workloads", [workload])]
        self.per_layer = [m for m in bench["per_layer"]
                          if workload in m.get("workloads", [workload])]


class CompileCounter:
    """Counts XLA backend compiles, on any thread, while ``armed``."""

    _installed: List["CompileCounter"] = []

    def __init__(self):
        self.count = 0
        self.armed = False
        self._lock = threading.Lock()
        if not CompileCounter._installed:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._dispatch)
        CompileCounter._installed.append(self)

    @staticmethod
    def _dispatch(event: str, duration: float, **_kw) -> None:
        if event != COMPILE_EVENT:
            return
        for c in CompileCounter._installed:
            if c.armed:
                with c._lock:
                    c.count += 1

    def close(self) -> None:
        CompileCounter._installed.remove(self)


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's trace (free when no trace runs)."""
    import jax

    with jax.profiler.TraceAnnotation(f"perfbench.{name}"):
        yield


def device_info(chips: int, require_tpu: bool) -> Tuple[dict, object]:
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}, devs[:chips]


def peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def load_reader(metric: str):
    path = os.path.join(HERE, "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(HERE, "peaks.json"))
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, t_start: Optional[float] = None,
             require_tpu: bool = True, config: Optional[dict] = None,
             traffic: Optional[dict] = None) -> dict:
    """Run ``workload`` once and return the result object. ``config`` and
    ``traffic`` replace the cell's files (the tests run cells at small
    sizes on the CPU with ``require_tpu=False``)."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = Cell(root, workload)
    if config is not None:
        cell.config = config
    if traffic is not None:
        cell.traffic = traffic
    device, devices = device_info(cell.chips, require_tpu)
    say(f"perfbench: cell {workload} seed {seed} seconds {seconds} "
        f"trace {int(trace)} device {device}")
    peaks = peaks_for(device["kind"]) if trace else None

    from perfbench import generators

    driver_mod = importlib.import_module(
        f"perfbench.drivers.{cell.traffic['driver']}")
    counter = CompileCounter()
    try:
        with annotate("generate"):
            t = time.perf_counter()
            graph = generators.generate(cell.config, seed)
            say(f"perfbench: generated nv={graph.nv} ne={graph.ne} in "
                f"{time.perf_counter() - t:.3f} s")
        driver = driver_mod.Driver(cell.config, cell.traffic, graph, seed)
        try:
            driver.setup()
            setup_s = time.perf_counter() - t_start
            say(f"perfbench: setup_s {setup_s}")
            trace_dir = os.path.join(root, ".bench_cache", "perfbench",
                                     "trace")
            if trace:
                import jax

                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0   # host spans, not every call
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            counter.armed = True
            try:
                with annotate("window"):
                    e2e = driver.window(seconds)
            finally:
                counter.armed = False
                if trace:
                    jax.profiler.stop_trace()
            say(f"perfbench: compiles_in_window {counter.count}")
            memory_peak = peak_bytes(devices)
            reduced = None
            if trace:
                from perfbench import trace_reduce

                t = time.perf_counter()
                reduced = trace_reduce.reduce(
                    trace_reduce.load_xplane(trace_dir))
                shutil.rmtree(trace_dir, ignore_errors=True)
                say(f"perfbench: trace busy_s {reduced['busy_s']} window_s "
                    f"{reduced['window_s']} read in "
                    f"{time.perf_counter() - t:.3f} s")
            driver.release()
            t = time.perf_counter()
            checks = driver.check()
            say(f"perfbench: reference check in "
                f"{time.perf_counter() - t:.3f} s")
        finally:
            driver.close()
    finally:
        counter.close()

    e2e["setup_s"] = setup_s
    if trace:
        ctx = {"layer": driver.layer, "work": driver.work, "trace": reduced,
               "peaks": peaks}
        metrics = {}
        for m in cell.per_layer:
            value = load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device["memory_peak_bytes"] = memory_peak
    result = {
        "correct": all(v <= lim for v, lim in checks.values()),
        "attempted": driver.attempted,
        "failed": driver.failed,
        "metrics": metrics,
        "device": device,
    }
    if trace:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    return result


def print_result(result: dict) -> None:
    """The checks as the last lines of standard error, then the result as
    the last line of standard output."""
    for name, c in result["checks"].items():
        say(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
