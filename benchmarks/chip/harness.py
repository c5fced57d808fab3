"""The benchmark harness: resolves a cell by name, checks the chip, runs the
cell's driver through set-up, the measured window and the outputs check,
and assembles the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

* ``configs/<config>.json`` names its driver (``"driver": "engine"``);
* ``drivers/<driver>.py`` is the code that drives one kind of system;
* ``traffic/<traffic>.json`` holds the mix's parameters;
* ``metrics/<metric>.py`` is the reader of one per-layer metric: its
  ``read(ctx)`` returns a number, or ``None`` where it finds nothing.

A later cell adds files and a ``workloads`` entry; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPAN_WINDOW = "bench.window"


class NoChip(RuntimeError):
    """JAX found no accelerator of a known kind, or too few of them."""


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    limits: Dict[str, float]


@dataclasses.dataclass
class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit       # NaN fails


@dataclasses.dataclass
class Window:
    """What a driver's measured window returns."""
    metrics: Dict[str, float]          # end-to-end metrics, by name
    attempted: int
    failed: int
    seconds: float                     # length of the window
    context: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _load_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(bench_path: Path, workload: str) -> Cell:
    """The cell ``workload`` of the benchmark file at ``bench_path``."""
    bench = _load_json(bench_path)
    root = bench_path.parent
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = configs[w["config"]]
    traffic_path = HERE / "traffic" / f"{w['traffic']}.json"
    return Cell(
        name=workload, chips=int(w["chips"]), config_name=cfg["name"],
        config=_load_json(root / cfg["file"]), traffic_name=w["traffic"],
        traffic=_load_json(traffic_path),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        limits=_load_json(HERE / "limits" / f"{workload}.json"))


def load_module(path: Path):
    """Import the Python file at ``path`` once (its name may hold dots)."""
    name = "bench_" + "_".join(path.relative_to(HERE).with_suffix("").parts)
    name = name.replace(".", "_").replace("-", "_")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


def load_peaks(path: Path = HERE / "peaks.json") -> Dict[str, Dict]:
    return {k: v for k, v in _load_json(path).items()
            if not k.startswith("_")}


def require_chips(n: int, peaks: Dict[str, Dict]):
    """The devices this run uses; raises :class:`NoChip` unless JAX sees
    at least ``n`` TPUs of a kind the peaks table knows."""
    import jax
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX platform is {d.platform!r}, not a TPU")
    if d.device_kind not in peaks:
        raise NoChip(f"device kind {d.device_kind!r} is not in peaks.json")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


class CompileCounter:
    """Counts backend compilations (``jax.monitoring`` events) while
    ``active``; a compile inside the measured window is a fault of the
    benchmark's warm-up."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event == self.EVENT:
            self.count += 1


def open_cell(workload: str, prog: str):
    """``(cell, devices, peaks)`` of a command's run, with the compile
    cache on; ``None``, after saying why on standard error, when JAX finds
    no chip the cell can run on."""
    cell = load_cell(ROOT / "BENCHMARK.json", workload)
    peaks = load_peaks()
    try:
        devices = require_chips(cell.chips, peaks)
    except NoChip as e:
        print(f"{prog}: {e}; nothing was run", file=sys.stderr)
        return None
    enable_compile_cache()
    return cell, devices, peaks


def enable_compile_cache() -> str:
    """The program's persistent compile cache (its own fixed directory in
    the checkout, or ``$JAX_COMPILATION_CACHE_DIR``), with every program
    kept, however fast it compiled, so that only a cell's first run in a
    checkout compiles."""
    import jax

    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_record(devices, trace_red=None) -> Dict[str, Any]:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices),
           "memory_peak_bytes": max((p for p in peaks if p is not None),
                                    default=None)}
    if trace_red is not None:
        rec["busy_s"] = trace_red.busy_s
        rec["window_s"] = trace_red.window_s
    return rec


def read_per_layer(cell: Cell, ctx: Dict[str, Any]) -> Dict[str, Dict]:
    """Each per-layer metric's reader applied to ``ctx``; a reader that
    finds nothing leaves its metric out."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(HERE / "metrics" / f"{m['name']}.py")
        value = reader.read(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def _finite(x: float) -> Optional[float]:
    """``x``, or ``None`` where JSON has no number for it."""
    return x if math.isfinite(x) else None


def start(cell: Cell, seed: int, seconds: float, devices, *,
          log: Callable[[str], None]):
    """``(driver, state)``: the cell's driver module and its set-up for
    ``seed``, ready for a window of ``seconds``."""
    driver = load_module(HERE / "drivers" / f"{cell.config['driver']}.py")
    return driver, driver.setup(cell, seed, seconds, devices, log=log)


def finish(driver, state, window: Window) -> None:
    """What a driver does once the window has closed and before its
    answers are checked (serving: wait for the first token of every
    request due in the window)."""
    if hasattr(driver, "finish"):
        driver.finish(state, window)


def judge(driver, state, window: Window, *, log: Callable[[str], None],
          control: bool = False):
    """``(checks, correct)`` of the window's answers; with ``control``,
    of the control's answers put in the program's place first (the plain
    reference in the precision below the one the configuration states),
    which have to come out not correct."""
    if control:
        driver.control(state)
    checks = driver.check(state, window, log=log)
    correct = bool(checks) and all(c.ok for c in checks) \
        and window.failed == 0
    for c in checks:
        log(f"[check{' control' if control else ''}] {c.name} = "
            f"{c.value!r} (limit {c.limit!r}) {'ok' if c.ok else 'FAIL'}")
    return checks, correct


def _stderr(s: str) -> None:
    print(s, file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t0: float, devices, peaks: Dict[str, Dict],
             log: Callable[[str], None] = _stderr) -> Dict[str, Any]:
    """Set-up, window and check of one run; returns the result object
    (without printing it)."""
    import jax

    counter = CompileCounter()
    driver, state = start(cell, seed, seconds, devices, log=log)
    setup_s = time.perf_counter() - t0
    length = float(seconds)
    if trace:
        import tempfile
        length = min(length, float(cell.traffic["trace_seconds"]))
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    counter.active = True
    try:
        with jax.profiler.TraceAnnotation(SPAN_WINDOW):
            window = driver.window(state, length)
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
    log(f"[bench] compiles inside the window: {counter.count}")
    finish(driver, state, window)
    red = None
    if trace:
        import shutil

        import reduce_trace
        try:
            red = reduce_trace.reduce_dir(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    device = device_record(devices, red)
    checks, correct = judge(driver, state, window, log=log)
    if trace:
        ctx = dict(window.context, trace=red, window=window, peaks=peaks[
            devices[0].device_kind], cell=cell)
        if hasattr(driver, "trace_context"):
            ctx.update(driver.trace_context(state, window))
        metrics = read_per_layer(cell, ctx)
    else:
        values = dict(window.metrics, setup_s=setup_s)
        metrics = {m["name"]: {"value": float(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result = {"correct": correct, "attempted": window.attempted,
              "failed": window.failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = red.breakdown()
    result["checks"] = {c.name: {"value": _finite(c.value),
                                 "limit": c.limit} for c in checks}
    return result
