"""One benchmark run of one cell, driven by the files named in
``BENCHMARK.json``.

A cell ``<name>`` is ``bench/workloads/<name>.json``: its configuration
(``bench/configs/<config>.json``, the graph), its traffic
(``bench/traffic/<traffic>.json``, the entry and how solves arrive), the
chips and the pinned slot count that holds every seed's graph.  The
code is found by name too: a configuration's generator is
``bench/generators/<generator>.py`` (see ``bench/graphs.py``), a traffic
file's entry ``bench/entries/<entry>.py`` (see
``bench/entries/static.py``), and a per-layer metric ``<name>`` is read
by ``bench/metrics/<name>.py``.  Adding a cell, a configuration, a
traffic mix, a generator, an entry or a metric adds files.

A run: make the graph from the seed, warm the cell's one program up
with one solve (set-up ends there), run whole solves back to back until
``seconds`` have passed, read the device's peak memory, then compare
every forest of the window with the reference.
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import sys
import tempfile
import time
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


class BenchError(RuntimeError):
    """The run cannot be measured: no result is printed."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def benchmark_spec() -> dict:
    path = CHECKOUT / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no BENCHMARK.json at {CHECKOUT}")
    return json.loads(path.read_text())


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


_PLUGINS: Dict[Tuple[str, str], ModuleType] = {}


def plugin(kind: str, name: str) -> ModuleType:
    """The module ``bench/<kind>/<name>.py``: a generator, an entry or a
    metric's reader, found by the name a data file gives it."""
    if (kind, name) not in _PLUGINS:
        path = BENCH / kind / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"no {kind} module named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _PLUGINS[kind, name] = mod
    return _PLUGINS[kind, name]


def reader(metric: str) -> Callable:
    return plugin("metrics", metric).read


def cell_metrics(spec: dict, workload: str, group: str) -> List[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that
    this cell reports."""
    return [m for m in spec[group]
            if workload in m.get("workloads", [workload])]


class Clock:
    """Host spans of the timed path: durations by the host clock, and the
    same spans as ``bench.<name>`` annotations in a profiler trace."""

    def __init__(self):
        self.spans: Dict[str, List[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(f"bench.{name}"):
            t = time.perf_counter()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    time.perf_counter() - t)


class CompileCounter:
    """Counts the programs JAX compiles or loads from its persistent
    cache: every one is a program the process had not run yet."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == self.EVENT:
            self.count += 1


class TraceView:
    """What a per-layer reader reads: the traced window's host spans (by
    the host clock) and its device trace."""

    def __init__(self, clock: Clock, record: dict):
        from bench import trace
        self.clock = clock
        self.record = record
        self.win = trace.window(record)
        if self.win is None:
            raise BenchError("the trace holds no bench.solve span")
        self.solves = len(clock.spans.get("solve", []))
        self.window_s = (self.win[1] - self.win[0]) / 1e9
        self.busy_s = trace.busy_ns(record, self.win) / 1e9

    def span_mean_ms(self, name: str) -> Optional[float]:
        xs = self.clock.spans.get(name)
        return 1e3 * float(np.mean(xs)) if xs else None

    def category_ms_per_solve(self, categories) -> Optional[float]:
        from bench import trace
        ns = trace.category_ns(self.record, self.win, categories)
        return ns / 1e6 / self.solves if ns > 0 else None


def require_devices(chips: int):
    """The chips this cell needs, or BenchError: a TPU only."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"needs {chips} chips; JAX found {len(devices)}")
    peaks(devices[0].device_kind)
    return devices


def peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` of the fullest device (0 on the CPU, whose
    backend keeps no such statistic)."""
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    if peak <= 0 and devices[0].platform != "cpu":
        raise BenchError("the device reports no peak_bytes_in_use")
    return int(peak)


def place_compile_cache() -> str:
    """JAX's persistent compilation cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), for every program however
    short its compile."""
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    from repro.compile_cache import place_compile_cache as place
    where = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def run(workload: str, seed: int, seconds: float, traced: bool,
        t_start: float, devices=None, cfg_override: Optional[dict] = None,
        slots_override: Optional[int] = None,
        make_solve: Optional[Callable] = None) -> dict:
    """One run of the cell; returns the result line's object.

    ``devices`` is what ``require_devices`` returned (tests pass the CPU's
    own); ``cfg_override`` and ``slots_override`` shrink the cell for a
    rehearsal on the CPU; ``make_solve`` stands in for the entry's
    ``make`` (the control puts the reference in the solver's place).
    """
    import jax
    from bench import reference, trace

    spec = benchmark_spec()
    cell = load("workloads", workload)
    cfg = dict(load("configs", cell["config"]), **(cfg_override or {}))
    traffic = load("traffic", cell["traffic"])
    slots = slots_override or cell["slots"]
    if devices is None:
        devices = require_devices(cell["chips"])
    log(f"device {devices[0].device_kind} x{len(devices)}, compilation "
        f"cache {place_compile_cache()}")
    compiles = CompileCounter()

    t = time.perf_counter()
    u, v, w, n = plugin("generators", cfg["generator"]).generate(cfg, seed)
    log(f"graph {cell['config']} seed {seed}: n={n} m={len(u)} in "
        f"{time.perf_counter() - t:.3f} s; {slots} slots pinned; device "
        f"peak so far {peak_bytes(devices)} bytes")
    need = len(u) * traffic.get("slots_per_edge", 1) / cell["chips"]
    if need > slots:
        raise BenchError(f"{need:.0f} slots needed, {slots} pinned: the "
                         "cell's pinned slot count is too small")
    params = dict(traffic, chips=cell["chips"])
    make = make_solve or plugin("entries", traffic["entry"]).make
    solve = make(u, v, w, n, slots, params)

    solve(Clock())  # warm-up: compiles or loads the cell's programs
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s ({compiles.count} compiles)")

    clock = Clock()
    forests, overflows = [], []
    before = compiles.count
    if traced:
        tmp = tempfile.TemporaryDirectory(prefix="bench_trace_")
        jax.profiler.start_trace(tmp.name)
    t0 = time.perf_counter()
    while True:
        with clock.span("solve"):
            mask, ovf = solve(clock)
        forests.append(mask)
        overflows.append(ovf)
        if time.perf_counter() - t0 >= seconds:
            break
    t1 = time.perf_counter()
    if traced:
        jax.profiler.stop_trace()
    in_window = compiles.count - before
    if in_window:
        raise BenchError(f"{in_window} compiles inside the window")
    peak = peak_bytes(devices)
    solve_ms = 1e3 * (t1 - t0) / len(forests)
    log(f"window {t1 - t0:.3f} s, {len(forests)} solves, "
        f"{solve_ms:.3f} ms per solve, peak {peak} bytes")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": None, "attempted": len(forests), "failed": None}
    if traced:
        record = trace.extract(tmp.name)
        tmp.cleanup()
        view = TraceView(clock, record)
        if view.busy_s <= 0:
            raise BenchError("no device operation in the traced window")
        metrics = {}
        for m in cell_metrics(spec, workload, "per_layer"):
            value = reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=view.busy_s, window_s=view.window_s)
        out["breakdown"] = {
            "device_ops": trace.top_ops(record, view.win),
            "idle_gaps": trace.idle_gaps(record, view.win)}
    else:
        e2e = {"setup_s": setup_s, "solve_ms": solve_ms,
               "peak_hbm_mb": peak / 1e6}
        metrics = {}
        for m in cell_metrics(spec, workload, "end_to_end"):
            if m["name"] not in e2e:
                raise BenchError(f"no end-to-end metric {m['name']!r}")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    del solve  # the program's state goes before the reference runs

    t = time.perf_counter()
    want = reference.msf_mask(u, v, w, n)
    checks = reference.compare(forests, want, overflows)
    log(f"reference in {time.perf_counter() - t:.3f} s, "
        f"{int(want.sum())} forest edges")
    out.update(correct=reference.passed(checks),
               failed=checks["wrong_forests"]["value"], metrics=metrics,
               device=device, checks=checks)
    return out


def report(out: dict) -> None:
    """The checks as the last lines of stderr, then the result line."""
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
