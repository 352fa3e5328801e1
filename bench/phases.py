"""Device time by program phase, and idle gaps named by program spans.

``bench/trace.py``'s record names a device operation by its HLO text
alone.  ``extract`` here takes that record and adds the operation's
module and HLO ``op_name`` metadata, in a list per device parallel to
its ``device_ops`` rows (``op_meta``, ``[module, op_name]``), and the
program's ``msf.*`` host spans with their arguments (``spans``,
``[name, start, duration, args]``).  A TPU trace's
operation events carry no metadata: the module is the device's ``XLA
Modules`` event around the operation, and the op_name comes from the
text of the optimized module that XLA dumps (``--xla_dump_to``).  The
engines put each phase of their jitted programs under a
``jax.named_scope`` (``repro.obs.PHASES``), so the innermost phase in an
operation's ``op_name`` is the phase it belongs to.

The reductions split a device's busy time exactly: at each instant the
innermost operation running (the latest started) owns it, and that is
its phase, ``unscoped`` for an operation under no phase, or ``control``
where only a control-flow container (``while``, ...) runs.  The parts
add up to ``trace.busy_ns``.

Run on the chip, it profiles solves of a cell with the whole trace kept:

    python3 bench/phases.py --workload rgg20.sharded --seed 7 \\
        --solves 1 --out phases_out

It prints one JSON line per cell: per-phase device ms per solve, the
share of busy time under a phase, the longest idle gaps named by the
innermost span, the costliest operations with their module and op_name,
and the program's solve records (``repro.obs``).  It compiles every
program afresh (the persistent cache off), so that XLA dumps each one.
With ``--out`` it also writes the compact record of the traced solves.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import gzip
import heapq
import json
import os
import re
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    _CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_CHECKOUT, os.path.join(_CHECKOUT, "src")]

from bench import trace  # noqa: E402

PHASES = ("label_gather", "minedges", "contract", "doubling", "sort",
          "ghost_setup", "exchange", "lookup", "push")
UNSCOPED = "unscoped"
CONTROL = "control"
MODULES_LINE = "XLA Modules"
PROGRAM_PREFIX = "msf."

Interval = Tuple[float, float]
# module name -> one table per compiled module of that name:
# instruction name -> (its HLO text up to the metadata, op_name)
HloTables = Dict[str, List[Dict[str, Tuple[str, str]]]]

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?([\w.\-]+) = .*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(dump_dir: str) -> HloTables:
    """The op_name of every instruction of the optimized modules XLA
    dumped as text under ``dump_dir`` (``--xla_dump_to=<dir>
    --xla_dump_hlo_as_text``)."""
    out: HloTables = {}
    for path in sorted(glob.glob(f"{dump_dir}/**/*after_optimizations.txt",
                                 recursive=True)):
        table: Dict[str, Tuple[str, str]] = {}
        module = None
        with open(path) as f:
            for line in f:
                if module is None and line.startswith("HloModule "):
                    module = line.split()[1].rstrip(",")
                    continue
                m = _INSTR.match(line)
                if m:
                    text = m.group(1).split(", metadata=")[0]
                    op = _OP_NAME.search(line)
                    table[m.group(2)] = (text, op.group(1) if op else "")
        if module:
            out.setdefault(module, []).append(table)
    return out


def _op_name(tables: HloTables, module: str, text: str) -> str:
    """The op_name of the trace event ``text`` (an HLO instruction's
    text) in a module named ``module``; "" where it is not found or the
    modules of that name disagree."""
    instr = text.split(" = ", 1)[0].lstrip("%")
    hits = [t[instr] for t in tables.get(module, []) if instr in t]
    names = {op for _, op in hits}
    if len(names) > 1:  # same-named modules: match the instruction text
        names = {op for full, op in hits
                 if full.startswith(text) or text.startswith(full)}
    return names.pop() if len(names) == 1 else ""


def extract(trace_dir: str, tables: Optional[HloTables] = None) -> dict:
    """``trace.extract``'s record of the newest trace under
    ``trace_dir``, with ``op_meta`` (each operation's module, from the
    device's ``XLA Modules`` line, and its op_name, from ``tables``) and
    the program's ``msf.*`` spans (``spans``) besides."""
    from jax.profiler import ProfileData

    record = trace.extract(trace_dir)
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    meta: Dict[str, List[list]] = {}
    spans: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name in record["device_ops"]:
            runs = sorted((float(e.start_ns), float(e.start_ns + e.duration_ns),
                           e.name.split("(")[0])
                          for e in (lines[MODULES_LINE].events
                                    if MODULES_LINE in lines else ()))
            starts = [r[0] for r in runs]
            dm = meta[plane.name] = []
            for e in lines[trace.OPS_LINE].events:
                s = float(e.start_ns)
                i = bisect.bisect_right(starts, s) - 1
                module = runs[i][2] if i >= 0 and s <= runs[i][1] else ""
                dm.append([module, _op_name(tables or {}, module, e.name)])
        elif plane.name.startswith("/host:"):
            spans += [[e.name, float(e.start_ns), float(e.duration_ns),
                       {k: str(v) for k, v in e.stats}]
                      for line in plane.lines for e in line.events
                      if e.name.startswith(PROGRAM_PREFIX)]
    record.update(op_meta=meta, spans=spans)
    return record


def phase_of(op_name: str) -> Optional[str]:
    """The innermost phase scope in an ``op_name`` path, or None."""
    for part in reversed(op_name.split("/")):
        if part in PHASES:
            return part
    return None


def attribute(record: dict, win: Interval) -> Dict[str, float]:
    """Busy nanoseconds in the window by owner (a phase, ``unscoped`` or
    ``control``), averaged over the devices.  Each instant belongs to
    the latest-started operation running then (of two that start
    together, the one that ends first)."""
    devs = record["device_ops"]
    if not devs:
        return {}
    out: Dict[str, float] = {}
    for dev, evs in devs.items():
        meta = record["op_meta"][dev]
        owner = [CONTROL if cat in trace.CONTAINERS
                 else (phase_of(meta[i][1]) or UNSCOPED)
                 for i, (_, cat, _, _) in enumerate(evs)]
        bounds = []
        for i, (_, _, s, d) in enumerate(evs):
            s, e = max(s, win[0]), min(s + d, win[1])
            if e > s:
                bounds += [(s, 1, i), (e, 0, i)]
        bounds.sort()
        active: List[Tuple[float, float, int]] = []  # innermost first
        ended = set()
        last = None
        for t, opening, i in bounds:
            while active and active[0][2] in ended:
                heapq.heappop(active)
            if active and t > last:
                who = owner[active[0][2]]
                out[who] = out.get(who, 0.0) + (t - last)
            if opening:
                _, _, s, d = evs[i]
                heapq.heappush(active, (-s, s + d, i))
            else:
                ended.add(i)
            last = t
    return {k: v / len(devs) for k, v in out.items()}


def scoped_share(parts: Dict[str, float]) -> float:
    """Share of the busy time, control-flow containers left out, that
    operations under a phase own."""
    ops = sum(v for k, v in parts.items() if k != CONTROL)
    return sum(parts.get(p, 0.0) for p in PHASES) / ops if ops else 0.0


def named_gaps(record: dict, win: Interval, k: int = 10,
               min_ns: float = 0.0) -> List[list]:
    """The ``k`` longest idle gaps of the first device over ``min_ns``,
    each named by the innermost span (``bench.*`` of the harness or
    ``msf.*`` of the program) open at its middle, ``-`` where none is,
    with its seconds."""
    per = trace.device_intervals(record, win)
    if not per:
        return []
    gaps, t = [], win[0]
    for s, e in per[sorted(per)[0]] + [(win[1], win[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [(trace.SPAN_PREFIX + name, a, a + d)
             for name, a, d in record["host_spans"] if name != "solve"]
    spans += [(name, a, a + d) for name, a, d, _ in record["spans"]]
    rows = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        if e - s <= min_ns:
            break
        mid = (s + e) / 2
        open_ = [(b - a, name) for name, a, b in spans if a <= mid <= b]
        rows.append([min(open_)[1] if open_ else "-", (e - s) / 1e9])
    return rows


def trim(record: dict, win: Interval) -> dict:
    """The record's events that overlap ``win``."""
    out = {"device_ops": {}, "op_meta": {}}
    for dev, evs in record["device_ops"].items():
        keep = [i for i, (_, _, s, d) in enumerate(evs)
                if s < win[1] and s + d > win[0]]
        out["device_ops"][dev] = [evs[i] for i in keep]
        out["op_meta"][dev] = [record["op_meta"][dev][i] for i in keep]
    out["host_spans"] = [x for x in record["host_spans"]
                         if x[1] < win[1] and x[1] + x[2] > win[0]]
    out["spans"] = [x for x in record["spans"]
                    if x[1] < win[1] and x[1] + x[2] > win[0]]
    return out


def profile(workload: str, seed: int, solves: int, out: Optional[str],
            hlo_dir: Optional[str] = None, devices=None,
            cfg_override: Optional[dict] = None,
            slots_override: Optional[int] = None) -> dict:
    """Trace ``solves`` solves of the cell after its warm-up solve, the
    op_names read from XLA's dump in ``hlo_dir``; ``devices`` and the
    overrides as ``harness.run`` takes them."""
    import jax
    from bench import harness
    from repro import obs

    cell = harness.load("workloads", workload)
    cfg = dict(harness.load("configs", cell["config"]),
               **(cfg_override or {}))
    traffic = harness.load("traffic", cell["traffic"])
    if devices is None:
        devices = harness.require_devices(cell["chips"])
    jax.config.update("jax_enable_compilation_cache", False)
    u, v, w, n = harness.plugin("generators", cfg["generator"]).generate(
        cfg, seed)
    solve = harness.plugin("entries", traffic["entry"]).make(
        u, v, w, n, slots_override or cell["slots"],
        dict(traffic, chips=cell["chips"]))
    solve(harness.Clock())
    clock = harness.Clock()
    with tempfile.TemporaryDirectory(prefix="bench_phases_") as tmp:
        jax.profiler.start_trace(tmp)
        t0 = time.perf_counter()
        for _ in range(solves):
            with clock.span("solve"):
                solve(clock)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        record = extract(tmp, hlo_op_names(hlo_dir) if hlo_dir else None)
    win = trace.window(record)
    if win is None:
        raise RuntimeError("the trace holds no bench.solve span")
    parts = attribute(record, win)
    busy = trace.busy_ns(record, win)
    result = {
        "workload": workload, "seed": seed, "solves": solves,
        "device": devices[0].device_kind, "solve_ms": 1e3 * wall / solves,
        "window_s": (win[1] - win[0]) / 1e9, "busy_s": busy / 1e9,
        "phase_ms": {k: v / 1e6 / solves for k, v in sorted(parts.items())},
        "scoped_share": scoped_share(parts),
        "parts_over_busy": sum(parts.values()) / busy if busy else None,
        "idle_gaps": named_gaps(record, win, min_ns=1e7),
        "spans_ms": _span_ms(record, solves),
        "solve_records": obs.solve_records(last=solves),
        "top_ops": _top_ops(record, win, solves),
    }
    if out:
        os.makedirs(out, exist_ok=True)
        with gzip.open(os.path.join(out, f"{workload}.record.json.gz"),
                       "wt") as f:
            json.dump(trim(record, win), f)
    return result


def _span_ms(record: dict, solves: int) -> Dict[str, float]:
    acc: Dict[str, float] = {}
    for name, _, d, _ in record["spans"]:
        acc[name] = acc.get(name, 0.0) + d / 1e6 / solves
    return dict(sorted(acc.items()))


def _top_ops(record: dict, win: Interval, solves: int,
             k: int = 12) -> List[list]:
    """The costliest operations, containers aside, as [module, name,
    op_name, ms per solve]."""
    acc: Dict[Tuple[str, str, str], float] = {}
    for dev, evs in record["device_ops"].items():
        for (name, cat, s, d), (mod, op) in zip(evs, record["op_meta"][dev]):
            c = min(s + d, win[1]) - max(s, win[0])
            if cat not in trace.CONTAINERS and c > 0:
                acc[mod, name, op] = acc.get((mod, name, op), 0.0) + c
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[*key, ns / 1e6 / solves / len(record["device_ops"])]
            for key, ns in rows]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--solves", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    with tempfile.TemporaryDirectory(prefix="bench_hlo_") as hlo_dir:
        # before JAX starts its backend, which reads the flags once
        os.environ["XLA_FLAGS"] = " ".join(
            [os.environ.get("XLA_FLAGS", ""), f"--xla_dump_to={hlo_dir}",
             "--xla_dump_hlo_as_text"]).strip()
        for cell in args.workload:
            print(json.dumps(profile(cell, args.seed, args.solves, args.out,
                                     hlo_dir)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
