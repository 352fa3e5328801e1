"""From a profiler trace to the numbers the per-layer readers take.

``extract`` reads the ``.xplane.pb`` file that ``jax.profiler`` writes
and keeps two lists, on the trace's one clock (nanoseconds):

* device operations: every event on the ``XLA Ops`` line of each TPU
  plane, as ``[name, category, start, duration]``.  On a TPU the event's
  name is the HLO instruction's text (``%fusion.57 = f32[1048576]{..}
  fusion(..), kind=kCustom, ..``); the category is its opcode, and for a
  fusion the opcode and its kind (``fusion:kCustom``: XLA's TPU backend
  emits gathers and scatters as such fusions).  The name kept is the
  instruction's name and result type, ``fusion.57 f32[1048576]``;
* host spans: the harness's own ``bench.<name>`` annotations.

The rest of this module reduces that record; ``tests/test_trace.py``
checks it on a synthetic record and on one recorded on a TPU v5e.  Busy
time is the union of the device-operation intervals of a device,
averaged over the devices; the window runs from the start of the first
traced solve to the end of the last.  A ``while`` operation's event
spans its body's events, so sums by category and the list of costliest
operations leave the control-flow containers out.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")

Interval = Tuple[float, float]

_KIND = re.compile(r"kind=(k[A-Za-z]+)")


def parse_op(text: str) -> Tuple[str, str]:
    """``(name, category)`` of an HLO instruction's text.

    ``%sort.2 = (s32[8]{0}, s32[8]{0}) sort(...)`` gives
    ``("sort.2 (s32[8], s32[8])", "sort")``; text that is not an
    instruction (``sort.12`` on another backend) is its own name, and
    its category is the part before the first dot.
    """
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text, text.split(".")[0]
    if rest.startswith("("):  # a tuple type: skip its balanced parens
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        typ, rest = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        typ, _, rest = rest.partition(" ")
    opcode = rest.split("(", 1)[0]
    kind = _KIND.search(rest) if opcode == "fusion" else None
    category = f"{opcode}:{kind.group(1)}" if kind else opcode
    return f"{head.lstrip('%')} {re.sub(r'{[^}]*}', '', typ)}", category


def extract(trace_dir: str) -> dict:
    """The compact record of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    ops: Dict[str, List[list]] = {}
    spans: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                dev = ops.setdefault(plane.name, [])
                for e in line.events:
                    name, category = parse_op(e.name)
                    dev.append([name, category, float(e.start_ns),
                                float(e.duration_ns)])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns),
                                      float(e.duration_ns)])
    return {"device_ops": ops, "host_spans": spans}


def window(record: dict, span: str = "solve") -> Optional[Interval]:
    """From the first ``span`` start to the last ``span`` end."""
    sel = [(s, s + d) for name, s, d in record["host_spans"] if name == span]
    if not sel:
        return None
    return min(s for s, _ in sel), max(e for _, e in sel)


def _clip(intervals: Iterable[Interval], win: Interval) -> List[Interval]:
    lo, hi = win
    out = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sorted((s, e) for s, e in out if e > s)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, disjoint cover of the intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def device_intervals(record: dict, win: Interval) -> Dict[str, List[Interval]]:
    """Per device, the union of its operations' intervals in the window."""
    return {dev: union(_clip(((s, s + d) for _, _, s, d in evs), win))
            for dev, evs in record["device_ops"].items()}


def busy_ns(record: dict, win: Interval) -> float:
    """Busy nanoseconds in the window, averaged over the devices."""
    per = device_intervals(record, win)
    if not per:
        return 0.0
    return sum(sum(e - s for s, e in iv) for iv in per.values()) / len(per)


def category_ns(record: dict, win: Interval,
                categories: Iterable[str]) -> float:
    """Device nanoseconds of operations of these categories, summed over
    their events in the window and averaged over the devices."""
    want = set(categories)
    devs = record["device_ops"]
    if not devs:
        return 0.0
    total = 0.0
    for evs in devs.values():
        total += sum(e - s for s, e in _clip(
            ((s, s + d) for _, op, s, d in evs if op in want), win))
    return total / len(devs)


def top_ops(record: dict, win: Interval, k: int = 10) -> List[list]:
    """The ``k`` device operations (by name, with their category) that
    took most time, control-flow containers aside, with their seconds
    summed over the window and averaged over devices."""
    acc: Dict[str, float] = {}
    devs = record["device_ops"]
    for evs in devs.values():
        for name, cat, s, d in evs:
            if cat in CONTAINERS:
                continue
            name = f"{name} {cat}"
            c = _clip([(s, s + d)], win)
            if c:
                acc[name] = acc.get(name, 0.0) + (c[0][1] - c[0][0])
    rows = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[name, ns / len(devs) / 1e9] for name, ns in rows]


def idle_gaps(record: dict, win: Interval, k: int = 10) -> List[list]:
    """The ``k`` longest gaps in which no operation ran on the first
    device, each named by the innermost harness span around its middle
    (``-`` where none was open), with its seconds."""
    per = device_intervals(record, win)
    if not per:
        return []
    busy = per[sorted(per)[0]]
    gaps, t = [], win[0]
    for s, e in busy + [(win[1], win[1])]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    spans = [(name, s, s + d) for name, s, d in record["host_spans"]
             if name != "solve"]
    rows = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:k]:
        mid = (s + e) / 2
        open_ = [(b - a, name) for name, a, b in spans if a <= mid <= b]
        rows.append([min(open_)[1] if open_ else "-", (e - s) / 1e9])
    return rows
