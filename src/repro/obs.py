"""Program-side tracing: host spans, phase scopes and solve counters.

Everything lands in the profiler trace that ``jax.profiler`` records,
on one clock with the device's events:

* ``span(name, **args)`` is a host span, ``msf.<name>`` in the trace
  (a ``jax.profiler.TraceAnnotation``).  It yields the annotation, so a
  caller can attach arguments it only knows at the end
  (``set_metadata``).  Its seconds by the host clock also add up, per
  name, into a solve record's ``host_s``, so that runs without a trace
  keep them too.  A record holds the spans of its solve, from the
  solve's ``begin`` on, and those of the newest input build before it
  (``building``: the packing of the graph a solve starts from); spans
  of anything else are dropped.
* ``scope(name)`` is a ``jax.named_scope`` for a phase of a jitted
  program.  XLA keeps it in the ``op_name`` metadata of every operation
  traced inside it.  ``PHASES`` is the vocabulary every engine uses.  It
  also works as a decorator.
* ``record(**counters)`` appends one dict per public solve to a bounded
  ring, read back with ``solve_records``.  A counter is an int, a device
  scalar or array, or a list of these, and its value is the sum of all
  their elements.  Nothing is synchronised when recording: device values
  are kept as they are and converted on read.  Nothing is recorded when
  a counter is a tracer (an outer ``jit`` or AOT lowering).

The ring and the span totals are per process.  ``minimum_spanning_forest``
(static engine), ``distributed_sharded_msf`` (host-driven shrinking
path) and the measurement pass of ``plan_sharded_msf`` record
``rounds``, ``live_slots``, ``slot_rounds`` and ``doubling_steps``: the
Borůvka rounds executed, the alive edge slots and all edge slots summed
over those rounds, and the pointer-doubling passes CONTRACT ran (the
sharded driver: its preprocessing program's).
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Dict, List, Optional

import jax
import numpy as np

PREFIX = "msf."
PHASES = ("label_gather", "minedges", "contract", "doubling", "sort",
          "ghost_setup", "exchange", "lookup", "push")
RING = 256

_records: collections.deque = collections.deque(maxlen=RING)
_host_s: Dict[str, float] = {}   # spans of the running solve
_input_s: Dict[str, float] = {}  # spans of the newest input build
_building = False


@contextlib.contextmanager
def span(name: str, **args):
    """Host span ``msf.<name>``; yields its ``TraceAnnotation``."""
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation(PREFIX + name, **args) as ann:
        try:
            yield ann
        finally:
            into = _input_s if _building else _host_s
            into[name] = into.get(name, 0.0) + time.perf_counter() - t


@contextlib.contextmanager
def building():
    """The spans inside build a solve's input; each build replaces the
    one before."""
    global _building
    _input_s.clear()
    _building = True
    try:
        yield
    finally:
        _building = False


def begin() -> None:
    """A solve starts: it takes the newest input build's spans, and the
    spans since the last record are dropped."""
    _host_s.clear()
    _host_s.update(_input_s)
    _input_s.clear()


def scope(name: str):
    """Phase scope for jitted code (one of ``PHASES``)."""
    if name not in PHASES:
        raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
    return jax.named_scope(name)


def record(**counters) -> None:
    """One solve's counters, with the host seconds of its spans; nothing
    where a counter is a tracer."""
    if any(isinstance(x, jax.core.Tracer)
           for x in jax.tree.leaves(counters)):
        return
    _records.append(dict(counters, host_s=dict(_host_s)))
    _host_s.clear()


def _total(x) -> int:
    if isinstance(x, (list, tuple)):
        return sum(_total(part) for part in x)
    return int(np.asarray(x).astype(np.int64).sum())


def solve_records(last: Optional[int] = None) -> List[dict]:
    """The newest ``last`` records (all kept where None), oldest first,
    each counter as an int and ``host_s`` as span name -> seconds."""
    recs = list(_records)
    if last is not None:
        recs = recs[-last:] if last > 0 else []
    return [{k: dict(v) if k == "host_s" else _total(v)
             for k, v in r.items()} for r in recs]


def clear() -> None:
    """Forget every record and every span total."""
    _records.clear()
    _host_s.clear()
    _input_s.clear()
