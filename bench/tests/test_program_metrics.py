"""The readers of the program's own counters and spans
(``bench/records.py``, ``repro.obs``): on solves of every cell's
rehearsal, and on a program that keeps no records."""
import sys

import pytest

from bench import harness, phases
from bench.tests import sizes
from repro import obs

CELLS = [w["name"] for w in harness.benchmark_spec()["workloads"]]
PROGRAM = ["rounds.solve", "live_slot_share.solve", "driver_host_ms.solve",
           "build_sort_ms.solve"]


class View:
    """The part of ``harness.TraceView`` these readers use."""

    def __init__(self, solves):
        self.solves = solves


def _solves(workload, k, seed=5):
    cfg, slots = sizes.rehearsal(workload)
    cell = harness.load("workloads", workload)
    cfg = dict(harness.load("configs", cell["config"]), **cfg)
    traffic = harness.load("traffic", cell["traffic"])
    u, v, w, n = harness.plugin("generators", cfg["generator"]).generate(
        cfg, seed)
    solve = harness.plugin("entries", traffic["entry"]).make(
        u, v, w, n, slots, dict(traffic, chips=cell["chips"]))
    solve(harness.Clock())  # the warm-up's record is not the window's
    for _ in range(k):
        solve(harness.Clock())


def _reported(workload):
    spec = harness.benchmark_spec()
    return {m["name"] for m in harness.cell_metrics(spec, workload,
                                                    "per_layer")}


@pytest.mark.parametrize("workload", CELLS)
def test_program_metrics_read(workload):
    _solves(workload, 2)
    view = View(2)
    for name in sorted(set(PROGRAM) & _reported(workload)):
        value = harness.reader(name)(view)
        assert value is not None and value > 0, name
    rounds = harness.reader("rounds.solve")(view)
    assert rounds == int(rounds) >= 2
    assert 0 < harness.reader("live_slot_share.solve")(view) < 100
    assert obs.solve_records(last=2)[0]["rounds"] == rounds
    # a window of more solves than records has nothing to read
    assert harness.reader("rounds.solve")(View(10 ** 6)) is None


def test_sharded_spans_are_its_own():
    assert set(PROGRAM) & _reported("kron20.boruvka") == {
        "rounds.solve", "live_slot_share.solve"}
    _solves("kron20.boruvka", 1)
    for name in ("driver_host_ms.solve", "build_sort_ms.solve"):
        assert harness.reader(name)(View(1)) is None


def test_nothing_to_read_without_records(monkeypatch):
    """A program without ``repro.obs`` (an older checkout)."""
    import repro
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    for name in PROGRAM:
        assert harness.reader(name)(View(1)) is None


def test_phase_vocabulary_is_the_programs():
    assert phases.PHASES == obs.PHASES
