"""The reader of the program's ``doubling_steps`` counter
(``bench/metrics/doubling_steps.solve.py``): on solves of every cell's
rehearsal, on hand-made records, and on a program that keeps no records
or records no such counter."""
import sys

import pytest

from bench import harness
from bench.tests.test_program_metrics import CELLS, View, _reported, _solves
from repro import obs

NAME = "doubling_steps.solve"


@pytest.mark.parametrize("workload", CELLS)
def test_doubling_steps_read(workload):
    """A whole count of at least one pass a round (the sharded cell: a
    round of its preprocessing program) in every cell that reports it."""
    assert NAME in _reported(workload)
    _solves(workload, 2)
    steps = harness.reader(NAME)(View(2))
    assert steps == int(steps) >= 1
    assert harness.reader(NAME)(View(10 ** 6)) is None


def test_doubling_steps_mean_of_window():
    """The mean over the window's records; nothing where a record of the
    window lacks the counter (a program that does not count it)."""
    obs.clear()
    obs.record(rounds=3, doubling_steps=[4, 3, 1])
    obs.record(rounds=3, doubling_steps=[4, 2, 1])
    read = harness.reader(NAME)
    assert read(View(2)) == 7.5
    assert read(View(1)) == 7
    obs.record(rounds=2)
    assert read(View(1)) is None and read(View(2)) is None
    assert read(View(4)) is None


def test_nothing_to_read_without_records(monkeypatch):
    """A program without ``repro.obs`` (an older checkout)."""
    import repro
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    monkeypatch.delattr(repro, "obs")
    assert harness.reader(NAME)(View(1)) is None
