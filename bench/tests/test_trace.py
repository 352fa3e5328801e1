"""The reduction from a trace to the per-layer numbers: on a synthetic
record with known answers, and on records taken on a TPU v5e."""
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
RECORDED = ["kron20.boruvka.trace.json", "rgg20.sharded.trace.json"]


def _synthetic():
    # two solves on [0, 100] and [100, 200] ns; one device
    ops = [["fusion.1 s32[8]", "fusion:kLoop", 10.0, 20.0],
           ["fusion.2 f32[4]", "fusion:kCustom", 25.0, 15.0],  # overlaps
           ["sort.3 s32[8]", "sort", 120.0, 30.0],
           ["while.4 (s32[8])", "while", 110.0, 60.0],  # holds sort.3
           ["fusion.2 f32[4]", "fusion:kCustom", 190.0, 40.0]]  # runs past
    spans = [["solve", 0.0, 100.0], ["host_prep", 0.0, 10.0],
             ["engine", 10.0, 90.0], ["solve", 100.0, 100.0],
             ["host_prep", 100.0, 10.0], ["engine", 110.0, 90.0]]
    return {"device_ops": {"/device:TPU:0": ops}, "host_spans": spans}


def test_synthetic_reduction():
    rec = _synthetic()
    win = trace.window(rec)
    assert win == (0.0, 200.0)
    # busy: [10, 40] + [110, 170] + [190, 200] = 30 + 60 + 10
    assert trace.busy_ns(rec, win) == 100.0
    assert trace.category_ns(rec, win, ["fusion:kCustom"]) == 15.0 + 10.0
    assert trace.category_ns(rec, win, ["sort"]) == 30.0
    # the while container is left out of the costliest operations
    assert trace.top_ops(rec, win, k=2) == [
        ["sort.3 s32[8] sort", 30e-9], ["fusion.2 f32[4] fusion:kCustom",
                                        25e-9]]
    # gaps: [0, 10] in host_prep, [40, 110] engine at its middle 75,
    # [170, 190] engine
    gaps = trace.idle_gaps(rec, win)
    assert gaps[0] == ["engine", 70e-9]
    assert sorted(g[1] for g in gaps) == [10e-9, 20e-9, 70e-9]


def test_devices_are_averaged():
    rec = _synthetic()
    rec["device_ops"]["/device:TPU:1"] = [["x", "copy", 0.0, 200.0]]
    win = trace.window(rec)
    assert trace.busy_ns(rec, win) == (100.0 + 200.0) / 2


@pytest.mark.parametrize("text,name,category", [
    ("%fusion.57 = f32[1048576]{0:T(1024)S(1)} fusion(s32[16777216]{0:T("
     "1024)} %bitcast.42, f32[]{:T(128)} %c), kind=kCustom, calls=%f.46",
     "fusion.57 f32[1048576]", "fusion:kCustom"),
    ("%sort.2 = (s32[1048576]{0:T(1024)S(1)}, s32[1048576]{0:T(1024)S(1)})"
     " sort(s32[1048576]{0:T(1024)S(1)} %g), dimensions={0}",
     "sort.2 (s32[1048576], s32[1048576])", "sort"),
    ("%while.12 = (s32[8]{0}, pred[]{:T(512)}) while((s32[8]{0}, pred[]"
     "{:T(512)}) %tuple.48), condition=%c, body=%b",
     "while.12 (s32[8], pred[])", "while"),
    ("sort.12", "sort.12", "sort"),
])
def test_parse_op(text, name, category):
    assert trace.parse_op(text) == (name, category)


def _sweep_busy(intervals, lo, hi):
    """Busy time by a sweep over sorted end points (independent of
    trace.union)."""
    pts = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            pts += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, d in sorted(pts, key=lambda p: (p[0], -p[1])):
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


@pytest.mark.parametrize("fname", RECORDED)
def test_recorded_trace(fname):
    with open(os.path.join(DATA, fname)) as f:
        rec = json.load(f)
    win = trace.window(rec)
    (evs,) = rec["device_ops"].values()
    busy = trace.busy_ns(rec, win)
    assert busy == pytest.approx(_sweep_busy(
        [(s, s + d) for _, _, s, d in evs], *win), rel=1e-12)
    assert 0 < busy <= win[1] - win[0]
    # everything the solve ran on the device lies inside its span
    custom = sum(d for _, c, s, d in evs if c == "fusion:kCustom")
    assert trace.category_ns(rec, win, ["fusion:kCustom"]) == \
        pytest.approx(custom, rel=1e-12)
    assert trace.category_ns(rec, win, ["sort"]) > 0
    top = trace.top_ops(rec, win)
    assert len(top) == 10 and all(" while" not in n for n, _ in top)
    assert top[0][1] >= top[-1][1] > 0
    gaps = trace.idle_gaps(rec, win)
    assert {g[0] for g in gaps} <= {"host_prep", "engine", "-"}
    assert sum(g[1] for g in gaps) <= (win[1] - win[0] - busy) / 1e9 + 1e-9
