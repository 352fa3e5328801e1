"""Device time by program phase and gaps named by program spans
(``bench/phases.py``): on a synthetic record with known answers."""
import pytest

from bench import phases, trace


def _synthetic():
    # one solve on [0, 200] ns, one device
    ops = [["while.1 (s32[8])", "while", 10.0, 100.0],
           ["fusion.2 s32[8]", "fusion:kCustom", 10.0, 30.0],
           ["fusion.3 f32[8]", "fusion:kCustom", 40.0, 30.0],
           ["fusion.4 s32[8]", "fusion:kCustom", 80.0, 20.0],
           ["fusion.5 s32[8]", "fusion:kCustom", 120.0, 10.0],
           ["add.6 s32[8]", "add", 130.0, 20.0],
           ["sort.7 s32[8]", "sort", 140.0, 20.0]]  # overlaps add.6
    meta = [["jit_f", "jit(f)/while"],
            ["jit_f", "jit(f)/while/body/label_gather/gather"],
            ["jit_f", "jit(f)/while/body/minedges/scatter-min"],
            ["jit_f", "jit(f)/while/body/contract/doubling/while/gather"],
            ["jit_g", "jit(g)/exchange/exchange/gather"],
            ["jit_g", "jit(g)/add"],
            ["jit_g", "jit(g)/sort/sort"]]
    host = [["solve", 0.0, 200.0], ["host_prep", 0.0, 12.0],
            ["engine", 12.0, 188.0]]
    spans = [["msf.pack", 2.0, 9.0, {}],
             ["msf.driver.bounds", 111.0, 8.0, {"round": "1"}]]
    return {"device_ops": {"/device:TPU:0": ops}, "host_spans": host,
            "op_meta": {"/device:TPU:0": meta}, "spans": spans}


def test_parts_add_up_to_busy_time():
    rec = _synthetic()
    win = trace.window(rec)
    parts = phases.attribute(rec, win)
    assert parts == {"label_gather": 30.0, "minedges": 30.0,
                     "doubling": 20.0, "control": 20.0, "exchange": 10.0,
                     "unscoped": 10.0, "sort": 20.0}
    assert sum(parts.values()) == trace.busy_ns(rec, win) == 140.0
    assert phases.scoped_share(parts) == pytest.approx(110 / 120)


def test_gaps_named_by_the_innermost_span():
    rec = _synthetic()
    win = trace.window(rec)
    assert phases.named_gaps(rec, win) == [
        ["bench.engine", 40e-9], ["msf.pack", 10e-9],
        ["msf.driver.bounds", 10e-9]]
    assert phases.named_gaps(rec, win, min_ns=10.0) == [
        ["bench.engine", 40e-9]]


@pytest.mark.parametrize("op_name,phase", [
    ("jit(f)/while/body/contract/doubling/while/body/gather", "doubling"),
    ("jit(f)/ghost_setup/sort/jit(argsort)/sort", "sort"),
    ("jit(sort)/add", None),
    ("", None),
])
def test_phase_of(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_trim_keeps_one_solve():
    rec = _synthetic()
    part = phases.trim(rec, (100.0, 135.0))
    ((dev, ops),) = part["device_ops"].items()
    assert [o[0] for o in ops] == ["while.1 (s32[8])", "fusion.5 s32[8]",
                                   "add.6 s32[8]"]
    assert [m[0] for m in part["op_meta"][dev]] == ["jit_f", "jit_g",
                                                    "jit_g"]
    assert [s[0] for s in part["host_spans"]] == ["solve", "engine"]
    assert [s[0] for s in part["spans"]] == ["msf.driver.bounds"]


def test_profile_rehearsal(tmp_path):
    """``phases.profile`` at a tiny size on the CPU, whose trace
    holds no TPU plane: the program's spans and records still come
    back."""
    import json

    import jax
    from bench.tests import sizes
    cfg, slots = sizes.rehearsal("rgg20.sharded")
    out = phases.profile("rgg20.sharded", 9, 2, str(tmp_path),
                         devices=jax.devices(), cfg_override=cfg,
                         slots_override=slots)
    json.dumps(out)
    assert out["busy_s"] == 0 and out["phase_ms"] == {}
    assert {"msf.build.sort", "msf.driver.prep",
            "msf.driver.finish"} <= set(out["spans_ms"])
    assert len(out["solve_records"]) == 2
    assert all(r["rounds"] > 0 for r in out["solve_records"])
    assert (tmp_path / "rgg20.sharded.record.json.gz").is_file()


def test_op_names_from_the_hlo_dump(tmp_path):
    """A compiled module's text, as XLA dumps it, gives each instruction
    its op_name; a trace event finds it by module and instruction."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("minedges"):
            y = jnp.zeros(8).at[x % 8].min(x.astype(jnp.float32))
        with jax.named_scope("doubling"):
            z = jax.lax.fori_loop(0, 3, lambda i, p: p[p % 8], x)
        return y, z

    text = f.lower(jnp.arange(64)).compile().as_text()
    (tmp_path / "module_0001.jit_f.after_optimizations.txt").write_text(text)
    (tmp_path / "module_0001.jit_f.before_optimizations.txt").write_text("")
    tables = phases.hlo_op_names(str(tmp_path))
    ((name, (table,)),) = tables.items()
    assert name == "jit_f"
    found = {phases.phase_of(op) for _, op in table.values()}
    assert {"minedges", "doubling"} <= found
    instr, (full, op) = next((k, v) for k, v in table.items()
                             if phases.phase_of(v[1]) == "doubling")
    assert phases._op_name(tables, "jit_f", full) == op
    assert phases._op_name(tables, "jit_g", full) == ""
    # two modules of one name that disagree: the instruction's text decides
    other = dict(table)
    other[instr] = (f"%{instr} = s32[] other(1)", "jit(f)/minedges/y")
    tables["jit_f"].append(other)
    assert phases._op_name(tables, "jit_f", full) == op
    assert phases._op_name(tables, "jit_f", f"%{instr} = s32[] other(1)") \
        == "jit(f)/minedges/y"
    assert phases._op_name(tables, "jit_f", f"%{instr} = neither") == ""


def test_recorded_phases():
    """One solve of ``rgg20.sharded`` traced on a TPU v5e: the parts add
    up to the busy time, operations under a phase own nearly all of it,
    and every idle gap over 10 ms lies in a program span."""
    import gzip
    import json
    import os
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "rgg20.sharded.phases.json.gz")
    with gzip.open(path, "rt") as f:
        rec = json.load(f)
    win = trace.window(rec)
    parts = phases.attribute(rec, win)
    assert sum(parts.values()) == pytest.approx(trace.busy_ns(rec, win),
                                                rel=1e-12)
    assert {"label_gather", "minedges", "contract", "doubling", "sort",
            "ghost_setup", "exchange"} <= set(parts)
    assert phases.scoped_share(parts) > 0.95
    gaps = phases.named_gaps(rec, win, min_ns=1e7)
    assert gaps and all(name.startswith("msf.") for name, _ in gaps)
    # the costliest operation, the preprocessing's pointer doubling
    ((module, name, op_name, ms),) = phases._top_ops(rec, win, 1, k=1)
    assert (module, name) == ("jit__sharded_prep_shard_fn",
                              "fusion.167 s32[8454144]")
    assert phases.phase_of(op_name) == "doubling" and ms > 1e4
