"""Unit tests for the sharded-label engine's internals (subprocess,
8 virtual devices): owner-routing round trip, shared-vertex root masks,
overflow accounting on undersized exchange capacities (including the
new smaller coalesced-lookup default), the comm counters that make the
ISSUE 2 optimizations measurable, and the ISSUE 3 additions — the
shrinking capacity schedule (bit-identity, decaying per-round
capacities, exact host bounds) and the bucketed O(edges/shard)
preprocessing (equivalence against the dense reference core, no [n]
transient in the compiled program)."""
import pytest

from repro.core.distributed import quantize_capacity, shrink_schedule
from tests.helpers.subproc import run_multidevice


def test_shrink_schedule_ladder():
    # geometric halving down to the floor, matching the engines' round
    # bound for full >= 2
    assert shrink_schedule(8) == (8, 4, 2, 1)
    assert shrink_schedule(7) == (7, 4, 2, 1)
    assert shrink_schedule(1) == (1,)
    assert shrink_schedule(5, floor=2) == (5, 3, 2)
    import math
    for full in (2, 3, 13, 64, 1000):
        assert len(shrink_schedule(full)) == math.ceil(math.log2(full)) + 1


def test_quantize_capacity_properties():
    for full in (1, 7, 512, 4096):
        for bound in (0, 1, 2, 3, full // 3 + 1, full, full + 5):
            q = quantize_capacity(bound, full)
            # never exceeds full (an explicit undersized user capacity
            # must stay undersized so overflow is *reported*) ...
            assert q <= max(full, 1), (bound, full, q)
            # ... and covers the bound whenever the ladder can
            if bound <= full:
                assert q >= max(bound, 1), (bound, full, q)
            # rungs come from the shared ladder
            assert q in shrink_schedule(full), (bound, full, q)

LOOKUP_ROUNDTRIP = """
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.distributed_sharded import _sharded_lookup

p, vps, L = 8, 16, 96
mesh = Mesh(np.array(jax.devices()), ("data",))
# global table[vid] = 7 * vid + 3, 1D-sharded by vid
table = (7 * np.arange(p * vps, dtype=np.int32) + 3)
rng = np.random.default_rng(0)
vids = rng.integers(0, p * vps, (p * L,)).astype(np.int32)
valid = rng.random(p * L) < 0.9

def body(tab, vq, va):
    out, ok, ovf = _sharded_lookup(tab, vq, va, vps, L, ("data",))
    return out, ok, ovf

f = shard_map(body, mesh=mesh,
              in_specs=(P("data"), P("data"), P("data")),
              out_specs=(P("data"), P("data"), P()))
out, ok, ovf = f(jnp.asarray(table), jnp.asarray(vids), jnp.asarray(valid))
out, ok = np.asarray(out), np.asarray(ok)
# capacity == L can never overflow; every valid request is answered with
# the owner's value, i.e. the round trip is the identity on the table
assert int(ovf) == 0, int(ovf)
assert np.array_equal(ok, valid)
assert np.array_equal(out[valid], table[vids[valid]])
print("OK")
"""


ROOT_MASK = """
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.distributed import build_dist_graph, _shared_vertex_root_mask
from repro.data import generators

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
u, v, w, n = generators.generate("grid2d", 1024, seed=2)
g, cap = build_dist_graph(u, v, w, n, p)

def body(uu, ww):
    valid = jnp.isfinite(ww)
    mask, firsts, lasts = _shared_vertex_root_mask(uu, valid, n, ("data",))
    return mask, firsts, lasts

f = shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
              out_specs=(P(), P(), P()))
mask, firsts, lasts = f(g.u, g.w)
mask = np.asarray(mask)

# host-side expectation: the sorted directed edge list is cut into p
# contiguous slices; a vertex is shared iff its edge run straddles a
# shard boundary, i.e. shard s's last source == shard s+1's first source
gu = np.asarray(g.u); gw = np.asarray(g.w)
expect = np.zeros(n, bool)
bounds = []
for s in range(p):
    sl = slice(s * cap, (s + 1) * cap)
    vv = np.isfinite(gw[sl])
    if vv.any():
        bounds.append((gu[sl][vv][0], gu[sl][vv][-1]))
    else:
        bounds.append((-1, -2))
for s in range(p - 1):
    if bounds[s][1] == bounds[s + 1][0] and bounds[s][1] >= 0:
        expect[bounds[s][1]] = True
assert np.array_equal(mask, expect), (np.nonzero(mask)[0],
                                      np.nonzero(expect)[0])
# a 64x64 grid over 8 shards must actually have shared vertices
assert expect.sum() > 0
print("OK")
"""


OVERFLOW = """
from jax.sharding import Mesh, PartitionSpec as P
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (_sharded_lookup,
                                            distributed_sharded_msf)
from repro.core import oracle
from repro.data import generators

p, vps, L = 8, 16, 24
mesh = Mesh(np.array(jax.devices()), ("data",))

# (1) primitive level: every shard fires L valid requests at vertex 0's
# owner with capacity 1 -> exactly L-1 drops per shard, all reported
table = np.arange(p * vps, dtype=np.int32)
vids = np.zeros(p * L, np.int32)

def body(tab, vq):
    va = jnp.ones(vq.shape, bool)
    out, ok, ovf = _sharded_lookup(tab, vq, va, vps, 1, ("data",))
    return out, ok, ovf

f = shard_map(body, mesh=mesh, in_specs=(P("data"), P("data")),
              out_specs=(P("data"), P("data"), P()))
out, ok, ovf = f(jnp.asarray(table), jnp.asarray(vids))
assert int(ovf) == p * (L - 1), (int(ovf), p * (L - 1))
ok = np.asarray(ok)
assert ok.sum() == p  # one winner per source shard
assert np.all(np.asarray(out)[ok] == 0)

# (2) engine level: undersized edge_capacity must be *reported*, never
# silently produce a confident wrong answer
u, v, w, n = generators.generate("gnm", 256, avg_degree=8.0, seed=5)
g, cap = build_dist_graph(u, v, w, n, p)
mask, wt, cnt, lab, ovf, st = distributed_sharded_msf(
    g, n, mesh, axis_names=("data",), edge_capacity=1)
assert int(ovf) > 0, "undersized capacity must report overflow"

# (3) default capacities on the same graph: exact, zero overflow — and
# the coalesced lookup default capacity is genuinely smaller than the
# full edges/shard buffer of PR 1 while staying overflow-free
from repro.core.distributed_sharded import default_lookup_capacity
lk = default_lookup_capacity(g, p, n)
assert lk < cap, (lk, cap)
mask, wt, cnt, lab, ovf, st = distributed_sharded_msf(
    g, n, mesh, axis_names=("data",))
_, expect = oracle.kruskal(u, v, w, n)
assert int(ovf) == 0
assert abs(float(wt) - expect) < 1e-3 * max(1.0, expect)

# (4) an undersized *lookup* capacity must also be reported, not silent
mask, wt, cnt, lab, ovf, st = distributed_sharded_msf(
    g, n, mesh, axis_names=("data",), lookup_capacity=1)
assert int(ovf) > 0, "undersized lookup capacity must report overflow"
print("OK")
"""


COMM_COUNTERS = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import distributed_sharded_msf
from repro.data import generators

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
u, v, w, n = generators.generate("rgg2d", 512, avg_degree=8.0, seed=7)
g, cap = build_dist_graph(u, v, w, n, p)
kmask, kweight = oracle.kruskal(u, v, w, n)
ksel = np.nonzero(kmask)[0]

recs = {}
for name, flags in (
    ("baseline", dict(local_preprocessing=False, coalesce=False,
                      src_only=False, adaptive_doubling=False)),
    ("optimized", {}),
):
    mask, wt, cnt, lab, ovf, st = distributed_sharded_msf(
        g, n, mesh, axis_names=("data",), **flags)
    # every variant stays exact at overflow 0 ...
    assert int(ovf) == 0, (name, int(ovf))
    sel = np.unique(np.asarray(g.eid)[np.asarray(mask)])
    assert np.array_equal(sel, ksel), (name, "edge set differs from oracle")
    recs[name] = (int(st.calls), float(st.items), float(st.bytes),
                  int(st.rounds))
    assert recs[name][3] > 0

# ... and the optimization flags must strictly cut both a2a invocations
# and routed item volume (the honest metric; 2x/4x floors are asserted
# at benchmark scale by benchmarks/sharded_scaling.py --smoke in CI)
base, opt = recs["baseline"], recs["optimized"]
assert opt[0] < base[0], (base, opt)
assert opt[1] < base[1], (base, opt)
print("OK")
"""


SHRINKING = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (distributed_sharded_msf,
                                            minedges_buffer_bytes)
from repro.data import generators

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
for fam in ("gnm", "rgg2d"):
    u, v, w, n = generators.generate(fam, 512, avg_degree=8.0, seed=7)
    g, cap = build_dist_graph(u, v, w, n, p)
    kmask, kweight = oracle.kruskal(u, v, w, n)
    ksel = np.nonzero(kmask)[0]
    flat = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                                   shrink_capacities=False)
    trace = []
    shr = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                                  shrink_capacities=True,
                                  round_trace=trace)
    for name, res in (("flat", flat), ("shrink", shr)):
        assert int(res[4]) == 0, (fam, name, int(res[4]))
        sel = np.unique(np.asarray(g.eid)[np.asarray(res[0])])
        assert np.array_equal(sel, ksel), (fam, name, "edge set != oracle")
    # bit-identical slot masks, weights, counts between the two paths
    assert np.array_equal(np.asarray(flat[0]), np.asarray(shr[0])), fam
    assert abs(float(flat[1]) - float(shr[1])) < 1e-3 * max(
        1.0, float(flat[1]))
    assert int(flat[2]) == int(shr[2])
    # the schedule must be populated, below the flat worst case, and
    # must cut the capacity-padded buffer bytes (the honest metric)
    caps = [t["cap_edge"] for t in trace]
    assert caps and len(caps) == int(shr[5].rounds), (fam, caps)
    assert max(caps) < cap, (fam, caps, cap)
    assert float(shr[5].bytes) < float(flat[5].bytes), fam
    # trace bookkeeping matches the engine totals
    assert sum(t["a2a_calls"] for t in trace) <= int(shr[5].calls)
    assert sum(t["minedges_buffer_bytes"] for t in trace) < \
        int(shr[5].rounds) * minedges_buffer_bytes(p, cap, 1, True), fam

# undersized explicit capacities must still *report* under the schedule
u, v, w, n = generators.generate("gnm", 256, avg_degree=8.0, seed=5)
g, cap = build_dist_graph(u, v, w, n, p)
res = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                              edge_capacity=1, shrink_capacities=True)
assert int(res[4]) > 0, "undersized edge capacity must report overflow"
res = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                              lookup_capacity=1, shrink_capacities=True)
assert int(res[4]) > 0, "undersized lookup capacity must report overflow"
print("OK")
"""


PREPROCESS_BUCKETED = """
from functools import partial
from jax.sharding import Mesh, PartitionSpec as P
from repro.comm.exchange import ExchangeStats
from repro.core.distributed import build_dist_graph, _local_preprocessing_core
from repro.core.distributed_sharded import (_sharded_preprocess,
                                            vertices_per_shard)
from repro.data import generators

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
# rgg2d: high locality => real contraction happens; grid2d: shared
# boundary vertices on nearly every shard edge
for fam in ("rgg2d", "grid2d"):
    u, v, w, n = generators.generate(fam, 1024, avg_degree=8.0, seed=2)
    g, cap = build_dist_graph(u, v, w, n, p)
    vps = vertices_per_shard(n, p)

    def bucketed(uu, vv, ww, ee):
        valid = jnp.isfinite(ww)
        lab, pre, dead0, ovf, st, _ = _sharded_preprocess(
            uu, vv, ww, ee, valid, n, vps, vps, ("data",), "grid",
            ExchangeStats.zeros())
        return lab, pre, dead0, ovf

    fb = shard_map(bucketed, mesh=mesh,
                   in_specs=(P("data"),) * 4,
                   out_specs=(P("data"), P("data"), P("data"), P()))
    lab_b, pre_b, dead_b, ovf = fb(g.u, g.v, g.w, g.eid)
    assert int(ovf) == 0

    # dense reference: the replicated engine's per-shard contribution
    # core, combined on the host exactly like _local_preprocessing's
    # psum (each vertex is contracted on at most one shard)
    def dense(uu, ww, ee, vv):
        valid = jnp.isfinite(ww)
        labs, mst = _local_preprocessing_core(uu, vv, ww, ee, valid, n,
                                              ("data",))
        return labs, mst

    fd = shard_map(dense, mesh=mesh, in_specs=(P("data"),) * 4,
                   out_specs=(P("data"), P("data")))
    labs_all, pre_d = fd(g.u, g.w, g.eid, g.v)
    labs_all = np.asarray(labs_all).reshape(p, n)
    iota = np.arange(n)
    comb = iota.copy()
    for s in range(p):
        ch = labs_all[s] != iota
        comb[ch] = labs_all[s][ch]
    # identical contracted slots ...
    assert np.array_equal(np.asarray(pre_b), np.asarray(pre_d)), fam
    # ... identical owner-side label vector ...
    lab_ref = np.arange(p * vps)
    lab_ref[:n] = comb
    assert np.array_equal(np.asarray(lab_b), lab_ref), fam
    # ... identical initial dead mask (locally-internal edges)
    uh, vh = np.asarray(g.u), np.asarray(g.v)
    dead_ref = comb[uh] == comb[vh]
    assert np.array_equal(np.asarray(dead_b), dead_ref), fam
print("OK")
"""


PREPROCESS_PEAK_MEMORY = """
from jax.sharding import Mesh
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (_build_sharded_prep_fn,
                                            vertices_per_shard)
from repro.data import generators

# tiny edge set over a HUGE vertex-id space: the bucketed preprocessing
# must compile to O(edges/shard + n/p) per-device temps, not O(n) — the
# dense [n] scratch of the PR 2 version would show up as ~4n temp bytes
p = 8
n = 1 << 20
mesh = Mesh(np.array(jax.devices()), ("data",))
rng = np.random.default_rng(0)
m = 512
u = rng.integers(0, n, m).astype(np.int32)
v = rng.integers(0, n, m).astype(np.int32)
keep = u != v
w = rng.uniform(1.0, 9.0, keep.sum()).astype(np.float32)
g, cap = build_dist_graph(u[keep], v[keep], w, n, p)
vps = vertices_per_shard(n, p)
prep = _build_sharded_prep_fn(n, vps, mesh, ("data",), vps, "grid")
specs = [jax.ShapeDtypeStruct((g.cap_total,), d)
         for d in (jnp.int32, jnp.int32, jnp.float32, jnp.int32)]
compiled = prep.lower(*specs).compile()
try:
    temp = compiled.memory_analysis().temp_size_in_bytes
except Exception as e:  # backend without memory analysis: inconclusive
    print("SKIP memory_analysis:", e)
    print("OK")
else:
    # per-device budget: the carried [vps] label slice + [p, vps] label
    # exchange buffers + O(cap) run-rank scratch; a dense [n] transient
    # alone would cost 4n = 4 MiB per device
    budget = p * (60 * cap + 40 * vps + 8 * p * vps)
    assert temp < budget, (temp, budget)
    assert temp < 4 * n, (temp, 4 * n)  # the smoking gun: sub-[n] temps
    print("temp_bytes", temp, "budget", budget)
    print("OK")
"""


GHOST_CACHE = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import distributed_sharded_msf
from repro.data import generators

p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
u, v, w, n = generators.generate("rgg2d", 512, avg_degree=8.0, seed=7)
g, cap = build_dist_graph(u, v, w, n, p)
kmask, kweight = oracle.kruskal(u, v, w, n)
ksel = np.nonzero(kmask)[0]

def check(res, ctx):
    assert int(res[4]) == 0, (ctx, int(res[4]))
    sel = np.unique(np.asarray(g.eid)[np.asarray(res[0])])
    assert np.array_equal(sel, ksel), (ctx, "edge set differs from oracle")

# (1) ghost on vs off: bit-identical results, and the cache must
# actually work — hits and pushes > 0, routed endpoint-lookup items
# (misses + pushed) strictly below the coalesced-only run's misses
trace = []
gres = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                               round_trace=trace)
cres = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                               ghost_cache=False)
check(gres, "ghost")
check(cres, "coalesce")
assert np.array_equal(np.asarray(gres[0]), np.asarray(cres[0]))
gst, cst = gres[5], cres[5]
assert float(gst.hits) > 0 and float(gst.pushed) > 0, (
    float(gst.hits), float(gst.pushed))
assert float(cst.hits) == 0 and float(cst.pushed) == 0
g_lookup = float(gst.misses) + float(gst.pushed)
assert g_lookup < float(cst.misses), (g_lookup, float(cst.misses))

# (2) per-round trace carries the ghost columns; the dirty push decays
# with the alive-component count
assert all("cache_hits" in t and "pushed_items" in t and "cap_push" in t
           for t in trace), trace[0].keys()
assert all(t["ghost"] for t in trace)
pushes = [t["pushed_items"] for t in trace]
assert pushes[-1] < pushes[0], pushes

# (2b) settled-vertex skip satellite: on a graph where most components
# finish early the host bound drops the RELABEL capacity below vps.
# A 10-vertex path strided across the id space (~1 vertex per shard)
# keeps the solve alive; every other vertex pairs into a single-edge
# component whose members settle right after round 1 (their component
# chose nothing), so round 2's unsettled set is ~1 vertex per shard.
# (On a giant-component graph like rgg2d nothing settles until the
# end, so the capacity legitimately stays at vps there.)
ns = 212
path_ids = np.arange(10, dtype=np.int32) * 21
rest = np.setdiff1d(np.arange(ns, dtype=np.int32), path_ids)
m2 = len(rest) // 2 * 2
su = np.concatenate([path_ids[:-1], rest[:m2:2]]).astype(np.int32)
sv = np.concatenate([path_ids[1:], rest[1:m2:2]]).astype(np.int32)
rng = np.random.default_rng(0)
sw = rng.uniform(1, 9, len(su)).astype(np.float32)
gs, _ = build_dist_graph(su, sv, sw, ns, p)
strace = []
sres = distributed_sharded_msf(gs, ns, mesh, axis_names=("data",),
                               round_trace=strace)
assert int(sres[4]) == 0
skmask, _ = oracle.kruskal(su, sv, sw, ns)
ssel = np.unique(np.asarray(gs.eid)[np.asarray(sres[0])])
assert np.array_equal(ssel, np.nonzero(skmask)[0])
svps = -(-ns // p)
caps_rel = [t["cap_relabel"] for t in strace]
assert len(caps_rel) >= 2 and caps_rel[-1] < svps, caps_rel

# (3) fused engine, push pinned to 1: overflow is REPORTED, not silent
res = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                              shrink_capacities=False, push_capacity=1)
assert int(res[4]) > 0, "undersized push capacity must report overflow"

# (4) shrinking driver, push pinned to 1: graceful exact fallback —
# the driver abandons the cache instead of risking stale ghosts, so the
# result stays exact at overflow 0 and the trace shows the switch
trace = []
res = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                              push_capacity=1, round_trace=trace)
check(res, "fallback")
assert np.array_equal(np.asarray(res[0]), np.asarray(cres[0]))
assert not any(t["ghost"] for t in trace), [t["ghost"] for t in trace]

# (5) undersized lookup capacity also starves the ghost *fills*:
# reported through the same overflow contract
res = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                              shrink_capacities=False, lookup_capacity=1)
assert int(res[4]) > 0, "undersized fill capacity must report overflow"
print("OK")
"""


GHOST_LIMIT = """
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import distributed_sharded_msf
from repro.data import generators

# ISSUE 5 satellite: the scatter_updates subscriber bitmask caps the
# ghost cache at MAX_GHOST_SHARDS = 31; beyond that the engine must
# auto-fall back to coalesced lookups.  32 virtual devices are too
# heavy for CI, so the forced-width knob `ghost_shard_limit` simulates
# the p > limit condition on the 8-device mesh: with limit=4 (< p=8)
# the engine must behave exactly like ghost_cache=False — same exact
# result, zero ghost counters — on both the shrinking driver and the
# fused path.  (The bit arithmetic of the mask itself is unit-tested
# to width 31 in tests/test_comm.py.)
p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
u, v, w, n = generators.generate("rgg2d", 512, avg_degree=8.0, seed=7)
g, cap = build_dist_graph(u, v, w, n, p)
kmask, _ = oracle.kruskal(u, v, w, n)
ksel = np.nonzero(kmask)[0]

for flags in (dict(), dict(shrink_capacities=False)):
    ref = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                                  ghost_cache=False, **flags)
    lim = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                                  ghost_shard_limit=4, **flags)
    for name, res in (("no_ghost", ref), ("limited", lim)):
        assert int(res[4]) == 0, (flags, name, int(res[4]))
        sel = np.unique(np.asarray(g.eid)[np.asarray(res[0])])
        assert np.array_equal(sel, ksel), (flags, name, "!= oracle")
    assert np.array_equal(np.asarray(lim[0]), np.asarray(ref[0])), flags
    # the fallback genuinely disabled the cache: no hits, no pushes,
    # and the routed lookup volume matches the coalesced engine's
    assert float(lim[5].hits) == 0 and float(lim[5].pushed) == 0, flags
    assert float(lim[5].misses) == float(ref[5].misses), flags
# a limit at/above p leaves the cache on
on = distributed_sharded_msf(g, n, mesh, axis_names=("data",),
                             ghost_shard_limit=8)
assert float(on[5].hits) > 0
print("OK")
"""


@pytest.mark.parametrize("name,script", [
    ("lookup_roundtrip", LOOKUP_ROUNDTRIP),
    ("root_mask", ROOT_MASK),
    ("overflow", OVERFLOW),
    ("comm_counters", COMM_COUNTERS),
    ("shrinking_schedule", SHRINKING),
    ("preprocess_bucketed", PREPROCESS_BUCKETED),
    ("preprocess_peak_memory", PREPROCESS_PEAK_MEMORY),
    ("ghost_cache", GHOST_CACHE),
    ("ghost_limit_fallback", GHOST_LIMIT)])
def test_sharded_internals(name, script):
    out = run_multidevice(script, ndev=8, timeout=900)
    assert "OK" in out
