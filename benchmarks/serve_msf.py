"""MSF serving gateway benchmark (ISSUE 6): throughput / latency /
plan-cache behaviour under a synthetic gnm + rgg2d traffic mix, and the
batched-vs-per-request dispatch comparison.

The gateway (``serve/msf_gateway.py``) serves every request through a
plan-LRU + continuous-batching loop: same-shape requests ride one
compiled planned program vmapped over a batch axis.  This benchmark
reports, from one subprocess run on 8 virtual devices:

  * requests/s and p50/p99 request latency over the full mix,
  * plan-cache hit rate, evictions, replan + drift-refresh counts,
  * per-request wall time of one **batched** planned dispatch vs the
    same B graphs dispatched **one by one** through the single-graph
    planned program (both warm) — the vmap win the gateway banks on.

Every served forest is checked bit-identical to the Kruskal oracle
in-script (the acceptance bar), in smoke and full mode alike.  Full
mode merges a ``serve_gateway`` section into ``BENCH_sharded_comm.json``
(preserving the other sections); ``--smoke`` additionally asserts the
CI acceptance floor: cache hit rate > 0.5 on the repeated-shape mix and
a batched dispatch that beats per-request dispatch — asserted on the
deterministic per-request collective-invocation count (exactly B-fold
fewer, the alpha-cost win that survives virtual-device timing noise)
with a loose wall-clock bound alongside.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, json, time
from jax.sharding import Mesh
from repro.core import oracle
from repro.core.distributed import build_dist_graph
from repro.core.distributed_sharded import (execute_plan,
                                            execute_plan_batched)
from repro.launch.serve_msf import make_traffic, percentile
from repro.serve.msf_gateway import MSFGateway

SMOKE = os.environ.get("SERVE_MSF_SMOKE") == "1"
p = 8
mesh = Mesh(np.array(jax.devices()), ("data",))
out = {}

# --- the serving loop: traffic mix through the gateway ------------------
requests = 24 if SMOKE else 100
sizes = (256,) if SMOKE else (512, 1024)
gw = MSFGateway(mesh, cache_size=8, batch_slots=4, pad_margin=0.25)
reqs = make_traffic(("gnm", "rgg2d"), sizes, requests, seed=0)
for r in reqs:
    gw.submit(r)
t0 = time.perf_counter()
gw.run()
wall = time.perf_counter() - t0
assert all(r.done for r in reqs)

# acceptance: every served forest bit-identical to the Kruskal oracle
for r in reqs:
    kmask, kweight = oracle.kruskal(r.u, r.v, r.w, r.n)
    assert np.array_equal(r.edges, np.nonzero(kmask)[0]), (
        r.rid, r.family, r.n, "served forest != oracle")
    assert abs(r.weight - kweight) < 1e-3 * max(1.0, kweight), r.rid

lat = sorted(r.latency for r in reqs)
s = gw.stats
out["traffic"] = {
    "requests": len(reqs), "wall_s": wall,
    "requests_per_s": len(reqs) / wall,
    "p50_s": percentile(lat, 0.50), "p99_s": percentile(lat, 0.99),
    "batches": s.batches, "hits": s.hits, "misses": s.misses,
    "hit_rate": s.hit_rate, "evictions": s.evictions,
    "replans": s.replans, "replan_rate": s.replan_rate,
    "refreshes": s.refreshes, "oracle_checked": len(reqs),
}

# --- batched vs per-request planned dispatch (warm, same graphs) --------
# B same-shape graphs through (a) one vmapped batched dispatch and
# (b) B sequential single-graph planned dispatches; strict replay
# (replan=False) so both paths run exactly the compiled program.  The
# batch is B replicas of the graph the plan was measured on: a measured
# plan always fits its own graph (capacities AND round count), so the
# strict-mode comparison can never hit a structural misfit — a
# weight-shuffled batchmate can legitimately need more rounds than the
# measured trajectory (seen at n=512) and belongs to the replan path
# the traffic section above exercises, not this timing microbenchmark;
# dispatch cost is independent of the weight values.
# Timing is best-of-N (the standard floor estimator for dispatch
# overhead; single runs on virtual devices are +-10% noisy).  The
# deterministic metric alongside it: the vmapped program issues the
# SAME number of collective invocations as one unbatched solve, so
# per-request all-to-all invocations — the alpha term the paper's
# grid schedule attacks — drop exactly B-fold.
from repro.core.distributed_sharded import plan_sharded_msf
from repro.data import generators
B = 8
nb = 256 if SMOKE else 512
u, v, w, nb = generators.generate("gnm", nb, avg_degree=8.0, seed=3)
g0, cap = build_dist_graph(u, v, w, nb, p)
plan = plan_sharded_msf(g0, nb, mesh, axis_names=("data",)).pad(0.5)
graphs = [g0] * B

# stack once (the gateway stacks at admission, outside the hot dispatch)
from repro.core.distributed import DistGraph
import jax.numpy as jnp
stacked = DistGraph(jnp.stack([g.u for g in graphs]),
                    jnp.stack([g.v for g in graphs]),
                    jnp.stack([g.w for g in graphs]),
                    jnp.stack([g.eid for g in graphs]))

def run_batched():
    res, bad = execute_plan_batched(stacked, nb, mesh, plan,
                                    axis_names=("data",), replan=False,
                                    stack=False)
    jax.block_until_ready(res[0][0])
    return res

def run_seq():
    outs = [execute_plan(g, nb, mesh, plan, axis_names=("data",),
                         replan=False) for g in graphs]
    jax.block_until_ready(outs[-1][0])
    return outs

bres = run_batched(); sres = run_seq()      # warmup/compile
for i in range(B):                          # and bit-identity across paths
    assert np.array_equal(np.asarray(bres[i][0]), np.asarray(sres[i][0])), i
# per-request collective invocations (CommStats.calls is the program's
# invocation count: shared across the batch in the vmapped run)
calls_batched = float(np.asarray(bres[0][5].calls)) / B
calls_seq = float(np.asarray(sres[0][5].calls))
iters = 3 if SMOKE else 5

def best_of(fn):
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best / B * 1e6

us_batched = best_of(run_batched)
us_seq = best_of(run_seq)
out["dispatch"] = {
    "batch": B, "n": nb,
    "us_per_request_batched": us_batched,
    "us_per_request_sequential": us_seq,
    "batched_speedup": us_seq / max(us_batched, 1e-9),
    "a2a_calls_per_request_batched": calls_batched,
    "a2a_calls_per_request_sequential": calls_seq,
    "a2a_invocation_shrink": calls_seq / max(calls_batched, 1e-9),
}

# --- recovery (ISSUE 9): checkpoint overhead, resume savings, elastic ---
from repro.comm import faults as _faults
from repro.core.distributed_sharded import (DEFAULT_CKPT_EVERY,
                                            distributed_sharded_msf)
nr = 256 if SMOKE else 512
u, v, w, nr = generators.generate("gnm", nr, avg_degree=8.0, seed=11)
gr, capr = build_dist_graph(u, v, w, nr, p)
planr = plan_sharded_msf(gr, nr, mesh, axis_names=("data",))
R = len(planr.rounds)

def best(fn):
    b = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        b = min(b, time.perf_counter() - t0)
    return b

# warm both programs (plain one-program replay vs segmented), then the
# acceptance number: warm wall overhead of the certify+snapshot barrier
# at the default cadence, plus a dense-cadence (every 2 rounds) worst
# case for context
cks_warm = []
execute_plan(gr, nr, mesh, planr, replan=False)
execute_plan(gr, nr, mesh, planr, replan=False,
             ckpt_every=DEFAULT_CKPT_EVERY, ckpt_out=cks_warm)
execute_plan(gr, nr, mesh, planr, replan=False, ckpt_every=2,
             ckpt_out=[])
t_plain = best(lambda: jax.block_until_ready(
    execute_plan(gr, nr, mesh, planr, replan=False)[0]))
t_ck = best(lambda: jax.block_until_ready(
    execute_plan(gr, nr, mesh, planr, replan=False,
                 ckpt_every=DEFAULT_CKPT_EVERY, ckpt_out=[])[0]))
t_ck2 = best(lambda: jax.block_until_ready(
    execute_plan(gr, nr, mesh, planr, replan=False, ckpt_every=2,
                 ckpt_out=[])[0]))

# resume savings: abort the driver past a dense cadence, resume from
# the last certified checkpoint, compare against a from-scratch solve
base_r = distributed_sharded_msf(gr, nr, mesh)
cks = []
try:
    with _faults.inject(_faults.FaultPlan(seed=0, specs=(
            _faults.FaultSpec(kind="abort", site="minedges",
                              rounds=(3,)),))):
        distributed_sharded_msf(gr, nr, mesh, ckpt_every=2, ckpt_out=cks)
except _faults.ShardAbort:
    pass
assert cks, "no certified checkpoint before the injected abort"
ck = cks[-1]
res_r = distributed_sharded_msf(gr, nr, mesh, resume_from=ck)
assert np.array_equal(np.asarray(res_r[0]), np.asarray(base_r[0]))
t_resume = best(lambda: jax.block_until_ready(
    distributed_sharded_msf(gr, nr, mesh, resume_from=ck)[0]))
t_scratch = best(lambda: jax.block_until_ready(
    distributed_sharded_msf(gr, nr, mesh)[0]))

# elastic restore: the same checkpoint re-keyed onto a p/2 sub-mesh vs
# solving from scratch on that mesh (wall ratio < 1 means the restore
# beats a full re-run even after losing half the shards)
p2 = p // 2
mesh2 = Mesh(np.array(jax.devices()[:p2]), ("data",))
g2, cap2 = build_dist_graph(u, v, w, nr, p2)
ck2 = ck.remap(p2, cap2, np.asarray(g2.u), np.asarray(g2.v),
               np.asarray(g2.eid))
res_el = distributed_sharded_msf(g2, nr, mesh2, resume_from=ck2)
res_sc = distributed_sharded_msf(g2, nr, mesh2)
eid2 = np.asarray(g2.eid)
assert np.array_equal(np.unique(eid2[np.asarray(res_el[0])]),
                      np.unique(eid2[np.asarray(res_sc[0])]))
t_elastic = best(lambda: jax.block_until_ready(
    distributed_sharded_msf(g2, nr, mesh2, resume_from=ck2)[0]))
t_scratch2 = best(lambda: jax.block_until_ready(
    distributed_sharded_msf(g2, nr, mesh2)[0]))

out["recovery"] = {
    "n": nr, "plan_rounds": R,
    "ckpt_every_default": DEFAULT_CKPT_EVERY,
    "checkpoints_at_default_cadence": len(cks_warm),
    "t_plain_ms": t_plain * 1e3, "t_ckpt_ms": t_ck * 1e3,
    "ckpt_overhead_pct": (t_ck / max(t_plain, 1e-9) - 1.0) * 100.0,
    "ckpt_overhead_dense_pct":
        (t_ck2 / max(t_plain, 1e-9) - 1.0) * 100.0,
    "resume": {
        "rounds_total": int(base_r[5].rounds),
        "ckpt_round": ck.round_index,
        "rounds_saved": ck.round_index,
        "t_resume_ms": t_resume * 1e3,
        "t_scratch_ms": t_scratch * 1e3,
        "resume_wall_ratio": t_resume / max(t_scratch, 1e-9),
    },
    "elastic": {
        "p_from": p, "p_to": p2,
        "t_elastic_resume_ms": t_elastic * 1e3,
        "t_scratch_p2_ms": t_scratch2 * 1e3,
        "elastic_wall_ratio": t_elastic / max(t_scratch2, 1e-9),
        "oracle_identical": True,
    },
}
print(json.dumps(out))
"""


def _run_script(smoke: bool) -> dict:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    if smoke:
        env["SERVE_MSF_SMOKE"] = "1"
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run(smoke: bool = False) -> None:
    out = _run_script(smoke)
    t = out["traffic"]
    emit("serve_msf/traffic", t["wall_s"] * 1e6,
         f"req_per_s={t['requests_per_s']:.2f};"
         f"p50_s={t['p50_s']:.3f};p99_s={t['p99_s']:.3f};"
         f"hit_rate={t['hit_rate']:.2f};replans={t['replans']};"
         f"refreshes={t['refreshes']};oracle_ok={t['oracle_checked']}")
    d = out["dispatch"]
    emit("serve_msf/dispatch", d["us_per_request_batched"],
         f"us_seq={d['us_per_request_sequential']:.0f};"
         f"batched_speedup={d['batched_speedup']:.2f}x;"
         f"a2a_shrink={d['a2a_invocation_shrink']:.1f}x;B={d['batch']}")
    r = out["recovery"]
    emit("serve_msf/recovery", r["t_ckpt_ms"] * 1e3,
         f"ckpt_overhead_pct={r['ckpt_overhead_pct']:.1f};"
         f"rounds_saved={r['resume']['rounds_saved']};"
         f"resume_ratio={r['resume']['resume_wall_ratio']:.2f};"
         f"elastic_ratio={r['elastic']['elastic_wall_ratio']:.2f}")
    if smoke:
        # CI acceptance (ISSUE 6): repeated-shape traffic must actually
        # reuse plans; the vmapped batch must beat per-request dispatch
        # on the deterministic metric (per-request collective
        # invocations shrink exactly B-fold — on one host, wall time
        # only bounds loosely because all 8 "devices" share the CPU;
        # oracle identity is asserted in-script)
        assert t["hit_rate"] > 0.5, t
        assert t["oracle_checked"] == t["requests"], t
        assert d["a2a_invocation_shrink"] >= d["batch"] * 0.999, d
        assert d["batched_speedup"] >= 0.8, d
        return
    # merge the serve_gateway section into the tracked BENCH json,
    # preserving the sections written by benchmarks/sharded_scaling.py
    path = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        "BENCH_sharded_comm.json"))
    # acceptance (ISSUE 9): the certify+snapshot barrier at the default
    # cadence must cost < 15% of the warm plain replay
    assert out["recovery"]["ckpt_overhead_pct"] < 15.0, out["recovery"]
    bench = {}
    if os.path.exists(path):
        with open(path) as f:
            bench = json.load(f)
    bench["serve_gateway"] = {k: v for k, v in out.items()
                              if k != "recovery"}
    bench["recovery"] = out["recovery"]
    with open(path, "w") as f:
        json.dump(bench, f, indent=2, sort_keys=True)


if __name__ == "__main__":
    run(smoke="--smoke" in sys.argv[1:])
    print("serve_msf: OK")
