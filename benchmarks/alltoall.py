"""Paper Fig. 2 analog: two-level grid all-to-all vs direct all-to-all.

The paper's win is startup cost: p-1 peers direct vs 2(sqrt(p)-1) via the
grid.  On virtual CPU devices wall time is not a network measurement, so
the primary derived metric is structural, from the compiled HLO: the
number of all-to-all ops and their replica-group sizes (= peer count per
exchange).  Runs in a subprocess with 16 virtual devices.
"""
from __future__ import annotations

import os
import re
import subprocess
import sys
import textwrap

from benchmarks.common import emit

SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
import jax, jax.numpy as jnp, numpy as np, json, time
from jax.sharding import Mesh, PartitionSpec as P
from repro.compat import shard_map
from repro.comm.grid_alltoall import all_to_all_nd

devices = np.array(jax.devices()).reshape(4, 4)
mesh = Mesh(devices, ("row", "col"))
p = 16
x = jnp.arange(p * p * 64, dtype=jnp.float32).reshape(p * p, 64)

out = {}
for sched in ("direct", "grid"):
    f = jax.jit(shard_map(lambda t: all_to_all_nd(t, ("row", "col"), sched),
                mesh=mesh, in_specs=P(("row", "col")),
                out_specs=P(("row", "col"))))
    comp = f.lower(x).compile()
    txt = comp.as_text()
    groups = []
    for line in txt.splitlines():
        if "all-to-all" in line and "=" in line:
            m = [g for g in line.split("replica_groups=")[-1:]]
            import re as _re
            mm = _re.search(r"replica_groups=\\[(\\d+),(\\d+)\\]", line)
            if mm:
                groups.append(int(mm.group(2)))
            else:
                mm = _re.search(r"replica_groups=\\{\\{([0-9,]+)\\}", line)
                if mm:
                    groups.append(len(mm.group(1).split(",")))
    f(x).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(10):
        f(x).block_until_ready()
    us = (time.perf_counter() - t0) / 10 * 1e6
    out[sched] = {"n_a2a": len(groups), "peer_counts": groups, "us": us}
print(json.dumps(out))
"""


def run() -> None:
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    import json
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for sched, st in out.items():
        peers = max(st["peer_counts"] or [1])
        emit(f"alltoall/{sched}", st["us"],
             f"n_a2a={st['n_a2a']};max_group={peers};"
             f"startup_proxy={st['n_a2a'] * (peers - 1)}")


if __name__ == "__main__":
    run()
