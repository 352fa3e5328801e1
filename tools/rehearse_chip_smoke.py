#!/usr/bin/env python
"""Cold-compile rehearsal of one ``chip_smoke.py`` phase, without a chip.

Runs the phase on the CPU (four virtual devices), records every engine
program it compiles — the sharded engine's prep, ghost-setup, round,
flat, planned and batched programs, the replicated engine's program and
the static engines — with the shapes it was called on, then compiles
each one again for a described TPU v5e (``v5e:2x2``) on the same mesh
shape.  It prints one line per program (TPU compile seconds on this
host, temp bytes, sorts in the HLO) and their sum: the compile time the
phase's cold run pays on the chip host, give or take that host's speed.
Nothing runs on a TPU, so no line is a device time.

    PYTHONPATH=src JAX_PLATFORMS=cpu python tools/rehearse_chip_smoke.py \\
        --phase four_chips --size 262144

``--size`` is the phase's size argument (``n``; ``scale`` for
``static``).  Small programs (casts, slices) are not recorded.  With
``--no-run`` the ``static`` phase is not run on the CPU: its graph is
generated and its two programs compiled at that graph's shape, so a
full-size phase A is rehearsed without a full-size CPU solve.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from functools import partial

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

import chip_smoke  # noqa: E402

PHASES = {"static": chip_smoke.phase_static,
          "sharded": chip_smoke.phase_sharded,
          "pallas": chip_smoke.phase_pallas,
          "gateway": chip_smoke.phase_gateway,
          "four_chips": chip_smoke.phase_four_chips}
SIZE_ARG = {"static": "scale"}


class Recorder:
    """Wraps program factories and jitted entry points; remembers each
    distinct (program, argument shapes) the phase calls."""

    def __init__(self):
        # key -> (label, rebuild(to_tpu) -> (lower, mesh), arguments)
        self.programs = {}

    def _note(self, key, label, rebuild, args):
        if key not in self.programs:
            self.programs[key] = (label, rebuild, [
                (a.shape, a.dtype, getattr(getattr(a, "sharding", None),
                                           "spec", None))
                if isinstance(a, jax.Array) else a for a in args])

    def factory(self, module, name):
        orig = getattr(module, name)

        def build(*a, **k):
            fn = orig(*a, **k)

            def rebuild(to_tpu):
                mesh = next(x for x in (*a, *k.values())
                            if isinstance(x, Mesh))
                tmesh = to_tpu(mesh)

                def swap(x):
                    return tmesh if x is mesh else x
                return (orig(*map(swap, a),
                             **{key: swap(x) for key, x in k.items()}
                             ).lower, tmesh)

            def call(*args):
                self._note((id(fn), _avals(args)), name, rebuild, args)
                return fn(*args)
            return call
        setattr(module, name, build)

    def jitted(self, module, name):
        orig = getattr(module, name)

        def call(*args, **static):
            self._note((name, _avals(args), tuple(static.items())), name,
                       lambda to_tpu: (partial(orig.lower, **static),
                                       None), args)
            return orig(*args, **static)
        setattr(module, name, call)


def _avals(args):
    return tuple((a.shape, str(a.dtype)) if isinstance(a, jax.Array)
                 else a for a in args)


def static_shapes(rec: Recorder, scale: int, seed: int) -> None:
    """Record phase A's programs at its graph's shape, solving nothing."""
    from repro.core import boruvka, filter_boruvka
    from repro.data import generators
    u, _, _, n = generators.rmat(scale, 16 << scale, seed)
    edge = jax.ShapeDtypeStruct((len(u),), np.int32)
    weight = jax.ShapeDtypeStruct((len(u),), np.float32)
    args = [(s.shape, s.dtype, None) for s in (edge, edge, weight)] + [n]
    for fn, static in ((boruvka.boruvka_msf, {}),
                       (filter_boruvka.filter_boruvka_msf,
                        {"num_buckets": 8})):
        rec.programs[fn.__name__] = (
            fn.__name__,
            lambda to_tpu, f=fn, k=static: (partial(f.lower, **k), None),
            args)


def rehearse(phase: str, size: int, seed: int, no_run: bool) -> float:
    from jax.experimental import topologies
    from repro.core import distributed, distributed_sharded, mst, verify

    rec = Recorder()
    for name in dir(distributed_sharded):
        if name.startswith("_build_") and name.endswith("_fn"):
            rec.factory(distributed_sharded, name)
    rec.factory(distributed, "_build_msf_fn")
    rec.factory(verify, "_build_verify_fn")
    rec.jitted(mst, "boruvka_msf")
    rec.jitted(mst, "filter_boruvka_msf")
    chip_smoke.peak_bytes = lambda device: 0  # the CPU keeps no stats

    t = time.perf_counter()
    if no_run:
        static_shapes(rec, size, seed)
    else:
        PHASES[phase](seed, jax.devices(),
                      **{SIZE_ARG.get(phase, "n"): size})
    print(f"phase {phase} size={size} ran on the CPU in "
          f"{time.perf_counter() - t} s; {len(rec.programs)} programs")

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")

    def tpu_mesh(m: Mesh) -> Mesh:
        devs = np.array(topo.devices[:m.devices.size])
        return Mesh(devs.reshape(m.devices.shape), m.axis_names)

    total = 0.0
    for label, rebuild, args in rec.programs.values():
        lower, tmesh = rebuild(tpu_mesh)
        specs = []
        for a in args:
            if not isinstance(a, tuple):
                specs.append(a)  # a static argument
                continue
            shape, dtype, spec = a
            sh = (NamedSharding(tmesh, spec if spec is not None else P())
                  if tmesh is not None else
                  jax.sharding.SingleDeviceSharding(topo.devices[0]))
            specs.append(jax.ShapeDtypeStruct(shape, dtype, sharding=sh))
        t = time.perf_counter()
        compiled = lower(*specs).compile()
        dt = time.perf_counter() - t
        total += dt
        temp = compiled.memory_analysis().temp_size_in_bytes
        sorts = compiled.as_text().count(" sort(")
        shapes = [s.shape for s in specs if hasattr(s, "shape")]
        print(f"  {label} {shapes[:1]}: v5e compile {dt} s, temp "
              f"{temp} B, {sorts} sorts", flush=True)
    print(f"phase {phase} size={size}: {len(rec.programs)} programs, "
          f"v5e cold compile sum {total} s (rehearsal, CPU host)")
    return total


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--phase", choices=sorted(PHASES), required=True)
    ap.add_argument("--size", type=int, required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-run", action="store_true",
                    help="static only: compile at the graph's shape")
    args = ap.parse_args()
    if args.no_run and args.phase != "static":
        ap.error("--no-run applies to --phase static only")
    rehearse(args.phase, args.size, args.seed, args.no_run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
