import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")
"""Particle-engine dry-run: the paper's own system on the production mesh.

Extends deliverable (e) beyond the LM cells: the distributed cell-list
engine (shard_map + Z-plane halo exchange) is lowered and compiled for the
single-pod 16×16 and multi-pod 2×16×16 meshes at cluster-scale particle
counts. The grid splits along Z over ("pod","data") — 32 Z-slabs for the
multi-pod mesh, pod boundary = one ghost-plane exchange per step, exactly
the paper's ghost cells stretched across the slow links.

  PYTHONPATH=src python -m repro.launch.particle_dryrun [--multi-pod]
"""

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import Domain, ParticleState, make_lennard_jones
from ..core import api as A, bounds
from . import roofline as RL
from .mesh import make_production_mesh

OUT = pathlib.Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def run(multi_pod: bool, division: int = 128, ppc: int = 16,
        m_c: int = 32) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    # fold the whole dp hierarchy into the Z split: 16 (data) or 32 (pod*data)
    axis = ("pod", "data") if multi_pod else ("data",)

    # shard_map needs a single named axis; reuse "data" and put pods on Z too
    # by splitting over the flattened axis tuple via a wrapper mesh axis.
    domain = Domain.cubic(division, cutoff=1.0, periodic=True)
    n = division ** 3 * ppc
    kernel = make_lennard_jones()

    n_shards = int(mesh.shape["data"])
    # uniform benchmark load: the analytic per-shard capacity with the
    # usual slack + alignment (no positions exist at dry-run time)
    cap = bounds.size(n // n_shards, bounds.SLACK["shard_cap"])
    p = A.plan(domain, kernel, m_c=m_c, strategy="xpencil", backend="halo",
               mesh=mesh, shard_axis="data", n_shards=n_shards,
               shard_cap=cap)
    fn = jax.jit(A._impl(p))
    spec = ParticleState(jax.ShapeDtypeStruct((n, 3), jnp.float32))
    t0 = time.time()
    lowered = fn.lower(spec)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = RL.collective_bytes(compiled.as_text())
    mem = {}
    try:
        m = compiled.memory_analysis()
        mem = {k: float(getattr(m, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes") if getattr(m, k, None) is not None}
    except Exception:
        pass

    # roofline: interactions ~ N * 27ppc * pi/6ish; paper kernel = 21 FLOP
    inter = n * ppc * 27 * 0.52
    rec = {
        "arch": "particle-xpencil", "shape": f"d{division}_ppc{ppc}",
        "mesh": mesh_name, "n_devices": mesh.size,
        "particles": n, "m_c": m_c,
        "compile_seconds": round(time.time() - t0, 1),
        "memory_analysis": mem,
        "cost_analysis": {"flops": float(cost.get("flops", 0)),
                          "bytes accessed":
                          float(cost.get("bytes accessed", 0))},
        "roofline": RL.analyze(cost, compiled.as_text(),
                               inter * 21 / mesh.size).to_dict(),
    }
    out = OUT / f"particle-xpencil__d{division}_ppc{ppc}__{mesh_name}.json"
    OUT.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(rec, indent=2))
    r = rec["roofline"]
    print(f"[particle-dryrun] OK {mesh_name}: N={n:,} "
          f"compile={rec['compile_seconds']}s flops/dev={r['flops']:.3e} "
          f"coll/dev={r['coll_bytes']:.3e}B dominant={r['dominant']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true")
    ap.add_argument("--division", type=int, default=128)
    ap.add_argument("--ppc", type=int, default=16)
    args = ap.parse_args()
    if args.both:
        run(False, args.division, args.ppc)
        run(True, args.division, args.ppc)
    else:
        run(args.multi_pod, args.division, args.ppc)


if __name__ == "__main__":
    main()
