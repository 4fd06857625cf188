"""Halo figure: weak scaling of the distributed backend over Z-slab shards.

Weak scaling holds the *per-shard* problem fixed and grows the domain with
the shard count: at ``n_shards = s`` the grid is ``division x division x
(division * s)`` cells with ``ppc`` particles per cell, so every shard owns
the same ``division^3 * ppc`` particles and the same slab of pencils. Ideal
weak scaling keeps time-per-step flat (efficiency ``t(1) / t(s) = 1``); the
gap is the ghost-exchange plus partition overhead the distributed engine
pays for crossing chips.

Before anything is timed, each case's halo forces are checked against the
single-device reference schedule on the same positions — a benchmark that
silently drifted from the oracle would be worse than no benchmark.

On emulated host devices (``--devices N`` asks the CPU backend for N
devices through ``XLA_FLAGS`` before JAX is imported) all shards share
one physical core, so absolute efficiency is pessimistic — the committed
``benchmarks/BENCH_halo.json`` is the *record structure* the perf
trajectory tracks per commit, not a hardware claim. On a real mesh the
same module runs unchanged.

``--json PATH`` writes BENCH_*.json perf records (case, strategy, backend,
us_per_call, reps, platform + n_shards/n_particles/weak_efficiency extras).
"""

from __future__ import annotations

import argparse
import os
import re
import sys
from typing import List, Optional, Sequence

import numpy as np


def _viable_shard_counts(device_count: int) -> List[int]:
    """1, 2, 4, ... up to the device count (weak scaling doubles shards;
    the grid is built per case as ``division^2 x (division * s)`` cells,
    so every count divides its own nz by construction)."""
    out, s = [], 1
    while s <= device_count:
        out.append(s)
        s *= 2
    return out


def run(csv: bool = True, json_path: Optional[str] = None,
        record_sink: Optional[List[dict]] = None, division: int = 6,
        ppc: int = 4, seed: int = 0, strategy: str = "xpencil",
        shard_counts: Optional[Sequence[int]] = None,
        rtol: float = 3e-4) -> List[dict]:
    import jax

    from repro.core import (Domain, ParticleState, make_lennard_jones,
                            plan)

    from .common import bench_record, time_fn, write_bench_json

    kern = make_lennard_jones()
    if shard_counts is None:
        shard_counts = _viable_shard_counts(jax.device_count())
    rows: List[dict] = []
    records: List[dict] = []
    if csv:
        print("name,us_per_call,derived")
    t1 = None
    for ns in shard_counts:
        dom = Domain(box=(float(division), float(division),
                          float(division * ns)),
                     ncells=(division, division, division * ns),
                     cutoff=1.0, periodic=True)
        n = division ** 3 * ns * ppc
        pos = dom.sample_uniform(jax.random.PRNGKey(seed), n)
        state = ParticleState(pos)
        p_halo = plan(dom, kern, positions=pos, strategy=strategy,
                      backend="halo", n_shards=ns)

        # correctness gate: the distributed result must match the
        # single-device schedule on the scene it is about to be timed on
        p_ref = plan(dom, kern, m_c=p_halo.m_c, strategy=strategy)
        f_r, _ = p_ref.execute(state)
        f_h, _ = p_halo.execute(state)
        scale = max(float(np.abs(np.asarray(f_r)).max()), 1.0)
        err = float(np.abs(np.asarray(f_h) - np.asarray(f_r)).max())
        if err > rtol * scale:
            print(f"fig_halo: ns={ns}: halo result DIVERGED from the "
                  f"reference (|dF|={err:.2e}) — not timing a wrong "
                  "answer", file=sys.stderr)
            continue

        t, r = time_fn(p_halo.execute, state)
        if ns == 1:
            t1 = t
        # weak efficiency is defined as t(1)/t(s): without a timed
        # single-shard baseline the ratio would silently mean something
        # else, so it is omitted rather than rebased
        eff = t1 / t if t1 is not None else None
        row = {"n_shards": ns, "n_particles": n, "ncells": dom.ncells,
               "shard_cap": p_halo.shard_cap, "seconds": t,
               "weak_efficiency": eff}
        rows.append(row)
        rec = dict(bench_record(f"halo/weak/ns{ns}", strategy, "halo",
                                t, r),
                   n_shards=ns, n_particles=n)
        if eff is not None:
            rec["weak_efficiency"] = eff
        records.append(rec)
        if csv:
            derived = f"N={n}"
            if eff is not None:
                derived += f";efficiency={eff:.3f}"
            print(f"halo/weak/{strategy}/ns{ns},{t * 1e6:.1f},{derived}")
    if json_path:
        write_bench_json(json_path, records)
    if record_sink is not None:
        record_sink.extend(records)
    return rows


def host_device_flags(devices: int, xla_flags: str) -> str:
    """``XLA_FLAGS`` asking the CPU backend for ``devices`` emulated
    devices (any earlier count is replaced).

    XLA reads the flag once, when JAX starts, so it is set before JAX is
    imported, in this process: nothing is re-executed, and on a TPU host
    the flag only shapes the unused CPU backend while the chips are the
    devices."""
    kept = re.sub(r"\s*--xla_force_host_platform_device_count=\S*", "",
                  xla_flags).strip()
    return (kept + f" --xla_force_host_platform_device_count={devices}"
            ).strip()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="shard counts up to this many devices (emulated "
                         "host devices on the CPU; 0 = the devices visible)")
    ap.add_argument("--division", type=int, default=6,
                    help="cells per axis of one shard's slab")
    ap.add_argument("--ppc", type=int, default=4, help="particles per cell")
    ap.add_argument("--strategy", default="xpencil")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write BENCH_*.json perf records to PATH")
    args = ap.parse_args(argv)

    shard_counts = None
    if args.devices:
        if "jax" not in sys.modules:
            os.environ["XLA_FLAGS"] = host_device_flags(
                args.devices, os.environ.get("XLA_FLAGS", ""))
        import jax

        if jax.device_count() < args.devices:
            raise SystemExit(
                f"fig_halo: --devices {args.devices} but "
                f"{jax.device_count()} {jax.default_backend()} device(s) "
                "are visible (JAX was started before the flag could be set)")
        # more devices visible than asked for: honour the request anyway
        # by capping the sweep instead of silently using them all
        shard_counts = _viable_shard_counts(args.devices)
    run(division=args.division, ppc=args.ppc, strategy=args.strategy,
        shard_counts=shard_counts, json_path=args.json)


if __name__ == "__main__":
    main()
