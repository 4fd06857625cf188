"""Proof that the particle engine runs on a TPU, through its normal entry
points: ``plan(...)`` -> ``execute`` / ``execute_checked`` and
``plan.trajectory``, with the Pallas kernels compiled for the chip.

    python chip_smoke.py             # one chip: every pallas kernel + MD
    python chip_smoke.py --chips 4   # the halo path over four chips only

The scene is the paper's few-particles-per-cell regime at a chip-filling
size: a periodic Lennard-Jones liquid in an 80^3-cell box, 2 particles per
cell (N = 1,024,000) on a jittered lattice from ``--seed``, so no two LJ
cores overlap. Each one-chip phase compiles one ``backend="pallas"`` plan
(``interpret=False``: the kernels run natively or not at all), checks the
compiled program holds a ``tpu_custom_call``, runs it guarded
(``execute_checked`` must report ``"ok"``: no step down to the reference
backend), and compares it with the reference backend on the same state:
``max|dF| / max|F| <= 3e-4``, the potential likewise. The reference itself
is checked against a plain float32 all-pairs sum over every source for
4,096 seeded targets. The trajectory phase runs 200 velocity-Verlet steps
on the pallas xpencil plan and holds status, rung and energy drift.

Every phase prints one JSON line (N, m_c, the kernel's VMEM/SMEM needs,
compile and steady seconds, parity errors, bitwise equality with the
reference): informational, not metrics. The last line is the verdict:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Any failure exits non-zero before that line, and so does a run that
finds no TPU: nothing falls back to the CPU.

Compiled programs are cached in ``JAX_COMPILATION_CACHE_DIR`` when it is
set, else in ``.jax_cache/`` next to this file.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DIVISION = 80            # cells per axis; cell width = cutoff = 1
PPC = 2                  # particles per cell
M_C = 16                 # slots per cell of the dense planes
TRAJ_M_C = 8             # the trajectory's base bound: its skin grid
                         # (cells 1.25^3 wide) scales it to 16 slots
SIGMA, SOFTENING = 0.25, 1e-4
JITTER = 0.1             # lattice jitter per axis (min spacing 0.3 > sigma)
VEL_SCALE = 0.05
TOL = 3e-4               # max|dF| / max|F|, and the same for the potential
N_ORACLE = 4096          # all-pairs targets checking the reference
STEPS, DT, SKIN, SEGMENT = 200, 1e-3, 0.25, 50
MAX_DRIFT = 0.05

# every backend="pallas" variant of the one-shot phase (plan kwargs)
VARIANTS = {
    "xpencil_dense": {"strategy": "xpencil"},
    "xpencil_compact": {"strategy": "xpencil", "compact": True},
    "xpencil_packed": {"strategy": "xpencil", "layout": "packed"},
    "allin": {"strategy": "allin"},
    "sfc": {"strategy": "cell_dense", "layout": "sfc"},
}
# the variants plan() may refuse for the chip's memories; any other
# refusal fails the smoke
REFUSABLE = {"sfc", "xpencil_packed"}


class SmokeFailure(AssertionError):
    """A phase produced a wrong, degraded or non-native result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(**row) -> None:
    print(json.dumps(row), flush=True)


# --------------------------------------------------------------------------
# the scene
# --------------------------------------------------------------------------

def lattice(division: int, seed: int):
    """``PPC`` particles per unit cell of a ``division^3`` box, split along
    X and jittered per axis by up to ``JITTER`` (generated on the device)
    -> (positions (N, 3), velocities (N, 3)), float32."""
    import jax
    import jax.numpy as jnp

    n = division ** 3 * PPC
    i = jnp.arange(n, dtype=jnp.int32)
    cell, k = i // PPC, i % PPC
    cx, cy, cz = cell % division, (cell // division) % division, \
        cell // (division * division)
    base = jnp.stack([cx + (k + 0.5) / PPC, cy + 0.5, cz + 0.5],
                     axis=-1).astype(jnp.float32)
    kj, kv = jax.random.split(jax.random.PRNGKey(seed))
    pos = base + jax.random.uniform(kj, (n, 3), jnp.float32, -JITTER, JITTER)
    vel = VEL_SCALE * jax.random.normal(kv, (n, 3), jnp.float32)
    return pos, vel


def all_pairs(domain, kernel, positions, targets, chunk: int = 16):
    """Plain float32 sum over every source for the ``targets`` rows, with
    periodic minimum images -> (forces (T, 3), potential (T,))."""
    import jax
    import jax.numpy as jnp

    box = jnp.asarray(domain.box, jnp.float32)
    src = [positions[:, a] for a in range(3)]
    cutoff2 = float(domain.cutoff) ** 2

    def rows(t):                               # (chunk, 3) targets
        d = [t[:, a:a + 1] - src[a][None, :] for a in range(3)]
        d = [x - box[a] * jnp.round(x / box[a]) for a, x in enumerate(d)]
        r2 = d[0] * d[0] + d[1] * d[1] + d[2] * d[2]
        near = (r2 < cutoff2) & (r2 > 0.0)
        r2s = jnp.where(near, r2, 1.0)
        s = jnp.where(near, kernel.coeff(r2s), 0.0)
        u = jnp.where(near, kernel.potential(r2s), 0.0)
        return (jnp.stack([(s * x).sum(1) for x in d], axis=-1), u.sum(1))

    tgt = positions[targets].reshape(-1, chunk, 3)
    f, u = jax.jit(lambda t: jax.lax.map(rows, t))(tgt)
    return f.reshape(-1, 3), u.reshape(-1)


def rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def bitwise(a, b) -> bool:
    import numpy as np
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def _compiled(p, state):
    t0 = time.perf_counter()
    exe = p.compile(state)
    return exe, time.perf_counter() - t0


def _steady(fn, reps: int = 3) -> float:
    import jax
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _budget(p) -> dict:
    from repro.core import kernel_budget
    name, vmem, smem = kernel_budget(p)
    return {"kernel": name, "vmem_bytes": vmem, "smem_bytes": smem}


def reference_check(domain, kernel, positions, seed: int) -> dict:
    """The reference backend against the all-pairs sum on ``N_ORACLE``
    seeded targets; returns the reference results per strategy."""
    import jax
    import jax.numpy as jnp
    from repro.core import ParticleState, plan

    state = ParticleState(positions)
    p = plan(domain, kernel, m_c=M_C, strategy="xpencil",
             backend="reference")
    exe, t_compile = _compiled(p, state)
    f, u = p.execute(state)
    n = positions.shape[0]
    targets = jax.random.choice(jax.random.PRNGKey(seed + 1), n,
                                (min(N_ORACLE, n),), replace=False)
    f_o, u_o = all_pairs(domain, kernel, positions, targets)
    err_f = rel_err(f[targets], f_o)
    err_u = rel_err(u[targets], u_o)
    emit(phase="reference_vs_all_pairs", n=n, m_c=M_C, targets=len(targets),
         compile_s=t_compile, steady_s=_steady(lambda: p.execute(state)),
         rel_dF=err_f, rel_dU=err_u,
         f_max=float(jnp.abs(f).max()))
    check(bool(jnp.isfinite(f).all() & jnp.isfinite(u).all()),
          "reference: non-finite output")
    check(err_f <= TOL and err_u <= TOL,
          f"reference vs all-pairs: rel dF {err_f:.3g}, dU {err_u:.3g}")
    return {("xpencil", False): (f, u)}


def one_shot(domain, kernel, positions, refs: dict,
             interpret=False) -> None:
    """Every pallas variant through plan -> compile -> execute_checked,
    against the reference backend of the same strategy."""
    from repro.core import ParticleState, degradation_ladder, plan

    state = ParticleState(positions)
    n = positions.shape[0]
    for name, kw in VARIANTS.items():
        try:
            p = plan(domain, kernel, positions=positions, m_c=M_C,
                     backend="pallas", interpret=interpret, **kw)
        except ValueError as e:
            # only a kernel that cannot fit the chip by design may be
            # refused, and only by name of the memory it would overflow
            if name not in REFUSABLE:
                raise
            check("VMEM" in str(e) or "SMEM" in str(e),
                  f"{name}: plan refused for another reason: {e}")
            emit(phase=name, n=n, m_c=M_C, refused=str(e))
            continue
        exe, t_compile = _compiled(p, state)
        native = "tpu_custom_call" in exe.as_text()
        (f, u), report = p.execute_checked(state)
        ref_plan = degradation_ladder(p)[-1]
        key = (ref_plan.strategy, ref_plan.compact)
        if key not in refs:
            refs[key] = ref_plan.execute(state)
        f_r, u_r = refs[key]
        err_f, err_u = rel_err(f, f_r), rel_err(u, u_r)
        emit(phase=name, n=n, m_c=p.m_c, **_budget(p),
             compile_s=t_compile,
             steady_s=_steady(lambda: p.execute(state)),
             status=report.status, ladder_level=report.ladder_level,
             tpu_custom_call=native, rel_dF=err_f, rel_dU=err_u,
             bitwise_equal_reference=bitwise(f, f_r) and bitwise(u, u_r))
        check(interpret is not False or native,
              f"{name}: no tpu_custom_call in the compiled program")
        check(report.status == "ok" and report.ladder_level == 0,
              f"{name}: execute_checked reported {report.status} at rung "
              f"{report.ladder_level} ({report.faults})")
        check(err_f <= TOL and err_u <= TOL,
              f"{name}: rel dF {err_f:.3g}, dU {err_u:.3g} vs reference")


def trajectory(domain, kernel, positions, velocities, *, steps: int = STEPS,
               interpret=False) -> None:
    """``plan.trajectory`` on the pallas xpencil plan: velocity-Verlet,
    Verlet skin ``SKIN``; status, rung, finiteness and energy drift."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core import ParticleState, plan

    p = plan(domain, kernel, positions=positions, m_c=TRAJ_M_C,
             strategy="xpencil", backend="pallas", interpret=interpret)
    t0 = time.perf_counter()
    res = p.trajectory(ParticleState(positions), n_steps=steps, dt=DT,
                       velocities=velocities, skin=SKIN,
                       segment_len=min(SEGMENT, steps))
    wall = time.perf_counter() - t0
    native = bool(res.executables) and all(
        "tpu_custom_call" in exe.as_text() for exe in res.executables)
    total = np.asarray(res.traces["total"], np.float64)
    e0 = float(total[0])
    drift = float(np.abs(total - e0).max() / max(abs(e0), 1.0))
    md = res.state
    finite = bool(jnp.isfinite(md.positions).all()
                  & jnp.isfinite(md.velocities).all()
                  & jnp.isfinite(md.forces).all())
    emit(phase="trajectory", n=positions.shape[0], m_c=res.plan.m_c,
         grid=list(res.plan.domain.ncells), **_budget(res.plan),
         steps=res.steps, wall_s=wall, rebins=res.rebins,
         status=res.status, ladder_level=res.ladder_level,
         tpu_custom_call=native, energy_drift=drift, finite=finite)
    check(interpret is not False or native,
          "trajectory: a segment program holds no tpu_custom_call")
    check(res.status == "ok" and res.ladder_level == 0,
          f"trajectory: status {res.status} at rung {res.ladder_level} "
          f"({res.faults})")
    check(res.steps == steps and finite, "trajectory: state not finite")
    check(drift < MAX_DRIFT, f"trajectory: energy drift {drift:.3g}")


def four_chips(domain, kernel, positions, n_chips: int = 4,
               interpret=False) -> None:
    """The halo path over ``n_chips`` Z-slabs, reference and pallas
    xpencil inside, and ``plan.distribute`` on a ``jax.make_mesh`` mesh,
    each against the single-chip plan on the same state."""
    import jax
    from repro.core import ParticleState, plan

    state = ParticleState(positions)
    n = positions.shape[0]
    check(domain.nz % n_chips == 0, f"nz={domain.nz} % {n_chips} != 0")
    singles = {}
    for inner in ("reference", "pallas"):
        kw = {"interpret": interpret} if inner == "pallas" else {}
        singles[inner] = plan(domain, kernel, positions=positions, m_c=M_C,
                              strategy="xpencil", backend=inner, **kw)
    base = {k: p.execute(state) for k, p in singles.items()}

    cases = {
        f"halo_{inner}": (inner, plan(
            domain, kernel, positions=positions, m_c=M_C, strategy="xpencil",
            backend="halo", halo_inner=inner, n_shards=n_chips,
            interpret=interpret))
        for inner in ("reference", "pallas")}
    cases["distribute_make_mesh"] = ("pallas", singles["pallas"].distribute(
        jax.make_mesh((n_chips,), ("halo",)), positions=positions))

    for name, (inner, p) in cases.items():
        exe, t_compile = _compiled(p, state)
        native = "tpu_custom_call" in exe.as_text()
        (f, u), report = p.execute_checked(state)
        f_1, u_1 = base[inner]
        err_f, err_u = rel_err(f, f_1), rel_err(u, u_1)
        spans = len(f.sharding.device_set)
        emit(phase=name, n=n, m_c=p.m_c, n_shards=p.n_shards,
             shard_cap=p.shard_cap, halo_inner=p.halo_inner,
             output_devices=spans,
             output_replicated=f.sharding.is_fully_replicated,
             compile_s=t_compile,
             steady_s=_steady(lambda: p.execute(state)),
             status=report.status, tpu_custom_call=native,
             rel_dF=err_f, rel_dU=err_u,
             bitwise_equal_one_chip=bitwise(f, f_1) and bitwise(u, u_1))
        check(p.n_shards == n_chips, f"{name}: {p.n_shards} shards")
        check(spans == n_chips, f"{name}: output spans {spans} devices")
        check(report.status == "ok", f"{name}: {report.status} "
              f"({report.faults})")
        check(inner == "reference" or interpret is not False or native,
              f"{name}: no tpu_custom_call in the compiled program")
        check(err_f <= TOL and err_u <= TOL,
              f"{name}: rel dF {err_f:.3g}, dU {err_u:.3g} vs one chip")


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the halo path over four chips (only)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {platform!r}); "
              "nothing runs on the CPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              "device(s)", file=sys.stderr)
        return 2

    from repro.core import Domain, make_lennard_jones

    domain = Domain.cubic(DIVISION, cutoff=1.0, periodic=True)
    kernel = make_lennard_jones(sigma=SIGMA, softening=SOFTENING)
    positions, velocities = lattice(DIVISION, args.seed)
    emit(phase="setup", n=int(positions.shape[0]), division=DIVISION,
         m_c=M_C, seed=args.seed, device_kind=devices[0].device_kind,
         devices=len(devices),
         compile_cache=jax.config.jax_compilation_cache_dir)
    if args.chips == 4:
        four_chips(domain, kernel, positions)
    else:
        refs = reference_check(domain, kernel, positions, args.seed)
        one_shot(domain, kernel, positions, refs)
        trajectory(domain, kernel, positions, velocities)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
