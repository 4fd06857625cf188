"""The control of the comparison that decides ``correct``: the plain
reference put in the engine's place, with its pair arithmetic in
bfloat16 (the precision below the configuration's float32), driven
through the cell's own set-up, window and check. Every comparison it
faces is the one a run faces, so a control that reads as correct would
mean the limits cannot tell a lower precision from the engine.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 2

prints one JSON line per seed with ``correct`` (false is the control
failing, as it must) and each number compared beside its limit. It needs
a TPU, like ``run.py``. The benchmark's own runs never run it.

``swapped(cfg, pair)`` is the swap itself: while it is open,
``InteractionPlan.execute`` returns the reference's forces and
potentials, and ``InteractionPlan.trajectory`` integrates with the
reference's velocity Verlet, both at ``pair_dtype``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


@dataclasses.dataclass
class _Result:
    """The fields of the engine's ``TrajectoryResult`` the window reads."""

    state: object
    steps: int
    status: str = "ok"
    ladder_level: int = 0
    rebins: int = 0
    forced_rebins: int = 0
    replans: int = 0
    eff_skin: float = 0.0
    faults: list = dataclasses.field(default_factory=list)
    executables: list = dataclasses.field(default_factory=list)


@contextlib.contextmanager
def swapped(cfg: dict, pair_term, pair_dtype="bfloat16"):
    """``pair_term``: the configuration's ``bench/pairs/<kind>.py``."""
    import jax.numpy as jnp
    from repro.core.api import InteractionPlan, ParticleState
    from repro.physics.integrators import MDState
    from . import reference

    pair, terms = cfg["pair"], pair_term.terms
    dtype = jnp.dtype(pair_dtype)

    def execute(self, state):
        f, u, _ = reference.grid_forces(pair, terms, self.domain.box,
                                        state.positions, pair_dtype=dtype)
        return f, u

    def trajectory(self, state, n_steps, dt, *, velocities=None,
                   mass=1.0, **_):
        box = self.domain.box
        if isinstance(state, ParticleState):
            pos = state.positions
            vel = (velocities if velocities is not None
                   else jnp.zeros_like(pos))
        else:
            pos, vel = state.positions, state.velocities
        x, v, f, u = reference.velocity_verlet(
            pair, terms, box, pos, vel, dt=dt, steps=n_steps, mass=mass,
            pair_dtype=dtype)
        step = 0 if isinstance(state, ParticleState) else int(state.step)
        return _Result(MDState(x, v, f, u, jnp.int32(step + n_steps)),
                       n_steps)

    saved = InteractionPlan.execute, InteractionPlan.trajectory
    InteractionPlan.execute, InteractionPlan.trajectory = execute, trajectory
    try:
        yield
    finally:
        InteractionPlan.execute, InteractionPlan.trajectory = saved


def run(spec: dict, cell_name: str, seeds, seconds: float, *, bench=None,
        interpret=False, log=print) -> list:
    """The control's result line for each seed."""
    from . import harness
    bench = bench or harness.BENCH
    cfg = harness.config(harness.cell(spec, cell_name)["config"], bench)
    pair_term = harness.pair(cfg["pair"]["kind"], bench)
    out = []
    for seed in seeds:
        with swapped(cfg, pair_term):
            r = harness.measure(spec, cell_name, seed=seed, seconds=seconds,
                                trace=False, t_start=time.perf_counter(),
                                bench=bench, interpret=interpret,
                                log=lambda s: None)
        row = {"seed": seed, "correct": r["correct"], "checks": r["checks"]}
        log(json.dumps(row))
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    if jax.devices()[0].platform != "tpu":
        print("control.py: JAX found no TPU", file=sys.stderr)
        return 2
    from bench import control, harness
    rows = control.run(harness.manifest(), args.workload,
                       [int(s) for s in args.seeds.split(",")],
                       args.seconds, log=lambda s: print(s, flush=True))
    return 0 if not any(r["correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
