"""Back-to-back ``plan.trajectory`` chunks of ``segments_per_chunk`` x
``segment_len`` velocity-Verlet steps on the skin grid, each chunk
starting from the last one's state, as a user's repeated ``run`` would.

Traffic keys: ``segment_len``, ``segments_per_chunk``, ``keep_within``,
``check_targets``. Set-up computes the start forces and runs one chunk
through the window's own call, so that every program is compiled and
warm. The window keeps the start and the result of one of its first
``keep_within`` chunks (drawn from the seed) and of its last chunk.
After the window the plain reference repeats each kept chunk from its
start positions and velocities:

- ``pos_gap``: max |dx| after the chunk, minimum image, in length units;
- ``vel_gap``: max|dv| / max|v_ref| after the chunk;
- ``force_err``, ``pot_err``: the forces and potentials the last chunk
  carried, against the all-pairs reference at its positions on
  ``check_targets`` seeded rows (binning, skin reuse, kernel and
  scatter-back at the window's end);
- ``step_gap``: |the state's step count - (its count at the window's
  start + the steps the window's chunks reported)|.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import drive, reference


class Traffic:
    def __init__(self, cfg, traffic, scene, pair, key, interpret: bool):
        self.cfg, self.traffic, self.scene = cfg, traffic, scene
        self.pair, self.key, self.interpret = pair, key, interpret
        self.seg = int(traffic["segment_len"])
        self.chunk = self.seg * int(traffic["segments_per_chunk"])

    def _run(self, state, steps=None, **kw):
        return self.plan.trajectory(
            state, self.chunk if steps is None else steps, self.cfg["dt"],
            traj_plan=self.plan, mass=self.cfg["mass"],
            segment_len=self.seg, **kw)

    def setup(self) -> dict:
        from repro.core import ParticleState
        from repro.core.domain import skin_domain
        cfg = self.cfg
        self.box = self.scene.box(cfg)
        k_state, k_pick, self.k_check = jax.random.split(self.key, 3)
        pos, vel = self.scene.make(cfg, k_state, 1)
        grid = skin_domain(drive.domain(cfg, self.box), cfg["skin"])
        self.plan = drive.plan(cfg, self.pair, grid, cfg["traj_plan"],
                               self.interpret)
        if self.plan.check_overflow(ParticleState(pos[0])):
            raise drive.BenchError(f"the start overflows m_c={self.plan.m_c}")
        md0 = self._run(ParticleState(pos[0]), 0, velocities=vel[0]).state
        first = self._run(md0)
        self._accept(first, "the set-up chunk")
        for exe in first.executables:
            drive.native(exe, self.interpret)
        self.md = first.state
        jax.block_until_ready(self.md)
        self.pick = int(jax.random.randint(
            k_pick, (), 0, int(self.traffic["keep_within"])))
        return {"n": int(pos.shape[1]), "m_c": self.plan.m_c,
                "grid": list(self.plan.domain.ncells), "chunk": self.chunk,
                "eff_skin": first.eff_skin}

    def _accept(self, res, what: str) -> None:
        if res.status != "ok" or res.steps != self.chunk:
            raise drive.BenchError(f"{what} ended {res.status!r} after "
                                   f"{res.steps} steps: {res.faults}")

    def window(self, seconds: float) -> drive.Window:
        w = drive.Window(counters={"rebins": 0, "forced_rebins": 0,
                                   "replans": 0})
        self.step0 = int(self.md.step)
        kept = {}
        with drive.annotate(drive.TRACE_WINDOW):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                start = self.md
                t = time.perf_counter()
                with drive.annotate("bench.chunk"):
                    res = self._run(start)
                    jax.block_until_ready(res.state)
                w.unit_times.append(time.perf_counter() - t)
                if w.attempted == self.pick:
                    kept[w.attempted] = (start, res.state)
                w.attempted += 1
                if res.status != "ok" or res.ladder_level != 0:
                    w.failed += 1
                w.units += res.steps
                w.counters["rebins"] += res.rebins
                w.counters["forced_rebins"] += res.forced_rebins
                w.counters["replans"] += res.replans
                self.md = res.state
            w.seconds = time.perf_counter() - t0
        kept[w.attempted - 1] = (start, res.state)
        self.kept, self.units = kept, w.units
        return w

    def release(self) -> None:
        del self.plan

    def check(self) -> dict:
        cfg, pair, terms = self.cfg, self.cfg["pair"], self.pair.terms
        box = jnp.asarray(self.box, jnp.float32)
        pos_gap = vel_gap = 0.0
        for _, (start, end) in sorted(self.kept.items()):
            x1, v1, _, _ = reference.velocity_verlet(
                pair, terms, self.box, start.positions, start.velocities,
                dt=cfg["dt"], steps=self.chunk, mass=cfg["mass"])
            dx = np.asarray(reference.min_image(end.positions - x1, box))
            pos_gap = max(pos_gap, float(np.abs(dx).max())
                          if np.isfinite(dx).all() else float("inf"))
            vel_gap = max(vel_gap, drive.rel_gap(end.velocities, v1))
        n = self.md.positions.shape[0]
        rows = drive.rows(self.k_check, n, self.traffic["check_targets"])
        f, u = reference.all_pairs(pair, terms, self.box, self.md.positions,
                                   rows)
        return {"pos_gap": pos_gap, "vel_gap": vel_gap,
                "force_err": drive.rel_gap(self.md.forces[rows], f),
                "pot_err": drive.rel_gap(self.md.potential[rows], u),
                "step_gap": float(abs(int(self.md.step)
                                      - (self.step0 + self.units)))}
