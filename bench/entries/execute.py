"""Back-to-back ``plan.execute`` force calls, each ended by
``block_until_ready``, cycling over ``states`` states made from the seed.

Traffic keys: ``states``, ``keep_one_in``, ``check_targets``. The outputs
of one call in ``keep_one_in`` (the phase drawn from the seed) and of the
last call are kept; after the window each kept output is compared on
``check_targets`` seeded rows with the all-pairs reference of its input:
``force_err`` = max|dF| / max|F_ref| and ``pot_err`` likewise, the worst
over the kept calls.
"""

from __future__ import annotations

import time

import jax

from bench import drive, reference


class Traffic:
    def __init__(self, cfg, traffic, scene, pair, key, interpret: bool):
        self.cfg, self.traffic, self.scene = cfg, traffic, scene
        self.pair, self.key, self.interpret = pair, key, interpret

    def setup(self) -> dict:
        from repro.core import ParticleState
        cfg = self.cfg
        self.box = self.scene.box(cfg)
        k_state, self.k_check = jax.random.split(self.key)
        pos, _ = self.scene.make(cfg, k_state, self.traffic["states"])
        self.positions = [pos[i] for i in range(pos.shape[0])]
        self.plan = drive.plan(cfg, self.pair, drive.domain(cfg, self.box),
                               {}, self.interpret)
        self.states = [ParticleState(p) for p in self.positions]
        for st in self.states:
            if self.plan.check_overflow(st):
                raise drive.BenchError(
                    f"a state overflows m_c={self.plan.m_c}")
        drive.native(self.plan.compile(self.states[0]), self.interpret)
        for st in self.states:
            jax.block_until_ready(self.plan.execute(st))
        self.phase = int(jax.random.randint(
            self.k_check, (), 0, int(self.traffic["keep_one_in"])))
        return {"n": int(pos.shape[1]), "m_c": self.plan.m_c,
                "grid": list(self.plan.domain.ncells)}

    def window(self, seconds: float) -> drive.Window:
        stride, phase = int(self.traffic["keep_one_in"]), self.phase
        w, kept, k = drive.Window(), {}, len(self.states)
        with drive.annotate(drive.TRACE_WINDOW):
            t0 = time.perf_counter()
            while time.perf_counter() - t0 < seconds:
                i = w.attempted
                t = time.perf_counter()
                with drive.annotate("bench.execute"):
                    out = self.plan.execute(self.states[i % k])
                    jax.block_until_ready(out)
                w.unit_times.append(time.perf_counter() - t)
                if i % stride == phase:
                    kept[i] = out
                w.attempted += 1
            w.seconds = time.perf_counter() - t0
        kept[w.attempted - 1] = out
        w.units = w.attempted
        self.kept = kept
        return w

    def release(self) -> None:
        del self.plan, self.states

    def check(self) -> dict:
        pair, n = self.cfg["pair"], self.positions[0].shape[0]
        rows = drive.rows(self.k_check, n, self.traffic["check_targets"])
        refs, gap_f, gap_u = {}, 0.0, 0.0
        for i, (f, u) in sorted(self.kept.items()):
            s = i % len(self.positions)
            if s not in refs:
                refs[s] = reference.all_pairs(pair, self.pair.terms, self.box,
                                              self.positions[s], rows)
            gap_f = max(gap_f, drive.rel_gap(f[rows], refs[s][0]))
            gap_u = max(gap_u, drive.rel_gap(u[rows], refs[s][1]))
        return {"force_err": gap_f, "pot_err": gap_u}
