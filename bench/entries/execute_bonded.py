"""``execute.py``'s back-to-back ``plan.execute`` calls on bonded states: a
plan with the configuration's FENE bond term, states that carry the
scene's bond list. Window and release are ``execute.py``'s.

Configuration keys besides ``execute.py``'s: ``bond`` (``k``, ``r0``,
``epsilon``, ``sigma``) and ``special_bonds`` (the 1-2, 1-3, 1-4 weights
of the pair term; the engine runs LAMMPS ``special_bonds fene``, 0, 1, 1,
and refuses others). After the window
each kept output, the total force and potential (pair + bonds - excluded
pairs), is compared on ``check_targets`` seeded rows with
``reference_bonded.all_pairs`` of its input: ``force_err`` and
``pot_err`` as in ``execute.py``.
"""

from __future__ import annotations

import pathlib

import jax

from bench import drive, harness, reference_bonded

_execute = harness.entry("execute",
                         pathlib.Path(__file__).resolve().parents[1])


class Traffic(_execute.Traffic):
    def setup(self) -> dict:
        from repro.core import BondedTerm, ParticleState
        cfg = self.cfg
        if list(cfg["special_bonds"]) != [0.0, 1.0, 1.0]:
            raise drive.BenchError(
                "the engine weights 1-2, 1-3 and 1-4 pairs by 0, 1, 1 "
                f"(special_bonds fene), the configuration by "
                f"{cfg['special_bonds']}")
        bond = cfg["bond"]
        term = BondedTerm(k=bond["k"], r0=bond["r0"], epsilon=bond["epsilon"],
                          sigma=bond["sigma"])
        self.box = self.scene.box(cfg)
        k_state, self.k_check = jax.random.split(self.key)
        pos, self.bonds = self.scene.make(cfg, k_state,
                                          self.traffic["states"])
        self.positions = [pos[i] for i in range(pos.shape[0])]
        self.plan = drive.plan(cfg, self.pair, drive.domain(cfg, self.box),
                               {"bonded": term}, self.interpret)
        self.states = [ParticleState(p, bonds=self.bonds)
                       for p in self.positions]
        for st in self.states:
            if self.plan.check_overflow(st):
                raise drive.BenchError(
                    f"a state overflows m_c={self.plan.m_c}")
            if self.plan.bond_faults(st):
                raise drive.BenchError("a state has a bond stretched past "
                                       "FENE's log floor")
        drive.native(self.plan.compile(self.states[0]), self.interpret)
        for st in self.states:
            jax.block_until_ready(self.plan.execute(st))
        self.phase = int(jax.random.randint(
            self.k_check, (), 0, int(self.traffic["keep_one_in"])))
        return {"n": int(pos.shape[1]), "bonds": int(self.bonds.shape[0]),
                "m_c": self.plan.m_c, "grid": list(self.plan.domain.ncells)}

    def check(self) -> dict:
        cfg, n = self.cfg, self.positions[0].shape[0]
        rows = drive.rows(self.k_check, n, self.traffic["check_targets"])
        table = reference_bonded.partners(self.bonds, n)
        refs, gap_f, gap_u = {}, 0.0, 0.0
        for i, (f, u) in sorted(self.kept.items()):
            s = i % len(self.positions)
            if s not in refs:
                refs[s] = reference_bonded.all_pairs(
                    cfg["pair"], self.pair.terms, cfg["bond"], self.box,
                    self.positions[s], table, rows)
            gap_f = max(gap_f, drive.rel_gap(f[rows], refs[s][0]))
            gap_u = max(gap_u, drive.rel_gap(u[rows], refs[s][1]))
        return {"force_err": gap_f, "pot_err": gap_u}
