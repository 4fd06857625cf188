"""Faults planted under the timed path, for the tests that show each one
turns ``correct`` false: the engine's entries are wrapped while the cell
runs, so set-up, window and check are the run's own. A fault is armed
when the window starts: set-up's calls run as they are."""

import contextlib
import dataclasses

import jax.numpy as jnp


def _half(n):
    return jnp.arange(n) < n // 2


def _exec_half_left_out(orig):
    """Only the first half of the particles reach the engine."""
    from repro.core.api import ParticleState

    def execute(self, state):
        keep = _half(state.positions.shape[0])
        return orig(self, ParticleState(state.positions, state.fields, keep))
    return execute


def _exec_answer_altered(orig):
    """Every call's x forces come back with their sign flipped."""
    def execute(self, state):
        f, u = orig(self, state)
        return f.at[:, 0].multiply(-1.0), u
    return execute


def _traj_state_unchanged(orig):
    """A chunk reports its steps but hands back the state it was given."""
    def trajectory(self, state, n_steps, *a, **kw):
        res = orig(self, state, n_steps, *a, **kw)
        if n_steps == 0:
            return res
        return dataclasses.replace(res, state=state)
    return trajectory


def _traj_half_left_out(orig):
    """The second half of the particles is left where it was."""
    def trajectory(self, state, n_steps, *a, **kw):
        res = orig(self, state, n_steps, *a, **kw)
        if n_steps == 0:
            return res
        keep = _half(state.positions.shape[0])[:, None]
        s, o = res.state, state
        return dataclasses.replace(res, state=dataclasses.replace(
            s, positions=jnp.where(keep, s.positions, o.positions),
            velocities=jnp.where(keep, s.velocities, o.velocities),
            forces=jnp.where(keep, s.forces, o.forces)))
    return trajectory


def _traj_answer_altered(orig):
    """Every chunk's carried x forces come back with their sign flipped."""
    def trajectory(self, state, n_steps, *a, **kw):
        res = orig(self, state, n_steps, *a, **kw)
        s = res.state
        return dataclasses.replace(res, state=dataclasses.replace(
            s, forces=s.forces.at[:, 0].multiply(-1.0)))
    return trajectory


FAULTS = {
    "execute": {"half_left_out": _exec_half_left_out,
                "answer_altered": _exec_answer_altered},
    "trajectory": {"state_unchanged": _traj_state_unchanged,
                   "half_left_out": _traj_half_left_out,
                   "answer_altered": _traj_answer_altered},
}


@contextlib.contextmanager
def planted(entry: str, fault: str):
    from repro.core.api import InteractionPlan
    from bench import harness
    orig, orig_window = getattr(InteractionPlan, entry), harness.run_window
    bad, armed = FAULTS[entry][fault](orig), []

    def method(self, *a, **kw):
        return (bad if armed else orig)(self, *a, **kw)

    def run_window(*a, **kw):
        armed.append(True)
        return orig_window(*a, **kw)

    setattr(InteractionPlan, entry, method)
    harness.run_window = run_window
    try:
        yield
    finally:
        setattr(InteractionPlan, entry, orig)
        harness.run_window = orig_window
