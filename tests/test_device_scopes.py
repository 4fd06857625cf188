"""The engine names its device work by layer with ``jax.named_scope``: the
compiled program carries each layer's scope in its instructions'
``op_name`` metadata, which is what maps a profiler trace's device ops
back to the layer that issued them (ARCHITECTURE.md, Observability).
Scopes are metadata only; the parity tests elsewhere hold the numbers.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import api, make_lennard_jones, plan
from repro.core.api import ParticleState
from repro.core.domain import Domain
from repro.physics.integrators import init_state
from repro.traj import run_trajectory

LAYOUTS = {
    "pallas_dense": dict(backend="pallas", strategy="xpencil"),
    "pallas_compact": dict(backend="pallas", strategy="xpencil",
                           compact=True),
    "pallas_packed": dict(backend="pallas", strategy="xpencil",
                          layout="packed"),
    "reference": dict(backend="reference", strategy="xpencil"),
}


def _op_names(hlo_text: str) -> list:
    """The name stack of every instruction, ``jit(...)`` frames dropped."""
    return [[p for p in name.split("/") if not p.startswith("jit(")]
            for name in re.findall(r'op_name="([^"]*)"', hlo_text)]


def _top_scopes(hlo_text: str) -> set:
    return {stack[0] for stack in _op_names(hlo_text) if stack}


@pytest.fixture(scope="module")
def scene():
    dom = Domain.cubic(4, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 128)
    return dom, pos


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_executor_names_each_layer(scene, layout):
    dom, pos = scene
    p = plan(dom, make_lennard_jones(), positions=pos, interpret=True,
             **LAYOUTS[layout])
    text = p.compile(ParticleState(pos)).as_text()
    stacks = _op_names(text)
    assert {"bin", "ghost", "pair", "scatter_back"} <= _top_scopes(text)
    subs = {s[1] for s in stacks if len(s) > 1 and s[0] == "bin"}
    assert {"sort", "scatter"} <= subs
    if LAYOUTS[layout].get("layout") == "packed":
        assert "pack" in subs
    # siblings, not children: ghost ring outside binning, the way back to
    # particle order outside the pair kernel's scope
    assert not any(s[0] == "bin" and "ghost" in s for s in stacks)
    assert not any(s[0] == "pair" and "scatter_back" in s for s in stacks)


def test_trajectory_names_integrate_and_refresh():
    dom = Domain.cubic(6, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 200)
    vel = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (200, 3),
                                  jnp.float32)
    p = plan(dom, make_lennard_jones(sigma=0.3, eps=1e-4), positions=pos)
    res = run_trajectory(p, init_state(p, pos, vel), 16, 1e-3, skin=0.25,
                         segment_len=16)
    assert res.status == "ok"
    named = set()          # inside the scan, below its while/body frames
    for programs in list(api._COMPILED.values()):
        for compiled in programs.values():
            named |= {p for s in _op_names(compiled.as_text()) for p in s}
    assert {"integrate", "bin_refresh", "ghost", "pair",
            "scatter_back"} <= named
