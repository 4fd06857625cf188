"""What every traffic entry shares. A traffic file (``bench/traffic/
<name>.json``) names its ``entry``, a file ``bench/entries/<entry>.py``
whose ``Traffic`` class builds the cell's plans from the configuration,
warms every program the window will run, drives the window, and compares
what the window produced with the plain reference. A new entry is a new
file there; this module holds what they share.

A ``Traffic`` is built as ``Traffic(cfg, traffic, scene, pair, key,
interpret)`` (``scene``: the ``bench/scenes`` module the configuration
names; ``pair``: its ``bench/pairs/<kind>.py`` module) and has:

- ``setup() -> dict``: state from the seed, plans, compiles, warm-up;
  the dict holds ``n`` (particles) and what else the log line shows;
- ``window(seconds) -> Window``: the measured loop, opened with
  ``annotate(TRACE_WINDOW)`` and ending with the unit of work in flight;
  its time runs from its start to the end of the last unit;
- ``release()``: drops the engine's plans before the reference runs;
- ``check() -> dict``: each number compared, by name (see
  ``bench/limits/<cell>.json``).
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

TRACE_WINDOW = "bench.window"


class BenchError(RuntimeError):
    """The cell cannot be run as its configuration states."""


@dataclasses.dataclass
class Window:
    seconds: float = 0.0          # window start to the end of the last unit
    units: int = 0                # force calls or MD steps completed
    unit_times: list = dataclasses.field(default_factory=list)
    attempted: int = 0            # calls or chunks
    failed: int = 0
    counters: dict = dataclasses.field(default_factory=dict)


def annotate(name: str):
    return jax.profiler.TraceAnnotation(name)


def rows(key, n: int, count: int):
    """``count`` (rounded down to 16) distinct rows of ``n``, from ``key``."""
    count = min(count, n) // 16 * 16
    return jax.random.choice(key, n, (count,), replace=False)


def rel_gap(a, b) -> float:
    """max|a - b| / max|b|; inf where either holds a non-finite number."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        return float("inf")
    return float(np.abs(a - b).max() / max(np.abs(b).max(), 1e-30))


def native(compiled, interpret: bool) -> None:
    if not interpret and "tpu_custom_call" not in compiled.as_text():
        raise BenchError("the compiled program holds no tpu_custom_call: "
                         "the Pallas kernel did not run natively")


def domain(cfg: dict, box):
    from repro.core import Domain
    from .reference import grid_cells
    cells = grid_cells(box, cfg["pair"]["cutoff"])
    return Domain(box=tuple(float(b) for b in box), ncells=cells,
                  cutoff=float(cfg["pair"]["cutoff"]), periodic=True)


def plan(cfg: dict, pair, dom, plan_args: dict, interpret: bool):
    """The engine's plan of ``cfg["plan"]`` updated by ``plan_args``."""
    from repro.core import plan as make_plan
    args = dict(cfg["plan"], **plan_args)
    return make_plan(dom, pair.engine(cfg["pair"]), interpret=interpret,
                     **args)
