"""The control of a bonded cell (``execute_bonded`` traffic): ``control.py``'s
swap, with the bonds in it. While it is open, ``InteractionPlan.execute``
returns the reference's grid pair forces plus the bonds' FENE + WCA less
the pair term between bonded ends, all arithmetic in bfloat16 (the
precision below the configuration's float32), driven through the cell's
own set-up, window and check.

    python3 bench/control_bonded.py --workload kg_melt.force_bonded \
        --seeds 1,2,3 --seconds 2

prints one JSON line per seed with ``correct`` (false is the control
failing, as it must) and each number compared beside its limit. It needs
a TPU, like ``run.py``. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


@contextlib.contextmanager
def swapped(cfg: dict, pair_term, pair_dtype="bfloat16"):
    """``pair_term``: the configuration's ``bench/pairs/<kind>.py``."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.api import InteractionPlan
    from . import control, reference_bonded

    dtype = jnp.dtype(pair_dtype)
    with control.swapped(cfg, pair_term, pair_dtype):
        pair_only = InteractionPlan.execute

        def execute(self, state):
            f, u = pair_only(self, state)
            n = state.positions.shape[0]
            fb, ub = reference_bonded.bond_rows(
                cfg["pair"], pair_term.terms, cfg["bond"], self.domain.box,
                state.positions, reference_bonded.partners(state.bonds, n),
                np.arange(n), minus_pair=True, pair_dtype=dtype)
            return f + fb, u + ub

        InteractionPlan.execute = execute
        try:
            yield
        finally:
            InteractionPlan.execute = pair_only


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
        print("control_bonded.py: JAX found no TPU", file=sys.stderr)
        return 2
    from bench import control_bonded, harness
    rows = control_bonded.run(harness.manifest(), args.workload,
                              [int(s) for s in args.seeds.split(",")],
                              args.seconds,
                              log=lambda s: print(s, flush=True))
    return 0 if not any(r["correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
