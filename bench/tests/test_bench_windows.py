"""Every cell's set-up, window and check, as functions on the CPU at a
tiny size (Pallas in interpret mode): the run is correct, reports the
cell's end-to-end metrics and prints each number beside its limit."""

import time

import pytest

from bench import harness
from bench.tests.conftest import with_pending

CELLS = [w["name"] for w in with_pending(harness.manifest())["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct_at_a_tiny_size(tiny_bench, cell):
    spec, bench = tiny_bench
    r = harness.measure(spec, cell, seed=2 ** 33 + 7, seconds=0.2,
                        trace=False, t_start=time.perf_counter(),
                        bench=bench, interpret=True, log=lambda s: None)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    want = {m["name"] for m in harness.metrics_of(spec, cell, "end_to_end")}
    got = set(r["metrics"])
    # a percentile needs ten calls, which a tiny window may not hold
    assert got <= want and want - got <= {"force_eval_p90_ms"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(harness.limits(cell, bench))
    assert set(r["device"]) >= {"platform", "kind", "count",
                                "memory_peak_bytes"}


def test_same_seed_same_state(tiny_bench):
    import numpy as np
    spec, bench = tiny_bench
    cfg = harness.config("lammps_inlj", bench)
    scene = harness.scene(cfg["scene"], bench)
    a = scene.make(cfg, harness._key(2 ** 33 + 1), 2)
    b = scene.make(cfg, harness._key(2 ** 33 + 1), 2)
    c = scene.make(cfg, harness._key(1), 2)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])      # high seed bits count
    assert not np.array_equal(a[0][0], a[0][1])  # states differ
