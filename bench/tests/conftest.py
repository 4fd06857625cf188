"""A tiny copy of the benchmark's files for CPU tests: the same cells,
traffic and limits, with the configurations cut to a few hundred
particles (the engine's Pallas kernels run in interpret mode here).

``PENDING`` holds the cells whose files stay under ``bench/`` but whose
entries are out of ``BENCHMARK.json`` while the engine is at fault on
them (PERF.md, Open questions); ``with_pending`` adds them to a manifest,
so their set-up, window, check and faults stay tested."""

import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# size keys only: every width, pair term and step stays as configured
TINY = {
    "lammps_inlj": {"unit_cells": 6, "plan": {"m_c": 32},
                    "traj_plan": {"m_c": 64}},
    "paper_ppc2": {"cells": 6},
}
PENDING = {
    "workloads": [
        {"name": "lammps_inlj.traj", "config": "lammps_inlj",
         "traffic": "traj", "chips": 1,
         "why": "plan.trajectory chunks of 32 steps on the 37^3 skin grid "
                "(m_c 48), dt 0.005: fused scan, skin reuse, rebins"}],
    "end_to_end": [
        {"name": "particle_steps_per_s", "unit": "particle-steps/s",
         "better": "higher", "bound": 0.03, "source": "host_clock",
         "workloads": ["lammps_inlj.traj"]}],
    "per_layer": [
        {"name": f"{m}.traj", "unit": "ms/step" if m != "device_idle_pct"
         else "%", "better": "lower", "source": "device_trace",
         "layer": layer, "moves": "particle_steps_per_s",
         "workloads": ["lammps_inlj.traj"]}
        for m, layer in (("kernel_ms", "kernels"), ("xla_ms", "xla ops"),
                         ("device_idle_pct", "device"))] + [
        {"name": "rebins_per_kstep", "unit": "rebins/kstep",
         "better": "lower", "source": "program_counter",
         "layer": "traj engine", "moves": "particle_steps_per_s",
         "workloads": ["lammps_inlj.traj"]}],
}
TINY_TRAFFIC = {
    "traj": {"segment_len": 4},
    "force": {"keep_one_in": 2},
}


def with_pending(spec: dict) -> dict:
    """A copy of ``spec`` with the ``PENDING`` cells and metrics added."""
    out = json.loads(json.dumps(spec))
    for key, entries in PENDING.items():
        out[key] = out[key] + json.loads(json.dumps(entries))
    return out


def _shrink(path: pathlib.Path, changes: dict) -> None:
    data = json.loads(path.read_text())
    for k, v in changes.items():
        data[k] = dict(data[k], **v) if isinstance(v, dict) else v
    if "check_targets" in data:
        data["check_targets"] = 64
    path.write_text(json.dumps(data))


@pytest.fixture
def tiny_bench(tmp_path):
    """-> (manifest with the pending cells, bench dir) of a tiny copy of
    the benchmark."""
    from bench import harness
    dst = tmp_path / "bench"
    shutil.copytree(harness.BENCH, dst,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for name, changes in TINY.items():
        _shrink(dst / "configs" / f"{name}.json", changes)
    for name, changes in TINY_TRAFFIC.items():
        _shrink(dst / "traffic" / f"{name}.json", changes)
    return with_pending(harness.manifest()), dst
