"""BENCHMARK.json against the benchmark's contract, and the files it
names: every cell finds its configuration, traffic and limits by name,
every metric its reader, and a file added beside them is found without
editing any other."""

import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.conftest import with_pending

SPEC = harness.manifest()
# the manifest and the cells kept out of it while the engine is at fault
# there (conftest.PENDING): the files of both resolve by name
FILES = with_pending(SPEC)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
CELLS = [w["name"] for w in SPEC["workloads"]]
METRICS = SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][:2] == ["python3", "bench/run.py"]
    assert all(PATH.match(p) and ".." not in p for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_units_and_one_line_texts():
    names = [m["name"] for m in METRICS] + CELLS + \
        [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in SPEC["workloads"]] + \
            [k for c in SPEC["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    texts = [c[k] for c in SPEC["configs"] for k in ("why", "source")] + \
        [w["why"] for w in SPEC["workloads"]] + \
        [m["layer"] for m in SPEC["per_layer"]]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t


def test_bounds_and_setup_metric():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("cell", [w["name"] for w in FILES["workloads"]])
def test_cell_resolves_its_files_by_name(cell):
    w = harness.cell(FILES, cell)
    assert w["chips"] in (1, 4)
    cfg = harness.config(w["config"])
    assert harness.scene(cfg["scene"]).make
    assert harness.traffic(w["traffic"])["entry"]
    assert harness.limits(cell)
    entry = next(c for c in SPEC["configs"] if c["name"] == w["config"])
    assert pathlib.Path(harness.BENCH.parent / entry["file"]).is_file()
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]


@pytest.mark.parametrize(
    "metric", [m["name"] for m in FILES["end_to_end"] + FILES["per_layer"]])
def test_metric_resolves_its_reader(metric):
    kind = "layers" if any(m["name"] == metric
                           for m in FILES["per_layer"]) else "end_to_end"
    assert callable(harness.reader(kind, metric))


@pytest.mark.parametrize("cell", [w["name"] for w in FILES["workloads"]])
def test_every_cell_reports_what_its_layers_move(cell):
    e2e = {m["name"] for m in harness.metrics_of(FILES, cell, "end_to_end")}
    layers = harness.metrics_of(FILES, cell, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layers
    for m in layers:
        assert m["moves"] in e2e, (cell, m["name"])


def test_one_layer_name_per_layer():
    seen = {}
    for m in SPEC["per_layer"]:
        base = m["name"].split(".")[0]
        assert seen.setdefault(base, m["layer"]) == m["layer"]


def test_a_new_config_traffic_and_reader_need_no_edit(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = harness.config("paper_ppc2")
    cfg["cells"] = 40
    (bench / "configs" / "paper_ppc2_small.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "burst.json").write_text(
        json.dumps({"entry": "execute", "states": 2, "keep_one_in": 4,
                    "check_targets": 64}))
    (bench / "layers" / "calls.force.py").write_text(
        "def read(run):\n    return run['window'].attempted\n")
    assert harness.config("paper_ppc2_small", bench)["cells"] == 40
    assert harness.traffic("burst", bench)["states"] == 2
    assert harness.reader("layers", "calls.force", bench)(
        {"window": type("W", (), {"attempted": 3})}) == 3
    assert harness.config("paper_ppc2", bench)["cells"] == 80


def test_a_suffixed_metric_falls_back_to_its_base_reader(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(harness.BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    base = harness.reader("layers", "kernel_ms", bench)
    assert harness.reader("layers", "kernel_ms.serve", bench) is base
    (bench / "layers" / "kernel_ms.serve.py").write_text(
        "def read(run):\n    return 7.0\n")
    assert harness.reader("layers", "kernel_ms.serve", bench)({}) == 7.0


def test_a_new_pair_kind_and_entry_run_without_an_edit(tiny_bench):
    """A pair term and an entry added as files beside the others drive a
    whole cell: set-up, window and the reference's check."""
    import time
    spec, bench = tiny_bench
    lj = (bench / "pairs" / "lennard_jones.py").read_text()
    (bench / "pairs" / "lj_halved.py").write_text(
        lj.replace('eps=pair["epsilon"]', 'eps=0.5 * pair["epsilon"]')
          .replace('eps = jnp.asarray(pair["epsilon"], dt)',
                   'CALLS.append(dt)\n    '
                   'eps = jnp.asarray(0.5 * pair["epsilon"], dt)')
        + "\n\nCALLS = []\n")
    (bench / "entries" / "execute_again.py").write_text(
        (bench / "entries" / "execute.py").read_text())
    cfg = harness.config("paper_ppc2", bench)
    cfg["pair"]["kind"] = "lj_halved"
    (bench / "configs" / "ppc2_halved.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "again.json").write_text(json.dumps(
        dict(harness.traffic("force", bench), entry="execute_again")))
    (bench / "limits" / "ppc2_halved.again.json").write_text(
        (bench / "limits" / "paper_ppc2.force.json").read_text())
    spec = json.loads(json.dumps(spec))
    spec["workloads"].append({"name": "ppc2_halved.again",
                              "config": "ppc2_halved", "traffic": "again",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if "paper_ppc2.force" in m.get("workloads", []):
            m["workloads"].append("ppc2_halved.again")
    r = harness.measure(spec, "ppc2_halved.again", seed=11, seconds=0.2,
                        trace=False, t_start=time.perf_counter(),
                        bench=bench, interpret=True, log=lambda s: None)
    assert r["correct"], r["checks"]
    assert r["metrics"]["force_eval_ms"]["value"] > 0
    assert harness.pair("lj_halved", bench).CALLS      # the reference's


def test_run_refuses_without_a_tpu():
    root = harness.BENCH.parent
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1"],
                       cwd=root, capture_output=True, text=True, timeout=300,
                       env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu",
                            "HOME": str(root)})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr
