"""``chip_smoke.py`` off the chip: its phases run end to end on a tiny box
with the Pallas kernels interpreted, so a change that breaks the smoke is
caught here and not on the chip; and the script itself refuses to run
without a TPU.
"""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import chip_smoke as cs  # noqa: E402
from repro.core import Domain, make_lennard_jones  # noqa: E402

DIVISION = 6


@pytest.fixture(scope="module")
def scene():
    dom = Domain.cubic(DIVISION, cutoff=1.0, periodic=True)
    kern = make_lennard_jones(sigma=cs.SIGMA, softening=cs.SOFTENING)
    pos, vel = cs.lattice(DIVISION, seed=0)
    return dom, kern, pos, vel


def _rows(out: str):
    return [json.loads(line) for line in out.splitlines()
            if line.startswith("{")]


def test_lattice_keeps_lj_cores_apart(scene):
    dom, _, pos, vel = scene
    assert pos.shape == (DIVISION ** 3 * cs.PPC, 3) == vel.shape
    p = np.asarray(pos, np.float64)
    d = p[:, None, :] - p[None, :, :]
    d -= dom.box[0] * np.round(d / dom.box[0])
    r2 = (d ** 2).sum(-1)
    np.fill_diagonal(r2, np.inf)
    assert r2.min() > cs.SIGMA ** 2


def test_one_chip_phases_interpreted(scene, capsys):
    dom, kern, pos, vel = scene
    refs = cs.reference_check(dom, kern, pos, seed=0)
    cs.one_shot(dom, kern, pos, refs, interpret=True)
    cs.trajectory(dom, kern, pos, vel, steps=4, interpret=True)
    rows = {r["phase"]: r for r in _rows(capsys.readouterr().out)}
    assert set(rows) == {"reference_vs_all_pairs", "trajectory",
                         *cs.VARIANTS}
    for name in cs.VARIANTS:        # interpreted plans are never refused
        assert rows[name]["status"] == "ok", rows[name]
        assert rows[name]["ladder_level"] == 0
    assert rows["trajectory"]["steps"] == 4


@pytest.mark.parametrize("variant", sorted(cs.VARIANTS))
def test_only_designed_refusals_pass(scene, monkeypatch, capsys, variant):
    """With every kernel over its VMEM limit, the smoke records sfc and
    packed as refused and fails on any other variant: a kernel it must run
    on the chip can never be refused quietly."""
    from repro.kernels import _lanes
    dom, kern, pos, _ = scene
    monkeypatch.setattr(_lanes, "VMEM_LIMIT_BYTES", 0)
    monkeypatch.setattr(cs, "VARIANTS", {variant: cs.VARIANTS[variant]})
    if variant in cs.REFUSABLE:
        cs.one_shot(dom, kern, pos, {}, interpret=False)
        (row,) = _rows(capsys.readouterr().out)
        assert "VMEM" in row["refused"], row
    else:
        with pytest.raises(ValueError, match="VMEM"):
            cs.one_shot(dom, kern, pos, {}, interpret=False)


def test_four_chip_phase_on_host_devices():
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import chip_smoke as cs
        from repro.core import Domain, make_lennard_jones
        dom = Domain.cubic(8, cutoff=1.0, periodic=True)
        kern = make_lennard_jones(sigma=cs.SIGMA, softening=cs.SOFTENING)
        pos, _ = cs.lattice(8, seed=0)
        cs.four_chips(dom, kern, pos, interpret=True)
    """)
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    rows = {r["phase"]: r for r in _rows(out.stdout)}
    assert set(rows) == {"halo_reference", "halo_pallas",
                         "distribute_make_mesh"}
    for r in rows.values():
        assert r["n_shards"] == 4 and r["output_devices"] == 4, r


def test_refuses_to_run_without_a_tpu(tmp_path):
    """No TPU, no verdict: a non-zero exit and no JSON line, both in the
    repository and in a directory that holds the script alone."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text((ROOT / "chip_smoke.py").read_text())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    for script in (ROOT / "chip_smoke.py", alone):
        out = subprocess.run([sys.executable, str(script)],
                             capture_output=True, text=True, env=env,
                             cwd=script.parent, timeout=120)
        assert out.returncode != 0
        assert "{" not in out.stdout
