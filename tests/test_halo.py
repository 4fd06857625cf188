"""Distributed halo execution subsystem (``backend="halo"``).

Three tiers:
  * single-device tests — partition/scatter algebra, plan validation,
    the bit-identical single-shard fallback, bound probes, the autotuner's
    shard-count twin axis (pure enumeration, no devices needed);
  * in-process multi-device tests — run when the pytest process itself
    sees >= 2 devices (the CI halo job sets
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), skipped in
    the single-device tier-1 run;
  * subprocess multi-device tests — spawn a fresh python with emulated
    devices so the tier-1 run exercises real shard_map/ppermute execution
    without contaminating this process's device count.
"""

import dataclasses
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Domain, ParticleState, make_lennard_jones, plan
from repro.core.binning import shard_pencil_active, shard_slab_counts
from repro.core.domain import slab_domain
from repro.dist import halo as H

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_sub(body: str, n_dev: int = 4, timeout: int = 600) -> str:
    code = ("import os\n"
            f"os.environ['XLA_FLAGS'] = "
            f"'--xla_force_host_platform_device_count={n_dev}'\n"
            + textwrap.dedent(body))
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


# --------------------------------------------------------------------------
# single-device: geometry, partition, plan contract
# --------------------------------------------------------------------------

def test_slab_domain_geometry():
    dom = Domain.cubic(8, cutoff=1.0, periodic=True)
    loc = slab_domain(dom, 4)
    assert loc.ncells == (8, 8, 2)
    assert loc.box == (8.0, 8.0, 2.0)
    assert loc.periodic_axes == (True, True, False)   # Z ghosts come from
    with pytest.raises(ValueError):                   # the exchange
        slab_domain(dom, 3)


def test_partition_scatter_roundtrip():
    dom = Domain.cubic(8, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 500)
    cap = int(H.suggest_shard_cap(dom, pos, 2))
    gidx, pos_part, _ = H.partition_by_shard(dom, pos, {}, 2, cap)
    assert pos_part.shape == (2 * cap, 3)
    # every real row belongs to its shard's slab; pads are sentinels
    valid = np.asarray(pos_part[:, 0] < H.VALID_MAX)
    zs = np.asarray(pos_part[:, 2])
    assert valid[:cap].sum() + valid[cap:].sum() == 500
    assert (zs[:cap][valid[:cap]] < 4.0).all()
    assert (zs[cap:][valid[cap:]] >= 4.0).all()
    # scatter-back restores particle order
    back = H.scatter_from_shards(gidx, 500, pos_part)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(pos))


def test_partition_drops_overflow_rows():
    dom = Domain.cubic(4, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(1), 200)
    gidx, pos_part, _ = H.partition_by_shard(dom, pos, {}, 2, cap=10)
    valid = np.asarray(pos_part[:, 0] < H.VALID_MAX)
    assert valid.sum() <= 20          # truncated, never out of bounds
    # and the plan layer detects exactly this situation
    p = plan(dom, make_lennard_jones(), positions=pos, strategy="xpencil",
             backend="halo", n_shards=2, shard_cap=10)
    assert p.check_overflow(ParticleState(pos))


def test_shard_probes_match_bincount():
    dom = Domain.cubic(8, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(2), 700)
    loads = np.asarray(H.shard_loads(dom, pos, 4))
    zc = np.asarray(dom.cell_coords(pos))[:, 2]
    expect = np.bincount(zc // 2, minlength=4)
    np.testing.assert_array_equal(loads, expect)
    assert loads.sum() == 700
    cap = H.suggest_shard_cap(dom, pos, 4)
    assert cap >= loads.max() and cap % 8 == 0
    ma = H.suggest_shard_max_active(dom, pos, 4)
    counts = jax.ops.segment_sum(jnp.ones((700,), jnp.int32),
                                 dom.cell_ids(pos),
                                 num_segments=dom.n_cells)
    assert ma >= int(np.asarray(shard_pencil_active(dom, counts, 4)).max())
    assert ma <= 2 * 8                # clipped to the slab's pencil count
    np.testing.assert_array_equal(
        np.asarray(shard_slab_counts(dom, counts, 4)), expect)


def test_single_shard_fallback_bit_identical():
    dom = Domain.cubic(6, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(3), 600)
    state = ParticleState(pos)
    kern = make_lennard_jones()
    p_ref = plan(dom, kern, positions=pos, strategy="xpencil")
    p_halo = dataclasses.replace(p_ref, backend="halo", n_shards=1)
    f_r, q_r = p_ref.execute(state)
    f_h, q_h = p_halo.execute(state)
    np.testing.assert_array_equal(np.asarray(f_r), np.asarray(f_h))
    np.testing.assert_array_equal(np.asarray(q_r), np.asarray(q_h))


def test_fig_halo_sets_host_devices_before_jax():
    """``fig_halo --devices N`` decides its device flags before JAX is
    imported and runs in one process: importing the module loads no JAX,
    and the flag replaces any earlier device count."""
    from benchmarks.fig_halo import host_device_flags
    assert (host_device_flags(4, "")
            == "--xla_force_host_platform_device_count=4")
    assert (host_device_flags(
        8, "--xla_foo=1 --xla_force_host_platform_device_count=2")
        == "--xla_foo=1 --xla_force_host_platform_device_count=8")
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, "-c",
         "import sys, benchmarks.fig_halo as f; "
         "print('jax' in sys.modules, hasattr(f, 'subprocess'))"],
        capture_output=True, text=True, cwd=root, timeout=60,
        env=dict(os.environ, PYTHONPATH=SRC))
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == ["False", "False"]


def test_halo_plan_validation():
    dom = Domain.cubic(8, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 100)
    kern = make_lennard_jones()
    with pytest.raises(ValueError, match="cell schedule"):
        plan(dom, kern, positions=pos, strategy="par_part", backend="halo")
    with pytest.raises(ValueError, match="divisible"):
        plan(dom, kern, positions=pos, strategy="xpencil", backend="halo",
             n_shards=3)
    with pytest.raises(ValueError, match="pencil schedules"):
        plan(dom, kern, positions=pos, strategy="allin", backend="halo",
             n_shards=2, compact=True)
    with pytest.raises(ValueError, match="shard_cap"):
        plan(dom, kern, m_c=8, strategy="xpencil", backend="halo",
             n_shards=2)               # no positions, no cap
    with pytest.raises(ValueError, match="concrete per-shard backend"):
        plan(dom, kern, positions=pos, strategy="xpencil", backend="halo",
             n_shards=2, halo_inner="halo")


def test_plan_defaults_follow_device_count():
    dom = Domain.cubic(8, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 400)
    p = plan(dom, make_lennard_jones(), positions=pos, strategy="xpencil",
             backend="halo")
    from repro.dist.engine import default_n_shards
    assert p.n_shards == default_n_shards(dom)
    assert p.n_shards <= jax.device_count() and 8 % p.n_shards == 0
    if p.n_shards > 1:
        assert p.shard_cap is not None and p.shard_cap >= 1


def test_distribute_builds_halo_twin():
    dom = Domain.cubic(8, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(4), 900)
    p = plan(dom, make_lennard_jones(), positions=pos, strategy="xpencil",
             compact=True)
    d = p.distribute(n_shards=4, positions=pos)
    assert d.backend == "halo" and d.halo_inner == "reference"
    assert d.n_shards == 4 and d.shard_cap >= 1
    # compact bound re-measured per shard: never larger than the global one
    assert d.compact and d.max_active <= p.max_active
    # replan grows only the shard capacity when only it overflows
    tight = dataclasses.replace(d, shard_cap=2)
    grown = tight.replan(ParticleState(pos))
    assert grown.shard_cap > 2 and grown.m_c == d.m_c
    assert grown.max_active == d.max_active


def test_halo_replan_measures_what_the_check_measures():
    """The overflow check and replan read one per-cell count, with padding
    rows (``valid`` False) left out of both: a breached shard_cap grows to
    cover the real rows, not the padding piled into shard 0."""
    dom = Domain.cubic(4, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 300)
    state = ParticleState(jnp.concatenate([pos, jnp.full((200, 3), 0.5)]),
                          valid=jnp.arange(500) < 300)
    p = plan(dom, make_lennard_jones(), positions=pos, strategy="xpencil",
             backend="halo", n_shards=2)
    tight = dataclasses.replace(p, shard_cap=8)
    assert tight.overflow_class(state) == "shard_cap"
    grown = tight.replan(state)
    assert grown.shard_cap == H.suggest_shard_cap(dom, pos, 2) == p.shard_cap
    assert not grown.check_overflow(state)


def test_autotune_halo_twins_enumeration():
    from repro.core.autotune import Candidate, halo_twins, prune_candidates
    dom = Domain.cubic(8, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(5), 600)
    base = [Candidate("xpencil", "reference", 64, 16),
            Candidate("xpencil", "reference", 64, 16, compact=True,
                      max_active=64),
            Candidate("par_part", "reference", 64, 16),
            Candidate("allin", "reference", 64, 16, box=(2, 2, 2),
                      compact=True, max_active=64)]
    twins = halo_twins(dom, pos, base, (2, 3, 4, 16), device_count=4)
    # 3 doesn't divide nz=8, 16 exceeds the injected device count,
    # par_part has no slab meaning, compact allin is excluded
    assert {t.n_shards for t in twins} == {2, 4}
    assert all(t.shard_cap and t.shard_cap >= 1 for t in twins)
    assert {t.strategy for t in twins} == {"xpencil"}
    comp = [t for t in twins if t.compact]
    assert comp and all(t.max_active <= 64 for t in comp)
    # round-robin pruning keeps distributed twins in the timed field
    kept, _ = prune_candidates(dom, 600 / dom.n_cells, base[:1] + twins,
                               top_k=3)
    assert any(c.distributed for c in kept)
    # and a JSON round trip preserves the distributed axis
    rt = Candidate.from_json(twins[0].to_json())
    assert rt == twins[0]


def test_cache_key_is_mesh_aware():
    from repro.core.autotune import cache_key
    dom = Domain.cubic(4, cutoff=1.0)
    kern = make_lennard_jones()
    k1 = cache_key("cpu", dom, 8, 4.0, kern, ("reference",),
                   device_count=1)
    k8 = cache_key("cpu", dom, 8, 4.0, kern, ("reference",),
                   device_count=8)
    assert k1 != k8 and "dev8" in k8


# --------------------------------------------------------------------------
# in-process multi-device (CI halo job: 8 emulated devices)
# --------------------------------------------------------------------------

multi = pytest.mark.skipif(jax.device_count() < 2,
                           reason="needs >= 2 devices (CI halo job)")


@multi
def test_halo_parity_in_process():
    ndev = jax.device_count()
    ns = max(n for n in range(1, min(ndev, 8) + 1) if 8 % n == 0)
    dom = Domain.cubic(8, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(7), 1000)
    state = ParticleState(pos)
    kern = make_lennard_jones()
    p_ref = plan(dom, kern, positions=pos, strategy="xpencil")
    p_halo = plan(dom, kern, m_c=p_ref.m_c, positions=pos,
                  strategy="xpencil", backend="halo", n_shards=ns)
    f_r, q_r = p_ref.execute(state)
    f_h, q_h = p_halo.execute(state)
    scale = float(np.abs(np.asarray(f_r)).max())
    np.testing.assert_allclose(np.asarray(f_h), np.asarray(f_r),
                               rtol=3e-4, atol=3e-4 * max(scale, 1.0))


@multi
def test_halo_compact_bit_identical_in_process():
    ndev = jax.device_count()
    ns = max(n for n in (2, 4) if n <= ndev)
    dom = Domain.cubic(8, cutoff=1.0)
    pos = np.array(Domain.cubic(8).sample_uniform(
        jax.random.PRNGKey(8), 400))
    pos[:, 2] = pos[:, 2] * 0.5       # cluster low in Z: uneven shards
    pos = jnp.asarray(pos)
    state = ParticleState(pos)
    kern = make_lennard_jones()
    pd = plan(dom, kern, positions=pos, strategy="xpencil", backend="halo",
              n_shards=ns)
    pc = plan(dom, kern, m_c=pd.m_c, positions=pos, strategy="xpencil",
              backend="halo", n_shards=ns, compact=True)
    f_d, q_d = pd.execute(state)
    f_c, q_c = pc.execute(state)
    np.testing.assert_array_equal(np.asarray(f_d), np.asarray(f_c))
    np.testing.assert_array_equal(np.asarray(q_d), np.asarray(q_c))


# --------------------------------------------------------------------------
# subprocess multi-device (tier-1: fresh python, emulated devices)
# --------------------------------------------------------------------------

def test_halo_backend_parity_subprocess():
    """Acceptance gate: on 4 emulated devices the halo backend matches the
    single-device schedule for dense and compacted shards, periodic and
    open Z — and compacted shards are bit-identical to dense shards."""
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import Domain, ParticleState, make_lennard_jones, \\
            plan
        kern = make_lennard_jones()
        for periodic in (False, True):
            dom = Domain.cubic(8, cutoff=1.0, periodic=periodic)
            pos = dom.sample_uniform(jax.random.PRNGKey(3), 1500)
            state = ParticleState(pos)
            p_ref = plan(dom, kern, positions=pos, strategy="xpencil")
            f_r, q_r = p_ref.execute(state)
            scale = max(float(np.abs(np.asarray(f_r)).max()), 1.0)
            p_h = plan(dom, kern, m_c=p_ref.m_c, positions=pos,
                       strategy="xpencil", backend="halo", n_shards=4)
            f_h, q_h = p_h.execute(state)
            np.testing.assert_allclose(np.asarray(f_h), np.asarray(f_r),
                                       rtol=3e-4, atol=3e-4 * scale)
            p_c = plan(dom, kern, m_c=p_ref.m_c, positions=pos,
                       strategy="xpencil", backend="halo", n_shards=4,
                       compact=True)
            f_c, q_c = p_c.execute(state)
            assert np.array_equal(np.asarray(f_h), np.asarray(f_c))
            assert np.array_equal(np.asarray(q_h), np.asarray(q_c))
        print("PARITY_OK")
    """)
    assert "PARITY_OK" in out


def test_halo_boundary_pair_vs_minimum_image_oracle():
    """Regression (non-periodic Z halo fill): a pair straddling the global
    Z boundary interacts through the wrap iff Z is periodic — checked
    against the O(N^2) minimum-image oracle on both axis settings."""
    out = run_sub("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import Domain, ParticleState, make_lennard_jones, \\
            plan
        kern = make_lennard_jones()
        pos = jnp.asarray([[2.1, 2.1, 0.15], [2.1, 2.1, 3.85]],
                          jnp.float32)
        state = ParticleState(pos)
        for periodic_z in (True, False):
            dom = Domain(box=(4., 4., 4.), ncells=(4, 4, 4), cutoff=1.0,
                         periodic=(False, False, periodic_z))
            f_n2, _ = plan(dom, kern, m_c=8,
                           strategy="naive_n2").execute(state)
            f_h, _ = plan(dom, kern, m_c=8, positions=pos,
                          strategy="xpencil", backend="halo",
                          n_shards=2).execute(state)
            np.testing.assert_allclose(np.asarray(f_h), np.asarray(f_n2),
                                       rtol=1e-5, atol=1e-6)
            if periodic_z:
                assert np.abs(np.asarray(f_h)).max() > 0
            else:
                assert np.abs(np.asarray(f_h)).max() == 0, \\
                    "open Z boundary leaked ghost particles"
        print("BOUNDARY_OK")
    """, n_dev=2)
    assert "BOUNDARY_OK" in out


def test_halo_batch_replan_and_fields_subprocess():
    out = run_sub("""
        import dataclasses
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import Domain, ParticleState, make_lennard_jones, \\
            plan
        from repro.core.api import dispatch_count
        kern = make_lennard_jones()
        dom = Domain.cubic(4, cutoff=1.0, periodic=True)
        pos = dom.sample_uniform(jax.random.PRNGKey(0), 300)
        state = ParticleState(pos)
        p = plan(dom, kern, positions=pos, strategy="xpencil",
                 backend="halo", n_shards=2)
        f0, q0 = p.execute(state)

        # batched: one dispatch, bit-identical to the per-state loop
        B = 3
        stack = ParticleState(jnp.stack([pos + 0.002 * i
                                         for i in range(B)]))
        before = dispatch_count()
        fb, qb = p.execute_batch(stack)
        assert dispatch_count() == before + 1
        for i in range(B):
            fi, qi = p.execute(ParticleState(stack.positions[i]))
            assert np.array_equal(np.asarray(fb[i]), np.asarray(fi)), i
            assert np.array_equal(np.asarray(qb[i]), np.asarray(qi)), i

        # overflow -> replan grows only the shard capacity
        tight = dataclasses.replace(p, shard_cap=8)
        assert tight.check_overflow(state)
        (f2, _), grown = tight.execute_or_replan(state)
        assert grown.shard_cap > 8 and grown.m_c == p.m_c
        assert np.array_equal(np.asarray(f2), np.asarray(f0))

        # per-particle fields ride through partition + ghost exchange
        sf = ParticleState(pos, {"mass": jnp.ones((300,))})
        ff, qf = p.execute(sf)
        assert np.array_equal(np.asarray(ff), np.asarray(f0))
        print("BATCH_REPLAN_OK")
    """, n_dev=2)
    assert "BATCH_REPLAN_OK" in out


@multi
def test_halo_packed_bit_identical_in_process():
    """Packed per-shard execution (ghost planes exchanged packed) is
    bit-identical to the dense-layout halo path, with and without
    per-shard compaction."""
    ndev = jax.device_count()
    ns = max(n for n in (2, 4) if n <= ndev)
    dom = Domain.cubic(8, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(9), 500)
    state = ParticleState(pos)
    kern = make_lennard_jones()
    pd = plan(dom, kern, positions=pos, strategy="xpencil", backend="halo",
              n_shards=ns)
    f_d, q_d = pd.execute(state)
    for compact in (False, True):
        pp = plan(dom, kern, m_c=pd.m_c, positions=pos, strategy="xpencil",
                  backend="halo", n_shards=ns, layout="packed",
                  compact=compact)
        f_p, q_p = pp.execute(state)
        np.testing.assert_array_equal(np.asarray(f_p), np.asarray(f_d))
        np.testing.assert_array_equal(np.asarray(q_p), np.asarray(q_d))


def test_halo_packed_parity_subprocess():
    """On 4 emulated devices the packed halo path (per-shard CSR packing +
    packed ghost-plane exchange) is bit-identical to the dense halo path
    on periodic and open Z, and its row_cap replan grows only that
    bound."""
    out = run_sub("""
        import dataclasses
        import jax, numpy as np
        from repro.core import Domain, ParticleState, make_lennard_jones, \\
            plan
        kern = make_lennard_jones()
        for periodic in (False, True):
            dom = Domain.cubic(8, cutoff=1.0, periodic=periodic)
            pos = dom.sample_uniform(jax.random.PRNGKey(5), 1200)
            state = ParticleState(pos)
            p_d = plan(dom, kern, positions=pos, strategy="xpencil",
                       backend="halo", n_shards=4)
            f_d, q_d = p_d.execute(state)
            p_p = plan(dom, kern, m_c=p_d.m_c, positions=pos,
                       strategy="xpencil", backend="halo", n_shards=4,
                       layout="packed", compact=True)
            f_p, q_p = p_p.execute(state)
            assert np.array_equal(np.asarray(f_p), np.asarray(f_d)), periodic
            assert np.array_equal(np.asarray(q_p), np.asarray(q_d)), periodic

            tight = dataclasses.replace(p_p, row_cap=8)
            assert tight.check_overflow(state)
            (f2, _), grown = tight.execute_or_replan(state)
            assert grown.row_cap > 8 and grown.m_c == p_p.m_c
            assert grown.shard_cap == p_p.shard_cap
            assert np.array_equal(np.asarray(f2), np.asarray(f_d))
        print("PACKED_HALO_OK")
    """)
    assert "PACKED_HALO_OK" in out
