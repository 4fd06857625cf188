"""Resilience layer under seeded fault injection (``repro.testing.chaos``).

The acceptance contract of the resilient execution layer: under any
injected fault schedule (overflow, NaN, straggler, transient backend
error, shard loss) ``plan.execute_checked`` and the ``ServingEngine``
never raise to the caller, every request terminates with a definite
status, retry counts respect the bound, and every degraded-path output is
parity-checked against the healthy path. With injection disabled, the
fault points are no-ops and all bit-identical guarantees (including the
serving steady-state zero-recompile assertion) still hold.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Domain, ParticleState, degradation_ladder,
                        fallback_plan, make_lennard_jones, plan, plan_health,
                        recompile_count, reset_health, scenarios)
from repro.core import api, autotune as at
from repro.serve import (RESPONSE_STATUSES, ServeMetrics, ServingEngine,
                         VirtualClock, classify)
from repro.testing import chaos


def _dom(division=4):
    return Domain.cubic(division, cutoff=1.0)


def _state(dom, n=80, seed=0, scenario="uniform"):
    pos = scenarios.sample(scenario, dom, jax.random.PRNGKey(seed), n)
    return ParticleState(pos)


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(autouse=True)
def _fresh_health():
    reset_health()
    yield
    reset_health()


# ---------------------------------------------------------------------------
# the fault registry itself
# ---------------------------------------------------------------------------

def test_fault_spec_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        chaos.FaultSpec("core.dispatch", "explode")
    with pytest.raises(ValueError, match="p must be"):
        chaos.FaultSpec("core.dispatch", "error", p=1.5)


def test_schedule_is_deterministic_per_seed():
    def pattern(seed):
        with chaos.inject(chaos.FaultSpec("s", "error", p=0.3),
                          seed=seed) as st:
            return [st.fire("s", "error") is not None for _ in range(200)]

    a, b, c = pattern(7), pattern(7), pattern(8)
    assert a == b                       # same seed replays the schedule
    assert a != c                       # different seed differs
    assert 20 < sum(a) < 100            # p=0.3 actually thins the firings


def test_after_and_max_fires_window():
    with chaos.inject(chaos.FaultSpec("s", "error", after=2, max_fires=3)):
        fired = [chaos.fire("s", "error") is not None for _ in range(8)]
    assert fired == [False, False, True, True, True, False, False, False]


def test_inactive_fault_points_are_noops():
    assert not chaos.active()
    assert chaos.fire("s", "error") is None
    chaos.maybe_raise("s")                        # must not raise
    assert chaos.maybe_delay("s") == 0.0
    x = jnp.ones((3, 3))
    assert chaos.corrupt("s", x) is x             # identity, not a copy
    assert not chaos.forced_overflow("s")
    assert chaos.snapshot()["total_fires"] == 0


def test_contexts_nest_and_restore():
    with chaos.inject(chaos.FaultSpec("outer", "error")) as outer:
        with chaos.inject(chaos.FaultSpec("inner", "error")) as inner:
            assert chaos.state() is inner
            assert chaos.fire("outer", "error") is None   # outer masked
        assert chaos.state() is outer
        assert chaos.fire("outer", "error") is not None
    assert chaos.state() is None


def test_snapshot_counts_fires_per_point():
    with chaos.inject(chaos.FaultSpec("a", "error", max_fires=2),
                      chaos.FaultSpec("b", "delay", param=0.0)) as st:
        for _ in range(4):
            st.fire("a", "error")
        st.fire("b", "delay")
        snap = st.snapshot()
    assert snap["fires"] == {"a/error": 2, "b/delay": 1}
    assert snap["total_fires"] == 3 and snap["total_visits"] == 5


# ---------------------------------------------------------------------------
# guarded dispatch: plan.execute_checked
# ---------------------------------------------------------------------------

def test_execute_checked_clean_path_bit_identical():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    f_ref, u_ref = p.execute(state)
    (f, u), report = p.execute_checked(state)
    _assert_bitwise(f, f_ref)
    _assert_bitwise(u, u_ref)
    assert report.status == "ok" and report.ladder_level == 0
    assert report.retries == 0 and not report.faults
    assert report.nonfinite == 0 and report.overflow is None


def test_nonfinite_output_detected_and_retried():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    f_ref, u_ref = p.execute(state)
    with chaos.inject(chaos.FaultSpec("core.dispatch", "nonfinite",
                                      max_fires=1)):
        (f, u), report = p.execute_checked(state)
    assert report.nonfinite > 0 and report.retries == 1
    assert any("NonFinite" in s for s in report.faults)
    _assert_bitwise(f, f_ref)            # the retry produced clean output
    _assert_bitwise(u, u_ref)


def test_always_failing_dispatch_is_bounded_and_never_raises():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    with chaos.inject(chaos.FaultSpec("core.dispatch", "error")):
        (f, u), report = p.execute_checked(state, max_retries=5)
    assert report.status == "failed"
    assert report.retries == 6                    # bound + the final check
    assert not np.any(np.asarray(f)) and not np.any(np.asarray(u))


def test_straggler_delay_is_simulated_not_burned():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    f_ref, u_ref = p.execute(state)
    clock = VirtualClock()
    with chaos.inject(chaos.FaultSpec("core.dispatch", "delay",
                                      param=1.5, max_fires=1)) as st:
        (f, u), report = p.execute_checked(state, sleep=clock.advance)
    assert clock.now() == 1.5 and st.fire_count(kind="delay") == 1
    assert report.status == "ok"                  # latency is not an error
    _assert_bitwise(f, f_ref)
    _assert_bitwise(u, u_ref)


def test_forced_overflow_replans_are_bounded():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    f_ref, u_ref = p.execute(state)
    with chaos.inject(chaos.FaultSpec("core.binning", "overflow")):
        (f, u), report = p.execute_checked(state, max_replans=3)
    assert report.overflow == "injected"
    assert report.replans <= 3                    # no replan storm
    assert report.status == "ok"
    _assert_bitwise(f, f_ref)
    _assert_bitwise(u, u_ref)


def test_degradation_ladder_construction():
    dom = _dom()
    state = _state(dom)
    p_pal = plan(dom, make_lennard_jones(), positions=state.positions,
                 strategy="xpencil", backend="pallas", interpret=True)
    rungs = degradation_ladder(p_pal)
    assert [r.backend for r in rungs] == ["pallas", "reference"]
    assert fallback_plan(p_pal).backend == "reference"

    p_packed = plan(dom, make_lennard_jones(), positions=state.positions,
                    strategy="xpencil", layout="packed")
    assert [r.layout for r in degradation_ladder(p_packed)] == [
        "packed", "dense"]

    p_compact = plan(dom, make_lennard_jones(), positions=state.positions,
                     strategy="xpencil", compact=True)
    assert [r.compact for r in degradation_ladder(p_compact)] == [
        True, False]

    p_ref = plan(dom, make_lennard_jones(), positions=state.positions,
                 strategy="xpencil")
    assert degradation_ladder(p_ref) == (p_ref,)   # nowhere left to go


def test_breaker_trips_down_ladder_and_parity_holds():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions,
             strategy="xpencil", layout="packed")
    f_ref, u_ref = p.execute(state)
    # exactly _FAILURE_THRESHOLD transient errors: the breaker trips one
    # rung down (packed -> dense) and the next attempt succeeds there
    with chaos.inject(chaos.FaultSpec("core.dispatch", "error",
                                      max_fires=api._FAILURE_THRESHOLD)):
        (f, u), report = p.execute_checked(state)
    assert report.breaker_trips == 1
    assert report.status == "degraded" and report.layout == "dense"
    assert plan_health(p).level == 1
    _assert_bitwise(f, f_ref)             # degraded rung is bit-identical
    _assert_bitwise(u, u_ref)


@pytest.fixture
def lowering_fails(monkeypatch):
    """The pallas dense xpencil backend replaced by one whose lowering
    fails, as a kernel the chip's compiler refuses would."""
    import repro.kernels  # noqa: F401  (register the real kernels first)

    def refused(p, bins, state):
        raise ValueError("Mosaic failed to lower the kernel body")
    api.clear_executor_cache()
    monkeypatch.setitem(api._BACKENDS, ("pallas", "xpencil", "dense"),
                        refused)
    yield
    api.clear_executor_cache()


@pytest.mark.parametrize("entry", ["execute_checked", "trajectory"])
def test_lowering_error_escapes_instead_of_degrading(lowering_fails, entry):
    """A program the compiler refuses is a defect, not a runtime fault:
    it raises out of the guarded entry points and never steps down the
    ladder to the reference rung."""
    dom = _dom()
    state = _state(dom)
    # a kernel no other test plans with: no cached trace can hide the error
    p = plan(dom, make_lennard_jones(sigma=0.21), positions=state.positions,
             strategy="xpencil", backend="pallas", interpret=True)
    with pytest.raises(ValueError, match="failed to lower"):
        if entry == "execute_checked":
            p.execute_checked(state)
        else:
            p.trajectory(state, n_steps=2, dt=1e-3)
    assert plan_health(p).level == 0


def test_compiled_program_goes_with_its_executor():
    """The program compiled ahead of an execute lives only as long as the
    cached executor it belongs to: evicting the executor frees it, so the
    executor cache's bound is the only one."""
    import gc
    import weakref
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(sigma=0.23), positions=state.positions,
             strategy="xpencil")
    compiled = p.compile(state)
    fn = api._executor(p, tuple(sorted(state.fields)))
    assert api._COMPILED[fn][next(iter(api._COMPILED[fn]))] is compiled
    gone = weakref.ref(fn)
    del fn, compiled
    api.clear_executor_cache()
    gc.collect()
    assert gone() is None


def test_breaker_recovers_after_clean_streak():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions,
             strategy="xpencil", layout="packed")
    with chaos.inject(chaos.FaultSpec("core.dispatch", "error",
                                      max_fires=api._FAILURE_THRESHOLD)):
        p.execute_checked(state)
    assert plan_health(p).level == 1
    recovered = False
    for _ in range(api._RECOVERY_THRESHOLD):
        (_, _), report = p.execute_checked(state)
        recovered = recovered or report.recovered
    assert recovered and plan_health(p).level == 0
    (_, _), report = p.execute_checked(state)
    assert report.status == "ok" and report.ladder_level == 0


def test_health_key_survives_replan():
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    health = plan_health(p)
    health.level = 0
    health.consec_failures = 2
    grown = dataclasses.replace(p, m_c=p.m_c + 8)
    assert plan_health(grown) is health   # replan keeps breaker state


def test_shard_loss_triggers_elastic_shrink_with_parity():
    dom = _dom()
    state = _state(dom)
    p_ref = plan(dom, make_lennard_jones(), positions=state.positions,
                 strategy="xpencil")
    f_ref, u_ref = p_ref.execute(state)
    p2 = plan(dom, make_lennard_jones(), positions=state.positions,
              strategy="xpencil", backend="halo", n_shards=2)
    with chaos.inject(chaos.FaultSpec("dist.exchange", "shard_loss",
                                      max_fires=1)):
        (f, u), report = p2.execute_checked(state)
    assert report.shard_shrinks == 1
    assert report.plan.n_shards == 1      # rebuilt at the survivor count
    assert report.status in ("ok", "degraded")
    _assert_bitwise(f, f_ref)
    _assert_bitwise(u, u_ref)


def test_execute_checked_survives_arbitrary_schedule():
    """The headline guarantee: any mixed schedule -> no exception, a
    definite status, bounded retries."""
    dom = _dom()
    state = _state(dom)
    p = plan(dom, make_lennard_jones(), positions=state.positions)
    specs = (
        chaos.FaultSpec("core.dispatch", "error", p=0.4),
        chaos.FaultSpec("core.dispatch", "nonfinite", p=0.2),
        chaos.FaultSpec("core.dispatch", "delay", p=0.3, param=0.01),
        chaos.FaultSpec("core.binning", "overflow", p=0.2),
    )
    clock = VirtualClock()
    for seed in range(5):
        with chaos.inject(*specs, seed=seed):
            (f, u), report = p.execute_checked(state, sleep=clock.advance)
        assert report.status in ("ok", "degraded", "failed")
        assert report.retries <= api._FAILURE_THRESHOLD * len(
            degradation_ladder(p)) + 1
        assert np.all(np.isfinite(np.asarray(f)))


# ---------------------------------------------------------------------------
# serving tier: deadlines, retries, per-class breaker
# ---------------------------------------------------------------------------

def _drain(eng, max_rounds=500):
    """Advance past every backoff holdback until the queue is empty."""
    for _ in range(max_rounds):
        if eng.pending() == 0:
            return
        eng.clock.advance(eng.retry_cap_s)
        eng.flush()
    raise AssertionError(f"queue did not drain ({eng.pending()} pending)")


def test_deadline_expired_requests_never_dispatch():
    dom = _dom()
    eng = ServingEngine(max_batch=4, max_wait=0.5)
    # already expired at submit
    r0 = eng.submit(dom, _state(dom, 40), deadline_s=0.0)
    # expires while queued: the sweep runs before any dispatch
    r1 = eng.submit(dom, _state(dom, 40), deadline_s=0.1)
    r2 = eng.submit(dom, _state(dom, 40))          # no deadline
    eng.clock.advance(1.0)
    eng.flush()
    by_id = {r.req_id: r for r in eng.take_responses()}
    assert by_id[r0].status == "deadline" and by_id[r0].forces is None
    assert by_id[r1].status == "deadline" and by_id[r1].forces is None
    assert by_id[r2].status == "ok"
    assert eng.metrics.deadline_expired == 2
    assert eng.metrics.batches == 1                # one real dispatch


def test_serving_retries_are_bounded_and_terminal():
    dom = _dom()
    eng = ServingEngine(max_batch=2, max_wait=0.01, max_retries=3)
    with chaos.inject(chaos.FaultSpec("serve.dispatch", "error")):
        ids = [eng.submit(dom, _state(dom, 40, seed=i)) for i in range(4)]
        _drain(eng)
        responses = eng.take_responses()
    assert {r.req_id for r in responses} == set(ids)
    assert all(r.status == "failed" for r in responses)
    assert all(r.attempts == eng.max_retries + 1 for r in responses)
    assert eng.metrics.failed == 4
    assert eng.metrics.retries > 0
    assert eng.pending() == 0


def test_transient_fault_recovers_with_parity():
    dom = _dom()
    eng = ServingEngine(max_batch=2, max_wait=0.01)
    state = _state(dom, 40)
    with chaos.inject(chaos.FaultSpec("serve.dispatch", "error",
                                      max_fires=1)):
        rid = eng.submit(dom, state)
        _drain(eng)
        resp = {r.req_id: r for r in eng.take_responses()}[rid]
    assert resp.status == "ok" and resp.attempts == 1
    sc = classify(dom, eng.kernel, 40, (), eng.min_n_cap)
    f_ref, u_ref = eng.class_plan(sc).execute(state)
    _assert_bitwise(resp.forces, f_ref)
    _assert_bitwise(resp.potential, u_ref)
    assert eng.metrics.retries == 1 and eng.metrics.failed == 0


def test_class_breaker_quarantines_then_restores():
    dom = _dom()
    eng = ServingEngine(max_batch=1, max_wait=0.01, max_retries=0,
                        breaker_threshold=2, breaker_recovery=2)
    state = _state(dom, 40)
    sc = classify(dom, eng.kernel, 40, (), eng.min_n_cap)
    with chaos.inject(chaos.FaultSpec("serve.dispatch", "error",
                                      max_fires=2)):
        for i in range(2):
            eng.submit(dom, _state(dom, 40, seed=i))
            eng.flush()
    assert eng.class_breaker(sc).open
    assert eng.metrics.breaker_opens == 1
    assert eng.metrics.breaker_open_classes == 1
    primary = eng.class_primary(sc)
    quarantined = eng.class_plan(sc)
    assert quarantined == api.fallback_plan(primary)
    assert quarantined.backend == "reference"

    # the quarantined class still answers — and bit-identically, because
    # the fallback rung computes the same forces
    rid = eng.submit(dom, state)
    eng.flush()
    resp = {r.req_id: r for r in eng.take_responses()}[rid]
    assert resp.status == "ok"
    f_ref, u_ref = primary.execute(state)
    _assert_bitwise(resp.forces, f_ref)
    _assert_bitwise(resp.potential, u_ref)

    # one more clean dispatch closes the breaker and restores the primary
    eng.submit(dom, _state(dom, 40, seed=9))
    eng.flush()
    eng.take_responses()
    assert not eng.class_breaker(sc).open
    assert eng.metrics.breaker_closes == 1
    assert eng.metrics.breaker_open_classes == 0
    assert eng.class_plan(sc) == primary


def test_quarantine_does_not_poison_other_classes():
    dom = _dom()
    eng = ServingEngine(max_batch=1, max_wait=0.01, max_retries=0,
                        breaker_threshold=1, breaker_recovery=100)
    sc_small = classify(dom, eng.kernel, 40, (), eng.min_n_cap)
    sc_big = classify(dom, eng.kernel, 200, (), eng.min_n_cap)
    assert sc_small != sc_big
    with chaos.inject(chaos.FaultSpec("serve.dispatch", "error",
                                      max_fires=1)):
        eng.submit(dom, _state(dom, 40))       # trips sc_small's breaker
        eng.flush()
    eng.submit(dom, _state(dom, 200))
    eng.flush()
    eng.take_responses()
    assert eng.class_breaker(sc_small).open
    br_big = eng.class_breaker(sc_big)
    assert br_big is None or not br_big.open
    assert eng.class_primary(sc_big) is None   # never quarantined


def test_serving_survives_mixed_fault_schedule():
    """The serving headline: a mixed seeded schedule over a real workload
    -> the queue drains, every request gets a definite status, nothing
    raises, and the fault counters are visible in the snapshot."""
    dom = _dom()
    eng = ServingEngine(max_batch=4, max_wait=0.01, max_retries=3)
    specs = (
        chaos.FaultSpec("serve.dispatch", "error", p=0.3),
        chaos.FaultSpec("serve.dispatch", "delay", p=0.2, param=0.02),
        chaos.FaultSpec("serve.dispatch", "nonfinite", p=0.1),
    )
    n = 30
    with chaos.inject(*specs, seed=42) as st:
        for i in range(n):
            eng.submit(dom, _state(dom, 40 + 10 * (i % 3), seed=i),
                       deadline_s=None if i % 5 else 30.0)
            eng.clock.advance(0.005)
            eng.poll()
        _drain(eng)
        assert st.fire_count() > 0             # the schedule actually bit
        responses = eng.take_responses()
    assert len(responses) == n
    assert all(r.status in RESPONSE_STATUSES for r in responses)
    ok = [r for r in responses if r.status == "ok"]
    assert ok                                  # some requests succeeded
    assert all(np.all(np.isfinite(np.asarray(r.forces))) for r in ok)
    snap = eng.metrics.snapshot()
    assert snap["faults"] > 0
    assert snap["served"] + snap["failed"] + snap["deadline_expired"] == n
    assert eng.pending() == 0


def test_fault_free_serving_keeps_zero_recompile_steady_state():
    """With injection disabled the resilience layer must be invisible:
    the PR 6 steady-state guarantee (warm second pass -> zero recompiles,
    zero timing runs) still holds, and responses stay bit-identical."""
    dom = _dom()
    eng = ServingEngine(max_batch=4, max_wait=0.01)
    states = [_state(dom, 50, seed=i) for i in range(8)]

    def one_pass():
        out = {}
        for s in states:
            rid = eng.submit(dom, s)
            eng.clock.advance(0.02)
            eng.poll()
        eng.flush()
        for r in eng.take_responses():
            out[r.req_id] = r
        return out

    first = one_pass()
    eng.clock = VirtualClock()
    eng.metrics = ServeMetrics()
    rc0, tr0 = recompile_count(), at.timing_run_count()
    second = one_pass()
    assert recompile_count() == rc0
    assert at.timing_run_count() == tr0
    assert all(r.status == "ok" for r in second.values())
    f1 = [first[k].forces for k in sorted(first)]
    f2 = [second[k].forces for k in sorted(second)]
    for a, b in zip(f1, f2):
        _assert_bitwise(a, b)
    snap = eng.metrics.snapshot()
    assert snap["faults"] == 0 and snap["retries"] == 0
    assert snap["breaker_opens"] == 0 and snap["failed"] == 0
