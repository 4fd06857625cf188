"""Observability layer: tracer, profiler spans, metrics registry, audit, shims.

The contracts under test, in the order the module docstrings state them:

* tracing is **off by default** and a disabled tracer is a no-op — zero
  recorded spans and unchanged ``dispatch_count`` semantics;
* enabled tracing records spans/events with attrs and exports both JSONL
  and Chrome ``trace_event`` JSON that parse and carry the span names the
  instrumented subsystems emit;
* while a ``jax.profiler`` session records, the same spans land in its
  XSpace, and the ring's absolute origin puts them on its clock;
* the metrics registry is the one counter store: the historical
  ``dispatch_count`` / ``recompile_count`` / ``replan_count`` /
  ``timing_run_count`` functions are shims over it, ``render_prom``
  exposes the families with (backend, strategy, layout) labels, and **one
  ``reset_counters()`` clears every steady-state counter** (the footgun
  this PR closes);
* the traffic audit reports near-zero drift where the uniform model is
  honest and surfaces a deliberately mis-modelled candidate as nonzero
  drift;
* serving metrics edge cases: percentile interpolation, NaN-on-empty,
  VirtualClock monotonicity, LatencyStats snapshot stability.
"""

import json
import math

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import Domain, make_lennard_jones, plan, scenarios
from repro.core import api, autotune
from repro.core.api import ParticleState
from repro.serve.metrics import LatencyStats, ServeMetrics, VirtualClock, \
    percentile


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Tracing off + empty buffer around every test (process-global)."""
    obs.disable()
    obs.clear()
    yield
    obs.disable()
    obs.clear()


@pytest.fixture(scope="module")
def tiny():
    dom = Domain.cubic(3, cutoff=1.0)
    pos = dom.sample_uniform(jax.random.PRNGKey(0), 60)
    p = plan(dom, make_lennard_jones(), positions=pos)
    return dom, pos, p, ParticleState(pos)


# ---------------------------------------------------------------- tracer

def test_tracing_disabled_records_nothing(tiny):
    _, _, p, state = tiny
    before = api.dispatch_count()
    with obs.trace("should.not.appear", k=1):
        pass
    obs.event("also.not.recorded")
    p.execute(state)
    assert obs.stats()["recorded"] == 0
    assert obs.spans() == []
    # counting semantics are unchanged by the (disabled) tracer
    assert api.dispatch_count() == before + 1


def test_tracing_records_spans_events_and_errors():
    obs.enable()
    with obs.trace("outer", layer="test") as sp:
        sp.set(extra=7)
        obs.event("tick", n=1)
    with pytest.raises(ValueError):
        with obs.trace("boom"):
            raise ValueError("x")
    recs = obs.spans()
    names = [r["name"] for r in recs]
    assert names == ["tick", "outer", "boom"]   # spans close after events
    outer = recs[1]
    assert outer["ph"] == "X" and outer["dur"] >= 0.0
    assert outer["attrs"] == {"layer": "test", "extra": 7}
    assert recs[0]["ph"] == "i"
    assert recs[2]["attrs"]["error"] == "ValueError"
    assert obs.stats()["recorded"] == 3


def test_tracing_context_manager_restores_state():
    assert not obs.tracing_enabled()
    with obs.tracing():
        assert obs.tracing_enabled()
        obs.event("inside")
    assert not obs.tracing_enabled()
    assert [r["name"] for r in obs.spans()] == ["inside"]


def test_ring_buffer_caps_and_counts_drops():
    obs.enable(capacity=4)
    for i in range(10):
        obs.event("e", i=i)
    st = obs.stats()
    assert st["recorded"] == 4 and st["dropped"] == 6
    assert [r["attrs"]["i"] for r in obs.spans()] == [6, 7, 8, 9]


def test_execute_emits_plan_spans(tiny):
    _, _, p, state = tiny
    obs.enable()
    p.execute(state)
    by_name = {r["name"]: r for r in obs.spans()}
    assert "plan.execute" in by_name
    at = by_name["plan.execute"]["attrs"]
    assert at["strategy"] == p.strategy and at["layout"] == p.layout
    assert at["backend"] == p.backend


def test_exports_parse_and_convert(tiny, tmp_path):
    _, _, p, state = tiny
    obs.enable()
    p.execute(state)
    obs.event("marker", k="v")
    jl = tmp_path / "t.trace.jsonl"
    ch = tmp_path / "t.trace.json"
    n_jl = obs.export_jsonl(jl)
    n_ch = obs.export_chrome_trace(ch)
    assert n_jl == n_ch == obs.stats()["recorded"]
    lines = [json.loads(l) for l in jl.read_text().splitlines()]
    assert {r["name"] for r in lines} >= {"plan.execute", "marker"}
    payload = json.loads(ch.read_text())
    evs = payload["traceEvents"]
    assert payload["displayTimeUnit"] == "ms" and len(evs) == n_ch
    for e in evs:
        assert {"name", "ph", "ts", "pid", "tid"} <= set(e)
        assert e["ph"] in ("X", "i")
        if e["ph"] == "X":
            assert e["dur"] >= 0                      # microseconds
        else:
            assert e["s"] == "t"
    # the CLI summarizes the JSONL form
    import subprocess, sys, pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    out = subprocess.run(
        [sys.executable, str(root / "tools" / "trace_view.py"), str(jl)],
        capture_output=True, text=True)
    assert out.returncode == 0 and "plan.execute" in out.stdout


# -------------------------------------------------------------- registry

def test_registry_counter_labels_and_total():
    reg = obs.MetricsRegistry()
    reg.counter("hits", kind="a").inc()
    reg.counter("hits", kind="a").inc(2)
    reg.counter("hits", kind="b").inc()
    assert reg.total("hits") == 4.0
    assert reg.get("hits", kind="a").value == 3.0
    assert reg.get("hits", kind="zzz") is None
    assert reg.total("absent") == 0.0
    snap = reg.snapshot()
    assert snap["hits"] == {'{kind="a"}': 3.0, '{kind="b"}': 1.0}


def test_registry_kind_conflict_rejected():
    reg = obs.MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_render_prom_families_and_labels(tiny):
    _, _, p, state = tiny
    api.reset_counters()
    p.execute(state)
    text = obs.render_prom()
    assert "# TYPE repro_dispatch_total counter" in text
    want = (f'repro_dispatch_total{{backend="{p.backend}",'
            f'layout="{p.layout}",strategy="{p.strategy}"}} 1')
    assert want in text
    # the recompile family carries the same label set
    assert "# TYPE repro_recompile_total counter" in text
    assert f'strategy="{p.strategy}"' in text


def test_histogram_renders_summary():
    reg = obs.MetricsRegistry()
    h = reg.histogram("lat")
    for v in (1.0, 3.0):
        h.observe(v)
    text = reg.render_prom()
    assert "lat_count 2" in text and "lat_sum 4" in text
    assert "lat_min 1" in text and "lat_max 3" in text
    # an empty (freshly reset) histogram renders NaN min/max, not a crash
    reg.reset()
    assert "lat_min nan" in reg.render_prom()


def test_one_reset_clears_every_steady_state_counter(tiny, tmp_path,
                                                     monkeypatch):
    """The counter-reset footgun: ``reset_counters()`` must clear the
    dispatch / recompile / replan / rebin / autotune families in one call
    — a test that resets 'the counters' and then asserts steady-state
    zero must not be lied to by a family living elsewhere."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache"))
    dom, pos, p, state = tiny
    p.execute(state)
    autotune.tune(dom, make_lennard_jones(), pos, top_k=2, reps=1,
                  budget_s=0.01)
    reg = obs.registry
    assert api.dispatch_count() > 0
    assert api.recompile_count() > 0
    assert autotune.timing_run_count() > 0
    api.reset_counters()
    for fn in (api.dispatch_count, api.recompile_count, api.replan_count,
               autotune.timing_run_count):
        assert fn() == 0, fn.__name__
    for fam in (api.DISPATCH_TOTAL, api.RECOMPILE_TOTAL, api.REPLAN_TOTAL,
                autotune.TIMING_RUNS_TOTAL, autotune.CACHE_TOTAL):
        assert reg.total(fam) == 0.0, fam
    # cached Counter handles keep working after the in-place reset
    p.execute(state)
    assert api.dispatch_count() == 1


def test_serve_counters_mirror_into_registry():
    m = ServeMetrics()
    m.submitted = 5
    m.served = 3
    assert obs.registry.get("serve_submitted").value == 5.0
    assert obs.registry.get("serve_served").value == 3.0
    assert "serve_submitted 5" in obs.render_prom()


# ----------------------------------------------------------------- audit

@pytest.fixture(scope="module")
def uniform():
    """A periodic uniform scene big enough for the uniform traffic model
    to be honest (open 3^3 boxes are all boundary, and boundary is
    exactly what the uniform model ignores)."""
    dom = Domain.cubic(6, cutoff=1.0, periodic=True)
    pos = dom.sample_uniform(jax.random.PRNGKey(1), 4 * dom.n_cells)
    return dom, pos


def test_audit_uniform_scene_has_small_drift(uniform):
    dom, pos = uniform
    rep = obs.audit_candidate(dom, pos, strategy="xpencil", m_c=12)
    assert math.isfinite(rep["drift"])
    assert abs(rep["drift"]) < 0.25          # uniform model, uniform scene
    assert rep["interactions"] > 0


def test_audit_flags_deliberately_mismodelled_candidate(uniform):
    """A candidate whose modelled cost is 10x the honest model must
    surface drift ~= -0.9 — the audit is the tripwire for a cost model
    that silently rots away from what the schedules actually move."""
    dom, pos = uniform
    honest = obs.audit_candidate(dom, pos, strategy="xpencil", m_c=12)
    lied = obs.audit_candidate(dom, pos, strategy="xpencil", m_c=12,
                               modelled=10.0 * honest["modelled_bpi"])
    assert lied["drift"] == pytest.approx(
        (honest["drift"] + 1.0) / 10.0 - 1.0, rel=1e-6)
    assert lied["drift"] < -0.8
    # recorded as the per-(strategy, layout) gauge
    g = obs.registry.get("repro_traffic_model_drift",
                         strategy="xpencil", layout="dense")
    assert g.value == pytest.approx(lied["drift"])


def test_model_drift_math():
    assert obs.model_drift(2.0, 2.0) == 0.0
    assert obs.model_drift(1.0, 1.5) == pytest.approx(0.5)
    assert math.isnan(obs.model_drift(0.0, 1.0))


def test_tune_audits_pruned_candidates(tmp_path, monkeypatch):
    """Every pruned candidate gets a model-vs-measured audit: on a
    clustered scene the uniform model undersells the interaction count,
    so the recorded drift is decisively nonzero."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "cache"))
    obs.registry.reset("repro_traffic_model_drift")
    dom = Domain.cubic(4, cutoff=1.0)
    pos = scenarios.sample_gaussian_blob(dom, jax.random.PRNGKey(2), 128,
                                         sigma_frac=0.15)
    autotune.tune(dom, make_lennard_jones(), pos, top_k=2, reps=1,
                  budget_s=0.01)
    snap = obs.registry.snapshot().get("repro_traffic_model_drift", {})
    assert snap, "tune() recorded no audits"
    assert any(abs(v) > 0.3 for v in snap.values()), snap


# -------------------------------------------------------------- profiler

def _xspace_host_events(trace_dir) -> dict:
    """name -> [(start_ns, end_ns)] of the host events of the one XSpace
    under ``trace_dir``, in absolute nanoseconds (the XSpace holds times
    from its ``profile_start_time``)."""
    import glob
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    env, = [dict(pl.stats) for pl in pd.planes
            if pl.name == "Task Environment"]
    t0 = int(env["profile_start_time"])
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(
                        (t0 + e.start_ns, t0 + e.end_ns))
    return out


def test_span_lands_in_the_profiler_trace_on_its_clock(tiny, tmp_path):
    _, _, p, state = tiny
    obs.enable()
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(p.execute(state))
    host = _xspace_host_events(tmp_path)
    (start, end), = host["plan.execute"]
    rec, = [r for r in obs.spans() if r["name"] == "plan.execute"]
    origin = obs.stats()["origin_ns"]
    assert abs(origin + rec["ts"] * 1e9 - start) < 1e6        # 1 ms
    assert abs(origin + (rec["ts"] + rec["dur"]) * 1e9 - end) < 1e6


def test_profiler_alone_records_spans_and_leaves_the_ring_empty(tiny,
                                                                 tmp_path):
    _, _, p, state = tiny
    assert not obs.active()
    with jax.profiler.trace(str(tmp_path)):
        assert obs.active() and not obs.tracing_enabled()
        jax.block_until_ready(p.execute(state))
        obs.event("marker", k="v")
    assert not obs.active()
    host = _xspace_host_events(tmp_path)
    assert len(host["plan.execute"]) == 1 and "marker" in host
    assert obs.stats()["recorded"] == 0 and obs.spans() == []


def test_exports_carry_the_ring_origin(tmp_path):
    obs.enable()
    obs.event("marker")
    origin = obs.stats()["origin_ns"]
    assert origin > 0
    obs.export_jsonl(tmp_path / "t.jsonl")
    obs.export_chrome_trace(tmp_path / "t.json")
    rec, = [json.loads(l) for l in
            (tmp_path / "t.jsonl").read_text().splitlines()]
    assert rec["origin_ns"] == origin
    payload = json.loads((tmp_path / "t.json").read_text())
    assert payload["otherData"]["origin_ns"] == origin


# ------------------------------------------------------------- sidecars

def test_write_bench_json_emits_sidecars_only_when_traced(tmp_path, tiny):
    import sys, pathlib
    root = pathlib.Path(__file__).resolve().parents[1]
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    from benchmarks.common import bench_record, write_bench_json
    _, _, p, state = tiny
    rec = [bench_record("t", "xpencil", "reference", 1e-3, 3,
                        layout="dense", drift=-0.02)]
    assert rec[0]["drift"] == -0.02
    off = tmp_path / "BENCH_off.json"
    write_bench_json(off, rec)
    assert not list(tmp_path.glob("*.trace.*"))
    obs.enable()
    p.execute(state)
    on = tmp_path / "BENCH_on.json"
    write_bench_json(on, rec)
    assert json.loads((tmp_path / "BENCH_on.trace.json").read_text())[
        "traceEvents"]
    assert (tmp_path / "BENCH_on.trace.jsonl").exists()
    metrics = json.loads((tmp_path / "BENCH_on.metrics.json").read_text())
    assert "repro_dispatch_total" in metrics


# -------------------------------------------- serve metrics edge cases

def test_percentile_two_sample_interpolation():
    assert percentile([1.0, 3.0], 50.0) == pytest.approx(2.0)
    assert percentile([1.0, 3.0], 0.0) == 1.0
    assert percentile([1.0, 3.0], 100.0) == 3.0
    assert percentile([1.0, 3.0], 75.0) == pytest.approx(2.5)
    assert percentile([5.0], 99.0) == 5.0


def test_percentile_nan_on_empty():
    assert math.isnan(percentile([], 50.0))
    s = LatencyStats()
    assert math.isnan(s.mean) and math.isnan(s.p(99.0))
    assert math.isnan(s.summary()["max_s"])


def test_virtual_clock_monotone_under_out_of_order_arrivals():
    clk = VirtualClock()
    clk.advance_to(5.0)
    # a late-scheduled arrival must not rewind the clock
    assert clk.advance_to(3.0) == 5.0
    assert clk.now() == 5.0
    with pytest.raises(ValueError):
        clk.advance(-0.1)
    clk.advance(0.5)
    assert clk() == 5.5


def test_latency_stats_snapshot_stable_under_interleaved_records():
    m = ServeMetrics()
    m.note_submit(0.0)
    m.note_submit(1.0)
    # completions land out of submission order
    m.note_served(t_submit=1.0, t_dispatch=1.5, t_done=2.0)
    snap1 = m.snapshot()
    m.note_served(t_submit=0.0, t_dispatch=0.5, t_done=3.0)
    snap2 = m.snapshot()
    assert snap1["served"] == 1 and snap2["served"] == 2
    # first snapshot unchanged by later records (it is a copy, not a view)
    assert snap1["served"] == 1
    assert snap1["total_latency"]["count"] == 1
    assert snap2["total_latency"]["count"] == 2
    assert snap2["total_latency"]["max_s"] == pytest.approx(3.0)
    assert m.t_last_done == 3.0
    assert m.rps == pytest.approx(2 / 3.0)
