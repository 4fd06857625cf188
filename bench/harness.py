"""Runs one cell of ``BENCHMARK.json`` once, from files found by name:

    bench/configs/<config>.json     sizes, physics, plan arguments, source
    bench/scenes/<scene>.py         the state a configuration names, made
                                    on the device from the seed
    bench/pairs/<kind>.py           the pair term a configuration names:
                                    the engine's kernel and the reference's
                                    formula
    bench/traffic/<traffic>.json    the entry into the engine and its
                                    parameters
    bench/entries/<entry>.py        the ``Traffic`` that drives an entry
                                    (see ``bench/drive.py``)
    bench/limits/<cell>.json        the limit of each number compared
    bench/end_to_end/<metric>.py    one reader per end-to-end metric
    bench/layers/<metric>.py        one reader per per-layer metric

A reader is a module with ``read(run) -> float | None``; ``run`` is the
dict ``measure`` builds. A reader that finds nothing to read returns
None and the metric is left out of the result. A metric whose name has a
suffix (``kernel_ms.traj``) is read by its own file where there is one,
else by the file of its base name (``kernel_ms.py``).
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import re
import shutil
import sys
import tempfile
import time

BENCH = pathlib.Path(__file__).resolve().parent
MANIFEST = BENCH.parent / "BENCHMARK.json"


def manifest(path=MANIFEST) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r}; have "
                   f"{[w['name'] for w in spec['workloads']]}")


def _json(bench: pathlib.Path, kind: str, name: str) -> dict:
    return json.loads((bench / kind / f"{name}.json").read_text())


def config(name: str, bench=BENCH) -> dict:
    return _json(pathlib.Path(bench), "configs", name)


def traffic(name: str, bench=BENCH) -> dict:
    return _json(pathlib.Path(bench), "traffic", name)


def limits(cell_name: str, bench=BENCH) -> dict:
    return _json(pathlib.Path(bench), "limits", cell_name)


def module(path: pathlib.Path):
    """Import the Python file at ``path`` (its name may hold dots)."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path.resolve()))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def scene(name: str, bench=BENCH):
    return module(pathlib.Path(bench) / "scenes" / f"{name}.py")


def pair(kind: str, bench=BENCH):
    return module(pathlib.Path(bench) / "pairs" / f"{kind}.py")


def entry(name: str, bench=BENCH):
    return module(pathlib.Path(bench) / "entries" / f"{name}.py")


def reader(kind: str, metric: str, bench=BENCH):
    """``kind`` is ``end_to_end`` or ``layers``."""
    folder = pathlib.Path(bench) / kind
    path = folder / f"{metric}.py"
    if not path.is_file():
        path = folder / f"{metric.split('.')[0]}.py"
    return module(path).read


def metrics_of(spec: dict, cell_name: str, kind: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries the cell reports."""
    return [m for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _key(seed: int):
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class _CompileCount:
    """XLA compilations while ``active`` (jax.monitoring events)."""

    EVENT = "backend_compile"

    def __init__(self):
        self.active, self.count = False, 0

    def __call__(self, event, duration, **kw):
        if self.active and self.EVENT in event:
            self.count += 1

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self)


def run_window(traffic_obj, seconds: float, trace: bool):
    """The measured window -> (Window, trace summary or None)."""
    if trace:
        return _trace(traffic_obj, seconds)
    return traffic_obj.window(seconds), None


def _trace(traffic_obj, seconds: float):
    """The window under the profiler -> (Window, trace summary)."""
    import jax
    from . import trace_reduce
    where = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(where)
        try:
            w = traffic_obj.window(seconds)
        finally:
            jax.profiler.stop_trace()
        return w, trace_reduce.reduce(trace_reduce.load(where))
    finally:
        shutil.rmtree(where, ignore_errors=True)


def memory_peak(device) -> int | None:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else int(peak)


def measure(spec: dict, cell_name: str, *, seed: int, seconds: float,
            trace: bool, t_start: float, bench=BENCH, interpret=False,
            log=print) -> dict:
    """Run the cell once -> the result line (a dict, ``checks`` last)."""
    import jax

    w_entry = cell(spec, cell_name)
    cfg = config(w_entry["config"], bench)
    mix = traffic(w_entry["traffic"], bench)
    lim = limits(cell_name, bench)
    dev = jax.devices()[0]

    traffic_obj = entry(mix["entry"], bench).Traffic(
        cfg, mix, scene(cfg["scene"], bench),
        pair(cfg["pair"]["kind"], bench), _key(seed), interpret)
    from repro.core import recompile_count
    from repro.core.api import replan_count
    info = traffic_obj.setup()
    setup_s = time.perf_counter() - t_start
    with _CompileCount() as compiles:
        r0, p0 = recompile_count(), replan_count()
        compiles.active = True
        w, summary = run_window(traffic_obj, seconds, trace)
        compiles.active = False
    log(json.dumps({"setup": info, "setup_s": setup_s, "window": {
        "seconds": w.seconds, "units": w.units, "attempted": w.attempted,
        "xla_compiles": compiles.count,
        "recompile_count": recompile_count() - r0,
        "replan_count": replan_count() - p0, **w.counters}}))
    mem = memory_peak(dev)
    traffic_obj.release()

    checks = traffic_obj.check()
    run = {"window": w, "n": info["n"], "setup_s": setup_s,
           "trace": summary}

    kind = "per_layer" if trace else "end_to_end"
    folder = "layers" if trace else "end_to_end"
    metrics = {}
    for m in metrics_of(spec, cell_name, kind):
        value = reader(folder, m["name"], bench)(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    missing = sorted(set(checks) - set(lim))
    if missing:
        raise KeyError(f"no limit for {missing} in limits/{cell_name}.json")
    correct = w.failed == 0 and all(
        math.isfinite(v) and v <= lim[k] for k, v in checks.items())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": w.attempted,
              "failed": w.failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim[k]}
                        for k, v in checks.items()}
    return result
