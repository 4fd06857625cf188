"""Device time by engine layer: each device op of a profiler trace is
charged to the top-level ``jax.named_scope`` its HLO instruction was
issued under, inside the same window ``trace_reduce`` reads.

The engine's scopes (ARCHITECTURE.md, Observability) stand here as
literal strings, not imports from the engine, so a renamed scope shows
as a missing number:

    bin            cell ids, counts, sort and rank (bin/sort), the slot
                   planes (bin/scatter), packed rows (bin/pack), the SFC
                   pair list (bin/sfc)
    ghost          the periodic ghost ring
    pair           the pair kernel's lane staging and call: its ops that
                   are not kernel events are ``pair_staging``
    scatter_back   slot planes back to particle order
    integrate, bin_refresh, exchange
                   the trajectory's integrator and in-place refresh, the
                   ghost-plane exchange across chips
    unscoped       every other XLA op

The name stack of an instruction is its ``metadata.op_name``. On a v5e
the trace carries it in the HLO protos of the ``/host:metadata`` plane,
one per program; a device op's event gives its instruction as the start
of its HLO text, and no event-level statistic holds the name stack or
the program (those sit on the event's metadata, which
``jax.profiler.ProfileData`` does not expose, as it does not expose the
protos). So this module reads the protos from the ``.xplane.pb`` bytes
with a small protobuf reader and looks each instruction up in every
program; one that two programs name differently is left unscoped.

    python3 bench/scopes.py --workload <cell> --seed <n> --seconds <s>

runs the cell's traffic for a window under the profiler, as
``bench/run.py --trace 1`` does, and prints one JSON line: the window's
kernel and XLA time and the time of each scope, in ms per unit.
"""

from __future__ import annotations

import collections
import glob
import os
import re

try:
    from . import trace_reduce
except ImportError:                     # run as a script
    import trace_reduce

SCOPES = ("bin", "ghost", "pair", "scatter_back", "integrate",
          "bin_refresh", "exchange")
METADATA_PLANE = "/host:metadata"
_INSTR = re.compile(r"%?([^\s=%]+)")


# -- a protobuf reader for the few XSpace and HLO fields used here --------

def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf: bytes):
    """(field number, value) of each field of one message: ints for
    varints, bytes for length-delimited and fixed fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, value


def _first(buf: bytes, number: int, default=b""):
    return next((v for f, v in _fields(buf) if f == number), default)


def _hlo_op_names(hlo_proto: bytes) -> dict:
    """HloProto -> {instruction name: metadata.op_name} over every
    computation (HloProto.hlo_module 1 -> computations 3 -> instructions
    2 -> name 1, metadata 7 -> op_name 2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for f, comp in _fields(module):
        if f != 3:
            continue
        for g, instr in _fields(comp):
            if g != 2:
                continue
            name = op_name = b""
            for h, v in _fields(instr):
                if h == 1:
                    name = v
                elif h == 7:
                    op_name = _first(v, 2)
            if op_name:
                out[name.decode()] = op_name.decode()
    return out


def program_op_names(xspace: bytes) -> dict:
    """{program id: {instruction name: op_name}} from the HLO protos of
    the metadata plane (XSpace.planes 1; XPlane name 2, event_metadata 4
    (map entry: key 1, XEventMetadata 2: id 1, stats 5); XStat
    bytes_value 6)."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1 or _first(plane, 2) != METADATA_PLANE.encode():
            continue
        for g, entry in _fields(plane):
            if g != 4:
                continue
            meta = _first(entry, 2)
            pid = _first(meta, 1, 0)
            for h, stat in _fields(meta):
                if h == 5:
                    proto = _first(stat, 6)
                    if proto:
                        out[pid] = _hlo_op_names(proto)
    return out


# -- the reduction ---------------------------------------------------------

def scope_of(op_name: str):
    """The first engine scope in an op's name stack, else None."""
    return next((p for p in op_name.split("/") if p in SCOPES), None)


def _op_name(event_name: str, names: dict) -> str:
    """The op_name of a device event's instruction, where the programs
    that hold an instruction of that name agree on it."""
    instr = _INSTR.match(event_name).group(1)
    found = {n[instr] for n in names.values() if instr in n}
    return found.pop() if len(found) == 1 else ""


def scopes(pd, names: dict) -> dict:
    """Device seconds in the window per scope, averaged over the device
    planes like ``trace_reduce.reduce``: one key per scope found (``pair``
    as ``pair_staging``, kernel events left out: they are ``kernel_s``)
    and ``unscoped`` for XLA ops under none. ``names`` is
    :func:`program_op_names` of the same trace."""
    lo, hi = trace_reduce.window(trace_reduce.host_spans(pd))
    per = collections.Counter()
    planes = 0
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        seen = False
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for e in line.events:
                seen = True
                d = min(e.end_ns, hi) - max(e.start_ns, lo)
                if d <= 0 or trace_reduce.is_kernel(
                        e.name, trace_reduce._stats(e)):
                    continue
                scope = scope_of(_op_name(e.name, names)) or "unscoped"
                per["pair_staging" if scope == "pair" else scope] += d
        planes += seen
    if not planes:
        raise RuntimeError("the trace holds no device operations")
    return {k: v * 1e-9 / planes for k, v in per.items()}


def load(trace_dir: str):
    """(ProfileData, program op names) of the one trace under
    ``trace_dir``."""
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    with open(found[0], "rb") as f:
        raw = f.read()
    from jax.profiler import ProfileData
    return ProfileData.from_serialized_xspace(raw), program_op_names(raw)


def per_unit_ms(run: dict, key: str):
    """A layer reader's value: ``run["trace"]["scopes"][key]`` in ms per
    unit of the window, None where the trace holds no such scope."""
    t = run["trace"]
    found = (t or {}).get("scopes") or {}
    if key not in found or not run["window"].units:
        return None
    return 1e3 * found[key] / run["window"].units


def main(argv=None) -> int:
    import argparse
    import json
    import pathlib
    import shutil
    import sys
    import tempfile
    import time

    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root), str(root / "src")]
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("scopes.py: JAX found no TPU", file=sys.stderr)
        return 2
    from bench import harness

    spec = harness.manifest(root / "BENCHMARK.json")
    cfg = harness.config(harness.cell(spec, args.workload)["config"])
    mix = harness.traffic(harness.cell(spec, args.workload)["traffic"])
    traffic = harness.entry(mix["entry"]).Traffic(
        cfg, mix, harness.scene(cfg["scene"]),
        harness.pair(cfg["pair"]["kind"]), harness._key(args.seed), False)
    traffic.setup()
    setup_s = time.perf_counter() - t_start
    where = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        jax.profiler.start_trace(where)
        try:
            w = traffic.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        pd, names = load(where)
        summary = trace_reduce.reduce(pd)
        found = scopes(pd, names)
    finally:
        shutil.rmtree(where, ignore_errors=True)
    ms = 1e3 / w.units
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "setup_s": setup_s,
        "units": w.units, "window_s": summary["window_s"],
        "busy_s": summary["busy_s"], "kernel_ms": summary["kernel_s"] * ms,
        "xla_ms": summary["xla_s"] * ms,
        "scopes_ms": {k: v * ms for k, v in sorted(found.items())},
        "programs": len(names)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
