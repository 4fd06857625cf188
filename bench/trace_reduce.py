"""From a profiler trace to the per-layer numbers: device busy time (the
union of the intervals in which an operation ran), the pair kernel's own
time, everything else the device ran, and the idle gaps with what the
host was doing in each.

The window is the host span named ``WINDOW`` (``bench/drive.py`` opens
it around the measured loop); device time outside it is not counted.
Device operations are the events of each device plane's ``XLA Ops``
line. A kernel event is one whose name or statistics name the Mosaic
target (``tpu_custom_call``) or a Pallas call: the engine's Pallas pair
kernels. The bare word ``custom-call`` does not make a kernel: an XLA
fusion that reads a custom call's output names it among its operands
(``fusion(... %custom-call.90)``) and is XLA's time, not the kernel's.
"""

from __future__ import annotations

import collections
import glob
import os

WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
KERNEL_MARKS = ("tpu_custom_call", "pallas_call")
TOP = 10


def load(trace_dir: str):
    """The ProfileData of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(found)}")
    return ProfileData.from_file(found[0])


def _stats(event) -> dict:
    return {k: v for k, v in event.stats}


def is_kernel(name: str, stats: dict) -> bool:
    texts = [name] + [v for v in stats.values() if isinstance(v, str)]
    return any(m in t for t in texts for m in KERNEL_MARKS)


def op_label(name: str, stats: dict) -> str:
    """A readable name for a device op: its JAX name stack where the
    trace gives one, else the HLO name."""
    return stats.get("tf_op") or name


def device_ops(pd) -> dict:
    """plane name -> [(start_ns, end_ns, label, is_kernel)] of its ops."""
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for e in line.events:
                st = _stats(e)
                ops.append((e.start_ns, e.end_ns, op_label(e.name, st),
                            is_kernel(e.name, st)))
        if ops:
            out[plane.name] = sorted(ops)
    return out


def host_spans(pd) -> list:
    """[(start_ns, end_ns, name)] of every host event."""
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.duration_ns > 0:
                    spans.append((e.start_ns, e.end_ns, e.name))
    return spans


def window(spans) -> tuple:
    hits = [(s, e) for s, e, n in spans if n == WINDOW]
    if len(hits) != 1:
        raise RuntimeError(f"expected one {WINDOW!r} host span, found "
                           f"{len(hits)}")
    return hits[0]


def union(intervals, lo: float, hi: float) -> list:
    """Merged intervals clipped to [lo, hi]."""
    merged = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _host_activity(spans, t: float) -> str:
    """The innermost host span other than the window around time t."""
    inside = [(e - s, n) for s, e, n in spans
              if s <= t <= e and n != WINDOW]
    return min(inside)[1] if inside else "host idle"


def reduce(pd) -> dict:
    """The window's device numbers, averaged over the device planes:
    ``window_s``, ``busy_s``, ``kernel_s``, ``xla_s`` (busy outside the
    kernel), ``kernel_events``, and the breakdown: ``device_ops`` (time
    per op label, longest first) and ``idle_gaps`` (idle time per host
    activity, longest first), each at most ``TOP`` entries."""
    spans = host_spans(pd)
    lo, hi = window(spans)
    planes = device_ops(pd)
    if not planes:
        raise RuntimeError("the trace holds no device operations")
    busy = kernel = 0.0
    events = 0
    per_op = collections.Counter()
    per_gap = collections.Counter()
    for ops in planes.values():
        merged = union([(s, e) for s, e, _, _ in ops], lo, hi)
        busy += _length(merged)
        kernel += _length(union([(s, e) for s, e, _, k in ops if k], lo, hi))
        events += sum(1 for s, e, _, k in ops if k and e > lo and s < hi)
        for s, e, label, _ in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[label] += d
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                per_gap[_host_activity(spans, 0.5 * (g0 + g1))] += g1 - g0
    k = len(planes)
    ns = 1e-9 / k
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * ns,
        "kernel_s": kernel * ns,
        "xla_s": (busy - kernel) * ns,
        "kernel_events": events // k,
        "device_ops": [[n, v * ns] for n, v in per_op.most_common(TOP)],
        "idle_gaps": [[n, v * ns] for n, v in per_gap.most_common(TOP)],
    }
