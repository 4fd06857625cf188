"""Device time by engine layer (``bench/scopes.py``) on a small trace built
here: each device op is charged to the engine scope of its instruction,
read from the HLO protos the trace carries; kernel events stay kernel
time, XLA ops under ``pair`` are lane staging, ops under no scope are
``unscoped``, and only the window counts."""

import pathlib

import pytest
from jax.profiler import ProfileData

from bench import harness, scopes, trace_reduce
from bench.drive import Window


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message from (field number, int | bytes | str) pairs."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(value)) + value
    return out


def _hlo_proto(op_names: dict) -> bytes:
    """HloProto: one computation whose instructions carry ``op_names``."""
    instrs = [(2, _msg((1, name), (7, _msg((2, op)))))
              for name, op in op_names.items()]
    return _msg((1, _msg((1, "jit_impl"), (3, _msg((1, "main"), *instrs)))))


def _plane(pid: int, name: str, lines, event_names, stat_names=(),
           event_stats=None) -> bytes:
    metas = [(4, _msg((1, i), (2, _msg((1, i), (2, n),
                                       *((event_stats or {}).get(n, ()))))))
             for n, i in event_names.items()]
    stats = [(5, _msg((1, i), (2, _msg((1, i), (2, n)))))
             for n, i in stat_names]
    return _msg((1, pid), (2, name), *lines, *metas, *stats)


def _line(name: str, events) -> tuple:
    """events: (metadata id, start ns, end ns, [(stat id, int)])"""
    evs = [(4, _msg((1, m), (2, s * 1000), (3, (e - s) * 1000),
                    *[(4, _msg((1, k), (4, v))) for k, v in stats]))
           for m, s, e, stats in events]
    return (3, _msg((1, 1), (2, name), (3, 0), *evs))


KERNEL = ("%xpencil_forces.1 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} "
          "%pad.6), custom_call_target=tpu_custom_call")
OPS = {   # instruction -> (op_name, start, end); HLO text names as on a TPU
    "fusion.34": ("jit(impl)/bin/sort/gather", 1000, 1800),
    "fusion.37": ("jit(impl)/bin/scatter/gather", 1800, 2400),
    "fusion.12": ("jit(impl)/ghost/scatter", 2400, 2600),
    "transpose.3": ("jit(impl)/pair/jit(xpencil_forces)/transpose", 2600,
                    2700),
    "xpencil_forces.1": ("jit(impl)/pair/jit(xpencil_forces)/pallas_call",
                         2700, 4000),
    "fusion.40": ("jit(impl)/scatter_back/gather", 4000, 4500),
    "copy.9": ("", 4500, 4600),                     # under no scope
    "fusion.50": ("jit(impl)/scatter_back/gather", 10500, 12000),  # cut
    "fusion.0": ("jit(impl)/bin/sort/iota", 0, 500),   # before the window
}
HOST = [("bench.window", 1000, 11000), ("bench.execute", 1000, 5000)]


def _event_name(instr: str) -> str:
    if instr == "xpencil_forces.1":
        return KERNEL
    return f"%{instr} = f32[1024]{{0}} fusion(f32[1024]{{0}} %p), kind=kLoop"


def _xspace(ops=OPS, program_id=7, with_metadata=True) -> bytes:
    """As a v5e writes it: device events give the instruction in their
    name and no statistic but their device timing."""
    dev_names = {_event_name(i): k + 1 for k, i in enumerate(ops)}
    device = _plane(1, "/device:TPU:0", [_line("XLA Ops", [
        (dev_names[_event_name(i)], s, e, [(1, s * 1000)])
        for i, (_, s, e) in ops.items()])], dev_names,
        stat_names=[("device_offset_ps", 1)])
    host_names = {n: k + 1 for k, n in enumerate(sorted({h[0]
                                                         for h in HOST}))}
    host = _plane(2, "/host:CPU", [_line("python", [
        (host_names[n], s, e, []) for n, s, e in HOST])], host_names)
    planes = [(1, device), (1, host)]
    if with_metadata:
        proto = _hlo_proto({i: op for i, (op, _, _) in ops.items() if op})
        meta = _plane(3, scopes.METADATA_PLANE, [], {"jit_impl": program_id},
                      stat_names=[("Hlo Proto", 1)],
                      event_stats={"jit_impl": [(5, _msg((1, 1),
                                                         (6, proto)))]})
        planes.append((1, meta))
    return _msg(*planes)


def _reduce(raw: bytes) -> dict:
    return scopes.scopes(ProfileData.from_serialized_xspace(raw),
                         scopes.program_op_names(raw))


def test_hlo_protos_map_instructions_to_op_names():
    names = scopes.program_op_names(_xspace())
    assert list(names) == [7]
    assert names[7]["fusion.37"] == "jit(impl)/bin/scatter/gather"
    assert "copy.9" not in names[7]                # no op_name, no entry


def test_scopes_sum_each_layer_inside_the_window():
    got = _reduce(_xspace())
    assert got == {
        "bin": pytest.approx(1400e-9),              # 800 + 600; fusion.0 out
        "ghost": pytest.approx(200e-9),
        "pair_staging": pytest.approx(100e-9),      # the kernel is not here
        "scatter_back": pytest.approx(1000e-9),     # 500 + 500 cut at 11000
        "unscoped": pytest.approx(100e-9)}


def test_scopes_and_kernel_add_up_to_the_busy_time():
    raw = _xspace()
    pd = ProfileData.from_serialized_xspace(raw)
    summary = trace_reduce.reduce(pd)
    got = scopes.scopes(pd, scopes.program_op_names(raw))
    assert summary["kernel_s"] == pytest.approx(1300e-9)
    assert sum(got.values()) == pytest.approx(summary["xla_s"])


def test_the_reduction_of_the_trace_is_unchanged_by_its_hlo_protos():
    with_meta = trace_reduce.reduce(ProfileData.from_serialized_xspace(
        _xspace()))
    without = trace_reduce.reduce(ProfileData.from_serialized_xspace(
        _xspace(with_metadata=False)))
    assert with_meta == without


def test_an_ambiguous_instruction_is_unscoped():
    """An instruction two programs name differently is left unscoped
    rather than guessed."""
    raw = _xspace()
    names = scopes.program_op_names(raw)
    names[8] = {"fusion.34": "jit(other)/scatter_back/gather"}
    got = scopes.scopes(ProfileData.from_serialized_xspace(raw), names)
    assert got["bin"] == pytest.approx(600e-9)
    assert got["unscoped"] == pytest.approx(900e-9)


def test_no_hlo_protos_leaves_every_op_unscoped():
    got = _reduce(_xspace(with_metadata=False))
    assert set(got) == {"unscoped"}
    assert got["unscoped"] == pytest.approx(2800e-9)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(impl)/bin/sort/argsort", "bin"),
    ("jit(run)/while/body/cond/branch_1_fun/bin_refresh/scatter",
     "bin_refresh"),
    ("jit(run)/while/body/integrate/add", "integrate"),
    ("jit(impl)/shard_map/exchange/ppermute", "exchange"),
    ("jit(impl)/binning/gather", None),
    ("", None)])
def test_scope_of_takes_the_first_engine_scope(op_name, scope):
    assert scopes.scope_of(op_name) == scope


READERS = {"binning_ms": "bin", "ghost_ms": "ghost",
           "lane_staging_ms": "pair_staging",
           "scatter_back_ms": "scatter_back"}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_layer_readers(metric):
    read = harness.reader("layers", f"{metric}.force")
    w = Window(units=4)
    trace = {"kernel_events": 4, "scopes": {READERS[metric]: 0.02}}
    assert read({"window": w, "trace": trace}) == pytest.approx(5.0)
    # absent where the scope, the scopes or the trace is
    assert read({"window": w, "trace": dict(trace, scopes={})}) is None
    assert read({"window": w, "trace": {"kernel_events": 4}}) is None
    assert read({"window": w, "trace": None}) is None
    assert read({"window": Window(), "trace": trace}) is None


def test_scope_names_are_the_engines():
    """The scopes read here are literal strings; each is one the engine
    opens (a renamed scope fails here before it goes missing on a chip)."""
    src = pathlib.Path(harness.BENCH).parent / "src" / "repro"
    text = "".join(p.read_text() for p in src.rglob("*.py"))
    for name in scopes.SCOPES:
        assert any(f'{opener}("{name}{end}' in text
                   for opener in ("named_scope", "device_scope")
                   for end in ('"', "/")), name
