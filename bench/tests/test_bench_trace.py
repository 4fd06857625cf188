"""The trace reduction on a small trace built here: busy time is the
union of the device's operations inside the window, the kernel is what
names the Mosaic target or a Pallas call, idle gaps are named by the innermost
host span around them, and the breakdown lists both longest first."""

import pytest
from jax.profiler import ProfileData

from bench import trace_reduce

# (name, start_ns, end_ns, tf_op) on the device's "XLA Ops" line
DEVICE = [
    ("fusion.0", 0, 500, None),                     # before the window
    ("custom-call.1", 1500, 3000, "jit(impl)/pallas_call"),
    ("fusion.2", 2500, 4000, None),                 # overlaps the kernel
    ("fusion.3", 6500, 9000, None),
    ("sort.4", 10500, 12000, None),                 # cut at the window end
]
HOST = [("bench.window", 1000, 11000), ("bench.execute", 1000, 5000),
        ("bench.execute", 6000, 10500)]


def _xspace(device, host, device_line="XLA Ops") -> ProfileData:
    names = sorted({e[0] for e in device})
    meta = {n: i + 1 for i, n in enumerate(names)}
    evs = []
    for name, s, e, op in device:
        stat = (f' stats {{ metadata_id: 1 str_value: "{op}" }}' if op
                else "")
        evs.append(f"events {{ metadata_id: {meta[name]} offset_ps: "
                   f"{s * 1000} duration_ps: {(e - s) * 1000}{stat} }}")
    dmeta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                     f'"{n}" }} }}' for n, i in meta.items())
    hnames = sorted({h[0] for h in host})
    hmeta = {n: i + 1 for i, n in enumerate(hnames)}
    hevs = " ".join(f"events {{ metadata_id: {hmeta[n]} offset_ps: "
                    f"{s * 1000} duration_ps: {(e - s) * 1000} }}"
                    for n, s, e in host)
    hm = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                  f'"{n}" }} }}' for n, i in hmeta.items())
    text = (
        f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 name: '
        f'"{device_line}" timestamp_ns: 0 {" ".join(evs)} }} {dmeta} '
        f'stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }} }} '
        f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 2 name: "python" '
        f'timestamp_ns: 0 {hevs} }} {hm} }}')
    return ProfileData.from_text_proto(text)


def test_busy_kernel_xla_and_window():
    r = trace_reduce.reduce(_xspace(DEVICE, HOST))
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(5500e-9)   # 2500 + 2500 + 500
    assert r["kernel_s"] == pytest.approx(1500e-9)
    assert r["xla_s"] == pytest.approx(4000e-9)
    assert r["kernel_events"] == 1


def test_an_op_that_reads_a_kernel_output_is_not_kernel_time():
    """On the chip an XLA fusion's name is its HLO text, which names its
    operands: ``%custom-call.90`` there is an input, not the op."""
    reader = ("%fusion.40 = f32[64]{0} fusion(f32[64]{0} %reshape.28, "
              "s32[64]{0} %custom-call.90), kind=kCustom")
    kernel = ("%xpencil_forces.1 = f32[8,128]{1,0} custom-call(f32[8,128]"
              "{1,0} %pad.6), custom_call_target=tpu_custom_call")
    device = [(kernel, 1500, 3000, None), (reader, 3000, 4000, None)]
    r = trace_reduce.reduce(_xspace(device, HOST))
    assert r["kernel_s"] == pytest.approx(1500e-9)
    assert r["xla_s"] == pytest.approx(1000e-9)
    assert r["kernel_events"] == 1


def test_idle_gaps_are_named_by_the_host():
    r = trace_reduce.reduce(_xspace(DEVICE, HOST))
    gaps = dict(r["idle_gaps"])
    assert gaps["host idle"] == pytest.approx(2500e-9)
    assert gaps["bench.execute"] == pytest.approx(2000e-9)  # 500 + 1500
    assert r["idle_gaps"][0][0] == "host idle"
    busy = r["busy_s"] + sum(gaps.values())
    assert busy == pytest.approx(r["window_s"])


def test_breakdown_lists_ops_longest_first():
    r = trace_reduce.reduce(_xspace(DEVICE, HOST))
    ops = r["device_ops"]
    assert ops[0] == ["fusion.3", pytest.approx(2500e-9)]
    assert dict(ops)["jit(impl)/pallas_call"] == pytest.approx(1500e-9)
    assert dict(ops)["sort.4"] == pytest.approx(500e-9)
    assert "fusion.0" not in dict(ops)
    many = [(f"fusion.{i}", 1000 + 10 * i, 1005 + 10 * i, None)
            for i in range(30)]
    assert len(trace_reduce.reduce(_xspace(many, HOST))["device_ops"]) \
        == trace_reduce.TOP


def test_no_window_or_no_device_ops_is_an_error():
    with pytest.raises(RuntimeError):
        trace_reduce.reduce(_xspace(DEVICE, HOST[1:]))
    with pytest.raises(RuntimeError):
        trace_reduce.reduce(_xspace(DEVICE, HOST, device_line="Steps"))
