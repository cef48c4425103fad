"""The trace reduction on a small synthetic `.xplane.pb` (the protobuf wire
format written by hand: XSpace.planes=1; XPlane.name=2, lines=3,
event_metadata=4; XLine.name=2, timestamp_ns=3, events=4; XEvent.metadata_id=1,
offset_ps=2, duration_ps=3)."""

import pytest

from benchmark import xplane


def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | 0x80]) if n else bytes([b])
        if not n:
            return out


def _field(num, wire, payload):
    key = _varint((num << 3) | wire)
    if wire == 2:
        return key + _varint(len(payload)) + payload
    return key + _varint(payload)


def _plane(name, lines):
    """lines: {line name: [(event name, start_ns, dur_ns)]}."""
    names = sorted({e[0] for events in lines.values() for e in events})
    ids = {n: i + 1 for i, n in enumerate(names)}
    out = _field(2, 2, name.encode())
    for i, (line_name, events) in enumerate(lines.items()):
        body = _field(1, 0, i + 1) + _field(2, 2, line_name.encode())
        for ev, start, dur in events:
            body += _field(4, 2, _field(1, 0, ids[ev])
                           + _field(2, 0, start * 1000)
                           + _field(3, 0, dur * 1000))
        out += _field(3, 2, body)
    for n, i in ids.items():
        meta = _field(1, 0, i) + _field(2, 2, n.encode())
        out += _field(4, 2, _field(1, 0, i) + _field(2, 2, meta))
    return out


@pytest.fixture
def trace(tmp_path):
    # device 0: busy [0,40) U [50,60) U [90,100); overlapping ops inside [0,40)
    dev0 = {"XLA Ops": [("fusion.1", 0, 30), ("copy.2", 20, 20),
                        ("fusion.1", 50, 10), ("all-reduce.3", 90, 10)],
            "XLA Modules": [("jit_step", 0, 100)]}
    # device 1: busy [0,100) in one op
    dev1 = {"XLA Ops": [("fusion.1", 0, 100)]}
    host = {"python": [("data_wait", 38, 14), ("device_step", 55, 40),
                       ("$frame.py:1 f", 0, 100)]}
    space = b"".join(_field(1, 2, _plane(n, l)) for n, l in (
        ("/device:TPU:0", dev0), ("/device:TPU:1", dev1), ("/host:CPU", host)))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    return xplane.read(str(path))


def test_read_keeps_op_lines_only_and_drops_python_frames(trace):
    assert sorted(trace["devices"]) == ["/device:TPU:0", "/device:TPU:1"]
    assert len(trace["devices"]["/device:TPU:0"]) == 4   # not the module line
    assert {n for n, _, _ in trace["host"]} == {"data_wait", "device_step"}


def test_busy_is_the_union_and_idle_the_rest(trace):
    busy_s, window_s = xplane.busy_and_window(trace)
    assert window_s == pytest.approx(100e-9)
    assert busy_s == pytest.approx((60 + 100) / 2 * 1e-9)    # mean over chips
    assert xplane.idle_share_percent(trace) == pytest.approx(20.0)


def test_a_window_clips_the_operations(trace):
    busy_s, window_s = xplane.busy_and_window(trace, (30, 60))
    assert window_s == pytest.approx(30e-9)
    assert busy_s == pytest.approx((20 + 30) / 2 * 1e-9)


def test_top_operations_by_device_time(trace):
    top = xplane.top_ops(trace, 2)
    assert [n for n, _ in top] == ["fusion.1", "copy.2"]
    # fusion.1 and copy.2 overlap on [20, 30): that time is charged once
    assert top[0][1] == pytest.approx((30 + 100) / 2 * 1e-9)
    assert top[1][1] == pytest.approx(20 / 2 * 1e-9)


def test_nested_operations_are_not_counted_twice():
    ops = [("%while.1 = (s32[]) while(...)", 0, 100), ("%fusion.2 = f32[] fusion()", 10, 40),
           ("%fusion.2 = f32[] fusion()", 50, 90), ("%copy.3 = f32[] copy()", 100, 110)]
    assert sorted(xplane.self_times(ops)) == sorted([
        (ops[0][0], 30), (ops[1][0], 30), (ops[2][0], 40), (ops[3][0], 10)])
    top = xplane.top_ops({"devices": {"/device:TPU:0": ops}, "host": []}, 3)
    assert top == [["fusion.2", pytest.approx(70e-9)],
                   ["while.1", pytest.approx(30e-9)],
                   ["copy.3", pytest.approx(10e-9)]]


def test_gaps_are_named_by_the_host_span_that_covers_them(trace):
    gaps = xplane.idle_gaps(trace, 5)
    assert gaps[0] == ["device_step", pytest.approx(30e-9)]   # [60, 90)
    assert gaps[1] == ["data_wait", pytest.approx(10e-9)]     # [40, 50)
    assert len(gaps) == 2


def test_a_trace_without_device_operations_is_an_error(tmp_path):
    path = tmp_path / "e.xplane.pb"
    path.write_bytes(_field(1, 2, _plane("/host:CPU", {"python": [("x", 0, 1)]})))
    empty = xplane.read(str(path))
    with pytest.raises(ValueError, match="no operation ran"):
        xplane.busy_and_window(empty)
