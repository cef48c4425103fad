"""A small `.xplane.pb` written by hand, with the `op_name` path of each
operation where the profiler keeps it: the `tf_op` stat of the event's
metadata. Wire format: XSpace.planes=1; XPlane.name=2, lines=3,
event_metadata=4, stat_metadata=5 (maps: key=1, value=2); XEventMetadata.id=1,
name=2, stats=5; XStatMetadata.id=1, name=2; XStat.metadata_id=1, str_value=5;
XLine.id=1, name=2, timestamp_ns=3, events=4; XEvent.metadata_id=1,
offset_ps=2, duration_ps=3."""

TF_OP = 7          # stat metadata id of `tf_op` in the planes built here


def varint(n: int) -> bytes:
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | 0x80]) if n else bytes([b])
        if not n:
            return out


def field(num: int, wire: int, payload) -> bytes:
    key = varint((num << 3) | wire)
    if wire == 2:
        return key + varint(len(payload)) + payload
    return key + varint(payload)


def instruction(name: str, result: str = "bf16[8,128]") -> str:
    """An event name as the TPU trace prints it: the whole instruction."""
    return f"%{name} = {result}{{1,0}} fusion(bf16[8,128]{{1,0}} %p.1)"


def plane(name: str, lines: dict, line_timestamp_ns: int = 0) -> bytes:
    """lines: {line name: [(event name, op_name path or None, start_ns,
    dur_ns)]}. Events of one name and path share a metadata entry."""
    keys = sorted({(e[0], e[1]) for events in lines.values() for e in events},
                  key=lambda k: (k[0], k[1] or ""))
    ids = {k: i + 1 for i, k in enumerate(keys)}
    out = field(2, 2, name.encode())
    for i, (line_name, events) in enumerate(lines.items()):
        body = (field(1, 0, i + 1) + field(2, 2, line_name.encode())
                + field(3, 0, line_timestamp_ns))
        for ev, path, start, dur in events:
            body += field(4, 2, field(1, 0, ids[(ev, path)])
                          + field(2, 0, (start - line_timestamp_ns) * 1000)
                          + field(3, 0, dur * 1000))
        out += field(3, 2, body)
    for (ev, path), i in ids.items():
        meta = field(1, 0, i) + field(2, 2, ev.encode())
        if path is not None:
            meta += field(5, 2, field(1, 0, TF_OP)
                          + field(5, 2, (path + ":").encode()))
        out += field(4, 2, field(1, 0, i) + field(2, 2, meta))
    out += field(5, 2, field(1, 0, TF_OP) + field(2, 2, field(1, 0, TF_OP)
                                                  + field(2, 2, b"tf_op")))
    return out


def write(path, planes: dict) -> str:
    """planes: {plane name: lines}. Returns the path as a string."""
    with open(path, "wb") as f:
        f.write(b"".join(field(1, 2, plane(n, l)) for n, l in planes.items()))
    return str(path)
