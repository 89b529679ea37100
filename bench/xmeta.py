"""What `jax.profiler.ProfileData` does not surface: the METADATA of a
trace's events.  In an `.xplane.pb` every device operation's event metadata
carries the stat `tf_op` (the HLO `op_name`, e.g.
`jit(run)/while/body/jaxmc.merge.sort/sort` — where a `jax.named_scope`
shows) beside `source`, `bytes_accessed`, `flops` and `hlo_category`, which
nothing reads yet and this file therefore skips; ProfileData (jax 0.9.0)
gives only per-event stats.  This is a decoder of the XSpace wire format for
exactly

    planes -> event_metadata -> {name, stats[tf_op]}
    planes -> lines -> events -> {metadata_id, start, duration}

in plain `bytes` parsing.  It imports nothing outside the standard library:
the chip machine is not assumed to have tensorflow or tsl.

Field numbers (tsl/profiler/protobuf/xplane.proto): XSpace.planes=1;
XPlane{name=2, lines=3, event_metadata=4 (map), stat_metadata=5 (map)};
XLine{name=2, timestamp_ns=3, events=4}; XEvent{metadata_id=1, offset_ps=2,
duration_ps=3}; XEventMetadata{id=1, name=2, stats=5}; XStatMetadata{id=1,
name=2}; XStat{metadata_id=1, str=5, ref=7 (a stat_metadata id whose NAME
is the string)}; map entry{key=1, value=2}.
"""

from __future__ import annotations

#: the one metadata stat kept per operation
TF_OP = "tf_op"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one message: varints as ints, length-
    delimited fields as memoryviews, fixed64/32 as raw bytes."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            val, i = buf[i:i + ln], i + ln
        elif wt == 1:
            val, i = bytes(buf[i:i + 8]), i + 8
        elif wt == 5:
            val, i = bytes(buf[i:i + 4]), i + 4
        else:
            raise ValueError(f"wire type {wt} at byte {i}")
        yield num, val


def _msg(buf) -> dict:
    """{field number: [values]} of one message."""
    out = {}
    for num, val in fields(buf):
        out.setdefault(num, []).append(val)
    return out


def _last(msg, num, default=0):
    return msg[num][-1] if num in msg else default


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _plane(buf):
    pl = _msg(buf)
    stat_names = {}
    for entry in pl.get(5, ()):
        sm = _msg(_last(_msg(entry), 2, b""))
        stat_names[_last(sm, 1)] = _text(_last(sm, 2, b""))
    meta = {}
    for entry in pl.get(4, ()):
        em = _msg(_last(_msg(entry), 2, b""))
        rec = meta[_last(em, 1)] = {"name": _text(_last(em, 2, b""))}
        for st in map(_msg, em.get(5, ())):
            if stat_names.get(_last(st, 1)) == TF_OP:
                rec[TF_OP] = _text(st[5][-1]) if 5 in st else \
                    stat_names.get(_last(st, 7), "")
    lines = []
    for ln in map(_msg, pl.get(3, ())):
        t0_ns = _signed(_last(ln, 3))
        # as ProfileData: start_ns = timestamp_ns + offset_ps / 1000
        lines.append({"name": _text(_last(ln, 2, b"")), "events": [
            (_last(ev, 1), t0_ns + _signed(_last(ev, 2)) / 1e3,
             _signed(_last(ev, 3)) / 1e3)
            for ev in map(_msg, ln.get(4, ()))]})
    return {"name": _text(_last(pl, 2, b"")), "lines": lines,
            "event_metadata": meta}


def read(path: str):
    """[{"name", "lines": [{"name", "events": [(metadata_id, start_ns,
    duration_ns)]}], "event_metadata": {id: {"name", "tf_op"?}}}] per
    plane."""
    with open(path, "rb") as fh:
        return [_plane(v) for num, v in fields(fh.read()) if num == 1]
