#!/usr/bin/env python3
"""Cut a profiler trace down to what ``benchmark/xplane.py`` reads, so a
recorded sample small enough to commit can pin the reduction's numbers.

    python3 benchmark/tools/cut_xplane.py <in.xplane.pb> <out.xplane.pb> [seconds]

Keeps the device planes' ``XLA Ops`` and ``XLA Modules`` lines and the
host line that holds the ``bench_anchor`` annotation (that event only);
drops every event's stats, the metadata's attachments (HLO text) and,
if ``seconds`` is given, every device event that starts later than that
after the first.  Works on the protobuf wire format directly (the
schema is tsl's ``xplane.proto``; no generated module is installed):
XSpace.planes=1; XPlane.name=2 .lines=3 .event_metadata=4; XLine.name=2
.timestamp_ns=3 .events=4; XEvent.metadata_id=1 .offset_ps=2
.duration_ps=3 .stats=4; XEventMetadata.id=1 .name=2.
"""

import sys

KEEP_LINES = ("XLA Ops", "XLA Modules")
ANCHOR = b"bench_anchor"


def varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def enc_varint(v):
    out = bytearray()
    while True:
        b = v & 0x7F
        v >>= 7
        out.append(b | (0x80 if v else 0))
        if not v:
            return bytes(out)


def fields(buf):
    """Yield ``(field number, wire type, value, raw bytes)``."""
    i = 0
    while i < len(buf):
        start = i
        tag, i = varint(buf, i)
        num, wt = tag >> 3, tag & 7
        if wt == 0:
            value, i = varint(buf, i)
        elif wt == 1:
            value, i = buf[i:i + 8], i + 8
        elif wt == 5:
            value, i = buf[i:i + 4], i + 4
        elif wt == 2:
            n, i = varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wt}")
        yield num, wt, value, buf[start:i]


def sub(num, payload):
    return enc_varint(num << 3 | 2) + enc_varint(len(payload)) + payload


def cut_event(buf):
    return b"".join(raw for num, _, _, raw in fields(buf) if num != 4)


def event_offset_ps(buf):
    for num, _, value, _ in fields(buf):
        if num == 2:
            return value
    return 0


def event_metadata_id(buf):
    for num, _, value, _ in fields(buf):
        if num == 1:
            return value
    return None


def cut_metadata_entry(buf):
    """map<int64, XEventMetadata> entry: key=1, value=2."""
    out = b""
    for num, _, value, raw in fields(buf):
        if num == 2:
            kept = b"".join(
                r for n, _, _, r in fields(value) if n in (1, 2)
            )
            out += sub(2, kept)
        else:
            out += raw
    return out


def metadata_names(plane):
    names = {}
    for num, _, value, _ in fields(plane):
        if num != 4:
            continue
        for n, _, v, _ in fields(value):
            if n == 2:
                mid = name = None
                for m, _, mv, _ in fields(v):
                    if m == 1:
                        mid = mv
                    elif m == 2:
                        name = bytes(mv)
                names[mid] = name
    return names


def cut_line(buf, keep_event):
    out, kept = b"", 0
    for num, _, value, raw in fields(buf):
        if num == 4:
            if keep_event(value):
                out += sub(4, cut_event(value))
                kept += 1
        else:
            out += raw
    return out, kept


def cut_plane(buf, limit_ps):
    name, out = b"", b""
    for num, _, value, _ in fields(buf):
        if num == 2:
            name = bytes(value)
    device = name.startswith(b"/device:TPU:")
    names = metadata_names(buf)
    used = set()

    def keep_device(ev):
        ok = limit_ps is None or event_offset_ps(ev) <= limit_ps
        if ok:
            used.add(event_metadata_id(ev))
        return ok

    def keep_anchor(ev):
        ok = names.get(event_metadata_id(ev)) == ANCHOR
        if ok:
            used.add(event_metadata_id(ev))
        return ok

    lines = []
    for num, _, value, raw in fields(buf):
        if num != 3:
            continue
        lname = b""
        for n, _, v, _ in fields(value):
            if n == 2:
                lname = bytes(v)
        if device and lname.decode() in KEEP_LINES:
            line, kept = cut_line(value, keep_device)
        elif not device:
            line, kept = cut_line(value, keep_anchor)
        else:
            continue
        if kept:
            lines.append(sub(3, line))
    if not lines:
        return None
    for num, _, value, raw in fields(buf):
        if num == 3 or num == 6:
            continue
        if num == 4:
            key = next(v for n, _, v, _ in fields(value) if n == 1)
            if key in used:
                out += sub(4, cut_metadata_entry(value))
        elif num == 5:
            continue  # stat metadata: no stats are kept
        else:
            out += raw
    return out + b"".join(lines)


def main(argv):
    src, dst = argv[1], argv[2]
    limit_ps = int(float(argv[3]) * 1e12) if len(argv) > 3 else None
    data = memoryview(open(src, "rb").read())
    out = b""
    for num, _, value, raw in fields(data):
        if num == 1:
            plane = cut_plane(value, limit_ps)
            if plane is not None:
                out += sub(1, plane)
    with open(dst, "wb") as f:
        f.write(out)
    print(f"{src}: {len(data)} -> {dst}: {len(out)} bytes")


if __name__ == "__main__":
    main(sys.argv)
