"""Cut a profiler capture down to a recording small enough to commit.

    python3 tests/benchmark/cut_capture.py <in.xplane.pb> <out.xplane.pb.gz> [updates]

Keeps, of every line of every plane, the events that start inside a window
of ``updates`` (3) whole updates, from the start of the capture's second
execution of ``jit_multi_step`` on the first chip to the start of a later
one, so that the shares of its op time are a steady state's; of the host's
planes only the benchmark's and the program's own spans (``bench_*``,
``fused.*``); and all the metadata of the device planes, which is where an
instruction's ``op_name`` lives. An ``.xplane.pb`` is a protobuf (``XSpace``); this copies
its fields as bytes and needs no schema: XSpace.planes=1; XPlane.name=2,
lines=3, event_metadata=4; XLine.timestamp_ns=3, events=4;
XEvent.metadata_id=1, offset_ps=2; XEventMetadata.id=1, name=2.
"""

import gzip
import sys

KEEP_HOST = (b"bench_", b"fused.")
DEVICE = b"/device:TPU:"
STEP = b"jit_multi_step"


def varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(number, value, raw bytes of the whole field) of one message."""
    i = 0
    while i < len(buf):
        start = i
        tag, i = varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = varint(buf, i)
        elif wire == 2:
            size, i = varint(buf, i)
            value = buf[i:i + size]
            i += size
        else:
            value = None
            i += 8 if wire == 1 else 4
        yield number, value, buf[start:i]


def encode(number, payload):
    out, size = bytearray([number << 3 | 2]), len(payload)
    while size >= 0x80:
        out.append(size & 0x7F | 0x80)
        size >>= 7
    out.append(size)
    return bytes(out) + payload


def names_of(plane):
    """{event metadata id: name} of a plane."""
    out = {}
    for number, value, _ in fields(plane):
        if number == 4:
            entry = dict((n, v) for n, v, _ in fields(value))
            meta = dict((n, v) for n, v, _ in fields(entry[2]))
            out[meta[1]] = bytes(meta.get(2, b""))
    return out


def events_of(line):
    """(timestamp_ns, [(start_ns, metadata id, raw)]) of a line."""
    stamp, events = 0, []
    for number, value, raw in fields(line):
        if number == 3:
            stamp = value
        elif number == 4:
            ev = dict((n, v) for n, v, _ in fields(value))
            events.append((ev.get(2, 0) / 1000.0, ev[1], raw))
    return stamp, [(stamp + off, mid, raw) for off, mid, raw in events]


def cut(space, updates):
    planes = [v for n, v, _ in fields(space) if n == 1]
    lo = hi = None
    for plane in planes:
        head = dict((n, v) for n, v, _ in fields(plane) if n == 2)
        if not bytes(head[2]).startswith(DEVICE) or lo is not None:
            continue
        names = names_of(plane)
        for number, line, _ in fields(plane):
            if number == 3:
                steps = sorted(s for s, mid, _ in events_of(line)[1]
                               if names[mid].startswith(STEP))
                if len(steps) > 1 + updates:
                    lo, hi = steps[1] - 1000.0, steps[1 + updates] - 1000.0
    out = bytearray()
    for plane in planes:
        names = names_of(plane)
        device = False
        body = bytearray()
        for number, value, raw in fields(plane):
            if number == 2:
                device = bytes(value).startswith(DEVICE)
            if number == 4 and not device:
                entry = dict((n, v) for n, v, _ in fields(value))
                meta = dict((n, v) for n, v, _ in fields(entry[2]))
                if not bytes(meta.get(2, b"")).startswith(KEEP_HOST):
                    continue
            if number != 3:
                body += raw
                continue
            kept = bytearray()
            stamp, events = events_of(value)
            inside = {id(r) for s, mid, r in events if lo <= s < hi and (
                device or names[mid].startswith(KEEP_HOST))}
            for n, v, r in fields(value):
                if n != 4:
                    kept += r
            for s, mid, r in events:
                if id(r) in inside:
                    kept += r
            if inside:
                body += encode(3, bytes(kept))
        out += encode(1, bytes(body))
    return bytes(out)


if __name__ == "__main__":
    src, dst = sys.argv[1], sys.argv[2]
    updates = int(sys.argv[3]) if len(sys.argv) > 3 else 3
    with open(src, "rb") as f:
        data = cut(f.read(), updates)
    with gzip.open(dst, "wb", compresslevel=9) as g:
        g.write(data)
    print(f"{src} -> {dst}: {len(data)} bytes before gzip")
