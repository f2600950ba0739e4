"""Write a small XSpace, the profiler's trace file, by hand: just the
fields ``bench.marks.load`` reads, in protobuf's wire format."""
from __future__ import annotations


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def field(num: int, value) -> bytes:
    """An int as a varint field, text or bytes as a length-delimited one."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def stat_int(metadata_id: int, value: int) -> bytes:
    return field(1, metadata_id) + field(4, value)


def stat_bytes(metadata_id: int, value: bytes) -> bytes:
    return field(1, metadata_id) + field(6, value)


def line(name: str, events) -> bytes:
    """``events``: (event metadata id, start ns, duration ns, [stats])."""
    return field(2, name) + b"".join(
        field(4, field(1, m) + field(2, int(a * 1000)) +
              field(3, int(d * 1000)) + b"".join(field(4, s) for s in st))
        for m, a, d, st in events)


def plane(name: str, lines=(), events=None, stats=None,
          event_stats=None) -> bytes:
    """One XSpace.planes field.  ``events``: {id: name} of the event
    metadata, ``stats``: {id: name} of the stat metadata,
    ``event_stats``: {event id: [stats]} kept on the event metadata."""
    meta = b"".join(
        field(4, field(1, i) + field(2, field(1, i) + field(2, n) + b"".join(
            field(5, s) for s in (event_stats or {}).get(i, []))))
        for i, n in (events or {}).items())
    meta += b"".join(field(5, field(1, i) + field(2, field(1, i) +
                                                   field(2, n)))
                     for i, n in (stats or {}).items())
    return field(1, field(2, name) + b"".join(field(3, ln) for ln in lines)
                 + meta)


def hlo_proto(module: str, op_names: dict) -> bytes:
    """An HloProto of one computation whose instructions carry
    ``op_names`` ({instruction: op_name}) as their metadata."""
    comp = field(1, "main") + b"".join(
        field(2, field(1, name) + field(7, field(2, path)))
        for name, path in op_names.items())
    return field(1, field(1, module) + field(3, comp))
