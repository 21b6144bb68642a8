"""A small reader of the profiler's ``.xplane.pb`` wire format.

`jax.profiler.ProfileData` gives each event its name, times and own
stats, but not the stats of the event's *metadata*, and those are where
the device trace keeps what an ``XLA Ops`` event came from: ``tf_op``
(the op's name-stack path, e.g. ``jit(round)/dsfl_update/while/body/
conv``), ``hlo_category``, ``flops``, ``bytes_accessed``.  This module
decodes the protobuf wire format of the messages it needs by hand, so the
benchmark imports neither tensorflow nor a generated ``xplane_pb2``.

The messages (tsl ``profiler/protobuf/xplane.proto``), by field number::

    XSpace          planes=1
    XPlane          id=1 name=2 lines=3 event_metadata=4 (map) stat_metadata=5
                    (map) stats=6
    XLine           id=1 name=2 timestamp_ns=3 events=4 duration_ps=9
    XEvent          metadata_id=1 offset_ps=2 duration_ps=3 stats=4
                    num_occurrences=5
    XStat           metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6
                    ref=7 (the id of an XStatMetadata whose name is the value)
    XEventMetadata  id=1 name=2 display_name=4 stats=5
    XStatMetadata   id=1 name=2
    map entry       key=1 value=2
"""
from __future__ import annotations

import struct
from dataclasses import dataclass, field


def _varint(buf: bytes, i: int):
    shift = out = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes):
    """Yield ``(field number, wire type, value)`` of one message: an int
    for varints and fixed widths (bits as read), bytes for a
    length-delimited field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = struct.unpack_from("<Q", buf, i)[0], i + 8
        elif wt == 2:
            ln, i = _varint(buf, i)
            v, i = buf[i:i + ln], i + ln
        elif wt == 5:
            v, i = struct.unpack_from("<I", buf, i)[0], i + 4
        else:
            raise ValueError(f"unsupported wire type {wt} (field {num})")
        yield num, wt, v


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


@dataclass
class Event:
    metadata_id: int
    offset_ps: int = 0
    duration_ps: int = 0
    stats: list = field(default_factory=list)     # raw XStat bytes


@dataclass
class Line:
    name: str = ""
    timestamp_ns: int = 0
    events: list = field(default_factory=list)


@dataclass
class Plane:
    name: str = ""
    lines: list = field(default_factory=list)
    event_names: dict = field(default_factory=dict)   # id -> name
    event_stats: dict = field(default_factory=dict)   # id -> [raw XStat]
    stat_names: dict = field(default_factory=dict)    # id -> name

    def stats(self, raw: list) -> dict:
        """Decode XStat messages to ``{stat name: value}``; a reference
        stat's value is the name of the stat metadata it points at."""
        out = {}
        for msg in raw:
            mid, val = 0, None
            for num, wt, v in fields(msg):
                if num == 1:
                    mid = v
                elif num == 2:
                    val = struct.unpack("<d", struct.pack("<Q", v))[0]
                elif num == 3:
                    val = v
                elif num == 4:
                    val = _signed(v)
                elif num in (5, 6):
                    val = v.decode("utf-8", "replace")
                elif num == 7:
                    val = self.stat_names.get(v, "")
            out[self.stat_names.get(mid, str(mid))] = val
        return out

    def metadata_stats(self, metadata_id: int) -> dict:
        return self.stats(self.event_stats.get(metadata_id, []))


def _event(buf: bytes) -> Event:
    ev = Event(0)
    for num, _wt, v in fields(buf):
        if num == 1:
            ev.metadata_id = v
        elif num == 2:
            ev.offset_ps = _signed(v)
        elif num == 3:
            ev.duration_ps = _signed(v)
        elif num == 4:
            ev.stats.append(v)
    return ev


def _line(buf: bytes) -> Line:
    ln = Line()
    for num, _wt, v in fields(buf):
        if num == 2:
            ln.name = v.decode("utf-8", "replace")
        elif num == 3:
            ln.timestamp_ns = _signed(v)
        elif num == 4:
            ln.events.append(_event(v))
    return ln


def _map_value(buf: bytes) -> bytes:
    for num, _wt, v in fields(buf):
        if num == 2:
            return v
    return b""


def _plane(buf: bytes) -> Plane:
    pl = Plane()
    for num, _wt, v in fields(buf):
        if num == 2:
            pl.name = v.decode("utf-8", "replace")
        elif num == 3:
            pl.lines.append(_line(v))
        elif num == 4:
            mid, name, stats = 0, "", []
            for n2, _w, v2 in fields(_map_value(v)):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    name = v2.decode("utf-8", "replace")
                elif n2 == 5:
                    stats.append(v2)
            pl.event_names[mid] = name
            pl.event_stats[mid] = stats
        elif num == 5:
            mid, name = 0, ""
            for n2, _w, v2 in fields(_map_value(v)):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    name = v2.decode("utf-8", "replace")
            pl.stat_names[mid] = name
    return pl


def read(path: str) -> list:
    """The planes of an ``.xplane.pb`` (an XSpace), in file order."""
    with open(path, "rb") as f:
        buf = f.read()
    return [_plane(v) for num, _wt, v in fields(buf) if num == 1]


def start_ns(line: Line, ev: Event) -> int:
    """An event's start on the trace's nanosecond timeline, in whole
    nanoseconds as `jax.profiler.ProfileData` gives it."""
    return line.timestamp_ns + ev.offset_ps // 1000


def duration_ns(ev: Event) -> int:
    return ev.duration_ps // 1000
