"""What an ``.xplane.pb`` profile holds beyond `jax.profiler.ProfileData`.

`ProfileData` gives each event its name, start and duration. On a TPU the
name of an event on the "XLA Ops" line is the op's whole HLO instruction;
what the op was compiled from lives in the plane's event metadata, which
`ProfileData` does not expose: the op's name stack (stat ``tf_op``, e.g.
``jit(traced)/vmap()/while/body/closed_call/inner_step/read/gather:``),
the source line (``source``) and the program it belongs to
(``program_id``). This module reads those from the protobuf wire format
itself, with no dependency beyond the standard library. It decodes the
planes' metadata and skips their lines, which hold the events, so a large
trace is read in well under a second.

The messages read (``tsl/profiler/protobuf/xplane.proto``):

    XSpace         { repeated XPlane planes = 1; }
    XPlane         { string name = 2; repeated XLine lines = 3;
                     map<int64, XEventMetadata> event_metadata = 4;
                     map<int64, XStatMetadata> stat_metadata = 5; }
    XEventMetadata { int64 id = 1; string name = 2; repeated XStat stats = 5; }
    XStatMetadata  { int64 id = 1; string name = 2; }
    XStat          { int64 metadata_id = 1; uint64 uint64_value = 3;
                     int64 int64_value = 4; string str_value = 5;
                     uint64 ref_value = 7; }

A map entry is a message whose key is field 1 and value field 2.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional, Tuple

_VARINT, _I64, _LEN, _I32 = 0, 1, 2, 5


@dataclasses.dataclass(frozen=True)
class OpMeta:
    """What the profile records of one op, whatever its events."""
    tf_op: str = ""               # the op's name stack; "" where none
    source: str = ""              # file:line it was traced from
    program_id: Optional[int] = None


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, lo: int, hi: int) -> Iterator[Tuple[int, object]]:
    """(field number, value) of each field in ``buf[lo:hi]``: an int for a
    varint, a (start, end) slice for a length-delimited field; fixed-width
    fields are skipped."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == _VARINT:
            value, i = _varint(buf, i)
            yield field, value
        elif wire == _LEN:
            n, i = _varint(buf, i)
            yield field, (i, i + n)
            i += n
        elif wire == _I64:
            i += 8
        elif wire == _I32:
            i += 4
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")


def _text(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map_value(buf: bytes, span):
    """The value slice of a map entry (field 2), or None."""
    return next((v for f, v in _fields(buf, *span) if f == 2), None)


def _stat_names(buf: bytes, entries) -> Dict[int, str]:
    names = {}
    for span in entries:
        value = _map_value(buf, span)
        if value is None:
            continue
        sid, name = None, ""
        for f, v in _fields(buf, *value):
            if f == 1:
                sid = v
            elif f == 2:
                name = _text(buf, v)
        names[sid] = name
    return names


def _op_meta(buf: bytes, span, stat_names: Dict[int, str]):
    """(name, OpMeta) of one XEventMetadata."""
    name, stats = "", {}
    for f, v in _fields(buf, *span):
        if f == 2:
            name = _text(buf, v)
        elif f == 5:
            sid, value = None, None
            for sf, sv in _fields(buf, *v):
                if sf == 1:
                    sid = sv
                elif sf in (3, 4):
                    value = sv
                elif sf == 5:
                    value = _text(buf, sv)
                elif sf == 7:           # a string kept once, as a stat name
                    value = stat_names.get(sv, "")
            stats[stat_names.get(sid, "")] = value
    pid = stats.get("program_id")
    return name, OpMeta(tf_op=str(stats.get("tf_op") or ""),
                        source=str(stats.get("source") or ""),
                        program_id=pid if isinstance(pid, int) else None)


def op_metadata(data: bytes) -> Dict[str, Dict[str, OpMeta]]:
    """Per plane name, each event name's `OpMeta`: the map to look up an
    event `ProfileData` gives by ``(plane.name, event.name)``. Where two
    metadata entries of one plane share a name, the first is kept."""
    out: Dict[str, Dict[str, OpMeta]] = {}
    for field, plane in _fields(data, 0, len(data)):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for f, v in _fields(data, *plane):
            if f == 2:
                name = _text(data, v)
            elif f == 4:
                events.append(v)
            elif f == 5:
                stats.append(v)
        stat_names = _stat_names(data, stats)
        ops: Dict[str, OpMeta] = {}
        for span in events:
            value = _map_value(data, span)
            if value is not None:
                op, meta = _op_meta(data, value, stat_names)
                ops.setdefault(op, meta)
        out[name] = ops
    return out


def read_op_metadata(path) -> Dict[str, Dict[str, OpMeta]]:
    with open(path, "rb") as f:
        return op_metadata(f.read())
