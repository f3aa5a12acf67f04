"""The profiler's ``.xplane.pb`` read from its wire format: planes, lines
(threads), events with their own stats, and each event's *metadata* stats.

``jax.profiler.ProfileData`` gives an event's name, times and own stats, and
not what the profiler stores once per kind of event: for a device operation
the scope path (``tf_op``), the source line, ``bytes_accessed``, ``flops``.
A generated ``xplane_pb2`` is importable only through tensorflow, so the few
messages are decoded here (tsl/profiler/protobuf/xplane.proto):

    XSpace          1 planes
    XPlane          2 name, 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    XLine           1 id, 2 name, 3 timestamp_ns, 4 events
    XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps, 4 stats
    XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str, 6 bytes, 7 ref
    XEventMetadata  1 id, 2 name, 4 display_name, 5 stats
    XStatMetadata   1 id, 2 name
"""

import struct


def _varint(buf, i):
    val = shift = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if b < 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wire == 1:
            val = buf[i:i + 8]
            i += 8
        elif wire == 5:
            val = buf[i:i + 4]
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield num, wire, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf, stat_names):
    """One XStat as (name, value); a ``ref`` value names another stat's
    metadata entry, whose name is the string."""
    name = value = None
    for num, _, val in _fields(buf):
        if num == 1:
            name = stat_names.get(val, str(val))
        elif num == 2:
            value = struct.unpack("<d", val)[0]
        elif num == 3:
            value = val
        elif num == 4:
            value = _signed(val)
        elif num == 5:
            value = bytes(val).decode("utf-8", "replace")
        elif num == 6:
            value = bytes(val)
        elif num == 7:
            value = stat_names.get(val, str(val))
    return name, value


def _map_entry(buf):
    key = value = None
    for num, _, val in _fields(buf):
        if num == 1:
            key = val
        elif num == 2:
            value = val
    return key, value


def read(path):
    """Every plane of the file::

        [{"name": str,
          "lines": [{"id": int, "name": str,
                     "events": [{"name": str, "start_ns": float,
                                 "end_ns": float, "stats": {..},
                                 "meta": {..}}, ...]}, ...]}, ...]

    ``stats`` are the event's own (a ``TraceAnnotation``'s arguments),
    ``meta`` those of its metadata entry, shared by every event of the name
    (a device operation's ``tf_op``, ``source``, ``bytes_accessed``).
    """
    with open(path, "rb") as f:
        space = memoryview(f.read())
    planes = []
    for num, _, plane_buf in _fields(space):
        if num != 1:
            continue
        name, line_bufs, event_bufs, stat_names = "", [], {}, {}
        for num, _, val in _fields(plane_buf):
            if num == 2:
                name = bytes(val).decode()
            elif num == 3:
                line_bufs.append(val)
            elif num == 4:
                key, value = _map_entry(val)
                event_bufs[key] = value
            elif num == 5:
                key, value = _map_entry(val)
                for n2, _, v2 in _fields(value):
                    if n2 == 2:
                        stat_names[key] = bytes(v2).decode()
        kinds = {}
        for key, buf in event_bufs.items():
            ev_name, display, meta = "", "", {}
            for num, _, val in _fields(buf):
                if num == 2:
                    ev_name = bytes(val).decode("utf-8", "replace")
                elif num == 4:
                    display = bytes(val).decode("utf-8", "replace")
                elif num == 5:
                    k, v = _stat(val, stat_names)
                    meta[k] = v
            kinds[key] = (ev_name, display, meta)
        lines = []
        for line_buf in line_bufs:
            line = {"id": 0, "name": "", "events": []}
            t0_ns, event_list = 0, []
            for num, _, val in _fields(line_buf):
                if num == 1:
                    line["id"] = val
                elif num == 2:
                    line["name"] = bytes(val).decode()
                elif num == 3:
                    t0_ns = val
                elif num == 4:
                    event_list.append(val)
            for ev_buf in event_list:
                kind = offset_ps = duration_ps = 0
                stats = {}
                for num, _, val in _fields(ev_buf):
                    if num == 1:
                        kind = val
                    elif num == 2:
                        offset_ps = val
                    elif num == 3:
                        duration_ps = val
                    elif num == 4:
                        k, v = _stat(val, stat_names)
                        stats[k] = v
                ev_name, display, meta = kinds.get(kind, ("", "", {}))
                start = t0_ns + offset_ps / 1e3
                line["events"].append({
                    "name": ev_name, "display_name": display,
                    "start_ns": start, "end_ns": start + duration_ps / 1e3,
                    "stats": stats, "meta": meta})
            lines.append(line)
        planes.append({"name": name, "lines": lines})
    return planes


def _describe(path):
    """Print what a trace holds, metadata included: look at one by hand
    before trusting code."""
    for plane in read(path):
        print("plane", plane["name"])
        for line in plane["lines"]:
            print(f"  line {line['name']!r} (id {line['id']}): "
                  f"{len(line['events'])} events")
            seen = set()
            for e in line["events"]:
                if e["name"] in seen or len(seen) >= 8:
                    continue
                seen.add(e["name"])
                print(f"    {e['name'][:60]!r} stats={e['stats']} "
                      f"meta={e['meta']}")


if __name__ == "__main__":
    import sys
    _describe(sys.argv[1])
