"""From the profiler's ``.xplane.pb`` to numbers: device busy time, the
operations that took most of it, and the idle gaps by what the host was doing.

Reads the trace with ``jax.profiler.ProfileData`` alone. Device planes are
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
operation. The harness's own ``TraceAnnotation`` spans sit on the host plane
on the same clock: ``slice_name`` bounds the traced slice, ``span_names`` label
the gaps. ``selfcheck.py`` holds this reduction to known numbers on a recorded
trace.
"""

import glob
import os

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the trace names an operation by its whole HLO line; its head tells it apart
OP_NAME_CHARS = 96


def find_xplane(trace_dir):
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {paths}")
    return paths[0]


def union(intervals):
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def read_planes(path, line=OPS_LINE):
    """(events of ``line`` per device plane as ``(name, start, end)`` in ns,
    host annotations as ``{name: [(start, end), ...]}``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for ln in plane.lines:
                if ln.name == line:
                    devices[plane.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in ln.events]
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                for e in ln.events:
                    host.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns))
    return devices, host


def reduce(path, slice_name, span_names):
    devices, host = read_planes(path)
    if len(host.get(slice_name, ())) != 1:
        raise RuntimeError(f"the trace holds {len(host.get(slice_name, ()))} "
                           f"{slice_name!r} spans, expected one")
    lo, hi = host[slice_name][0]
    spans = [(s, e, name) for name in span_names
             for s, e in host.get(name, ())]
    busy_ns, op_ns, gaps = [], {}, []
    for ops in devices.values():
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                   if e > lo and s < hi]
        if not clipped:
            continue
        for n, s, e in clipped:
            op_ns[n] = op_ns.get(n, 0) + (e - s)
        merged = union((s, e) for _, s, e in clipped)
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    n_dev = max(len(busy_ns), 1)
    gaps.sort(reverse=True)
    labelled = []
    for length, s, e in gaps[:10]:
        # the host span that covers most of the gap names it
        cover = {}
        for a, b, name in spans:
            ov = min(b, e) - max(a, s)
            if ov > 0:
                cover[name] = cover.get(name, 0) + ov
        name = max(cover, key=cover.get) if cover else "none"
        labelled.append([name, length / 1e9])
    ops_sorted = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "devices": len(busy_ns),
        "device_ops": [[n[:OP_NAME_CHARS], ns / n_dev / 1e9]
                       for n, ns in ops_sorted],
        "idle_gaps": labelled,
    }


def _describe(path):
    """Print what a trace holds: look at one by hand before trusting code."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            events = list(line.events)
            names = sorted({e.name for e in events})
            print(f"  line {line.name!r}: {len(events)} events, "
                  f"{len(names)} names, e.g. {names[:6]}")


if __name__ == "__main__":
    import sys
    _describe(sys.argv[1])
