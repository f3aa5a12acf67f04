"""From the program's own spans and operator scopes, as the profiler's file
holds them, to per-layer numbers.

The program marks its host work with ``wf.*`` spans (``TraceAnnotation``s:
``windflow_tpu/observability/tracing.py::span``, names in
``docs/ARCHITECTURE.md``) and traces each operator under a
``jax.named_scope`` (``Class:name``, and ``insert`` / ``emit`` inside the
window engine), which the profiler stores as each device operation's
``tf_op``.  Both are on the clock of the device plane.  ``reduce`` clips
them to the harness's ``bench_slice`` and adds them up; the readers in
``layer_metrics/`` turn that into time per batch.  A program without spans
or scopes (a parent commit) gives empty tables and every reader ``None``.

A span that was open when the profiler's session began, or still open when
it ended, is not in the file (a TraceMe is recorded when it both starts and
ends inside a session), and the session is the slice.  The drive thread
opens a new span at once and closes its last with the slice; the prefetch
thread of ``kcb.backlog`` sits in ``wf.source.put`` for 50 of every 68 ms,
so its recorded spans can begin up to 50 ms into the slice and end as long
before its end.  A thread's spans are therefore taken over the part of the
slice the thread was *observed* in, from its first recorded span's start to
its last one's end, and a span's time per batch is its share of that window
times the slice's time per batch.

The first reader of a run also writes the tables a ``perf_opt`` issue quotes
to standard error and to ``.bench_trace/<cell>/scopes.json``: device time by
operation with its scope path, source line and ``bytes_accessed``, and
device idle time by the innermost ``wf.*`` span open meanwhile.
"""

import json
import os
import re
import sys

import xplane_meta
from trace_reduce import union

SLICE_NAME = "bench_slice"
HOST_PLANE = "/host:CPU"
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "wf."
#: an operator's scope is ``Class:name``; the window engine's phases sit
#: directly under it
OPERATOR = re.compile(r"^[A-Za-z_]\w*:[^/]+$")
PHASES = ("insert", "emit")
PROFILE_DIR = os.sep + os.path.join("plugins", "profile") + os.sep


def overlap(intervals, cover):
    """Length of ``intervals`` (disjoint, sorted) inside ``cover`` (same)."""
    total, j = 0.0, 0
    for s, e in intervals:
        while j < len(cover) and cover[j][1] <= s:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            total += min(e, cover[k][1]) - max(s, cover[k][0])
            k += 1
    return total


def self_intervals(events, lo, hi):
    """Per event of one line (they nest, never cross): the parts of it, inside
    ``[lo, hi]``, that no later-starting event of the line covers."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i]["start_ns"], -events[i]["end_ns"]))
    own = [[] for _ in events]
    stack = []                     # indices of the open events, outermost first

    def close_until(t):
        # pop every event that ended by t, giving each its tail
        while stack and events[stack[-1]]["end_ns"] <= t:
            i = stack.pop()
            cursor[i] = max(cursor[i], lo)
            end = min(events[i]["end_ns"], hi)
            if end > cursor[i]:
                own[i].append((cursor[i], end))
            if stack:
                cursor[stack[-1]] = max(cursor[stack[-1]], events[i]["end_ns"])

    cursor = [e["start_ns"] for e in events]
    for i in order:
        start = events[i]["start_ns"]
        close_until(start)
        if stack:
            p = stack[-1]
            a, b = max(cursor[p], lo), min(start, hi)
            if b > a:
                own[p].append((a, b))
            cursor[p] = max(cursor[p], start)
        stack.append(i)
    close_until(float("inf"))
    return own


def scope_of(tf_op):
    """(scope path, operator, phase) of a device operation's ``tf_op``
    (``<scope path>:<type>``); operator and phase are None where the path
    names none."""
    if not tf_op:
        return None, None, None
    path = tf_op.rsplit(":", 1)[0]
    parts = path.split("/")
    for i, part in enumerate(parts):
        if OPERATOR.match(part):
            phase = parts[i + 1] if i + 1 < len(parts) else None
            return path, part, phase if phase in PHASES else None
    return path, None, None


def reduce(path):
    """Everything the readers need from one ``.xplane.pb``, in ns inside the
    slice: ``spans`` (per ``wf.*`` name: clipped total, its share of its
    thread's observed window, count, thread names),
    ``device_ops`` (per operation: self time, scope, source, bytes), the
    device's busy and idle time, and idle time by innermost open span."""
    planes = xplane_meta.read(path)
    host = [p for p in planes if p["name"] == HOST_PLANE]
    slices = [e for p in host for ln in p["lines"] for e in ln["events"]
              if e["name"] == SLICE_NAME]
    if len(slices) != 1:
        raise RuntimeError(f"the trace holds {len(slices)} {SLICE_NAME!r} "
                           f"spans, expected one")
    lo, hi = slices[0]["start_ns"], slices[0]["end_ns"]

    # ---- host: the program's spans, per thread -----------------------------
    spans, innermost, any_span = {}, {}, []
    for plane in host:
        for line in plane["lines"]:
            evs = [e for e in line["events"]
                   if e["name"].startswith(SPAN_PREFIX)
                   and e["end_ns"] > lo and e["start_ns"] < hi]
            if not evs:
                continue
            # the thread was observed from its first recorded span to its last
            seen_ns = (min(hi, max(e["end_ns"] for e in evs))
                       - max(lo, min(e["start_ns"] for e in evs)))
            for e, own in zip(evs, self_intervals(evs, lo, hi)):
                row = spans.setdefault(e["name"], {
                    "ns": 0.0, "share": 0.0, "count": 0, "threads": set()})
                ns = min(e["end_ns"], hi) - max(e["start_ns"], lo)
                row["ns"] += ns
                row["share"] += ns / seen_ns
                row["count"] += 1
                row["threads"].add(line["name"])
                innermost.setdefault(e["name"], []).extend(own)
                any_span.append((max(e["start_ns"], lo), min(e["end_ns"], hi)))
    for row in spans.values():
        row["threads"] = sorted(row["threads"])

    # ---- device: self time per operation, by scope ---------------------------
    ops, busy = {}, []
    for plane in planes:
        if not plane["name"].startswith(DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            if line["name"] != OPS_LINE:
                continue
            evs = [e for e in line["events"]
                   if e["end_ns"] > lo and e["start_ns"] < hi]
            for e, own in zip(evs, self_intervals(evs, lo, hi)):
                scope, operator, phase = scope_of(e["meta"].get("tf_op"))
                hlo = e["name"].split(" = ")[0]
                row = ops.setdefault(hlo, {
                    "hlo": hlo, "scope": scope, "operator": operator,
                    "phase": phase, "source": e["meta"].get("source"),
                    "category": e["meta"].get("hlo_category"),
                    "bytes_accessed": e["meta"].get("bytes_accessed"),
                    "ns": 0.0, "count": 0})
                row["ns"] += sum(b - a for a, b in own)
                row["count"] += 1
                busy.append((max(e["start_ns"], lo), min(e["end_ns"], hi)))
    busy = union(busy)
    busy_ns = sum(e - s for s, e in busy)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    idle_ns = sum(e - s for s, e in idle)
    return {
        "slice_ns": hi - lo,
        "spans": spans,
        "device_ops": sorted(ops.values(), key=lambda r: -r["ns"]),
        "busy_ns": busy_ns,
        "idle_ns": idle_ns,
        "idle_by_span": {name: overlap(idle, union(own))
                         for name, own in innermost.items()},
        "idle_unexplained_ns": idle_ns - overlap(idle, union(any_span)),
    }


def for_run(run):
    """The reduction of a traced run (``run.py``'s reader context, which the
    readers of one run share: it is kept there), or None where there is no
    trace; the first reader reports."""
    path = run.get("trace_path")
    if not path or not run.get("slice_batches"):
        return None
    if "span_reduce" not in run:
        run["span_reduce"] = reduce(path)
        report(run["span_reduce"], run["slice_batches"], path)
    return run["span_reduce"]


def span_ms(run, name):
    """ms per batch of the slice inside the program's span ``name``."""
    red = for_run(run)
    if red is None or name not in red["spans"]:
        return None
    return (red["spans"][name]["share"] * red["slice_ns"] / 1e6
            / run["slice_batches"])


def window_engines(red):
    """Operators whose scope holds an ``insert`` or ``emit`` phase."""
    return {r["operator"] for r in red["device_ops"] if r["phase"]}


def window_ms(run, phase=None):
    """ms of device time per batch under the window engines' scopes (one
    ``phase`` of them, or all of each engine)."""
    red = for_run(run)
    if red is None:
        return None
    engines = window_engines(red)
    if not engines:
        return None
    ns = sum(r["ns"] for r in red["device_ops"] if r["operator"] in engines
             and (phase is None or r["phase"] == phase))
    return ns / 1e6 / run["slice_batches"]


def unscoped_share(run):
    """Share of the device's busy time in operations whose ``tf_op`` names
    no operator; None where no operation names one."""
    red = for_run(run)
    if red is None or not red["busy_ns"]:
        return None
    if not any(r["operator"] for r in red["device_ops"]):
        return None
    ns = sum(r["ns"] for r in red["device_ops"] if not r["operator"])
    return 100.0 * ns / red["busy_ns"]


def idle_unexplained_share(run):
    """Share of the device's idle time in the slice during which no ``wf.*``
    span was open on any thread; None where the program emits none."""
    red = for_run(run)
    if red is None or not red["spans"] or not red["idle_ns"]:
        return None
    return 100.0 * red["idle_unexplained_ns"] / red["idle_ns"]


def report(red, slice_batches, path):
    per_batch = 1e6 * slice_batches
    table = {
        "slice_ms": red["slice_ns"] / 1e6,
        "slice_batches": slice_batches,
        "device_busy_ms_per_batch": red["busy_ns"] / per_batch,
        "device_idle_ms_per_batch": red["idle_ns"] / per_batch,
        "device_ops": [dict({k: r[k] for k in (
            "hlo", "scope", "operator", "phase", "source", "category",
            "bytes_accessed", "count")},
            ms_per_batch=r["ns"] / per_batch,
            share_of_busy=100.0 * r["ns"] / max(red["busy_ns"], 1.0))
            for r in red["device_ops"]],
        "host_spans": {name: {"ms_per_batch": (row["share"] * red["slice_ns"]
                                               / per_batch),
                              "count": row["count"],
                              "threads": row["threads"]}
                       for name, row in sorted(red["spans"].items())},
        "idle_ms_per_batch_by_innermost_span": dict(sorted(
            ((n, ns / per_batch) for n, ns in red["idle_by_span"].items()),
            key=lambda kv: -kv[1])),
        "idle_unexplained_ms_per_batch": red["idle_unexplained_ns"] / per_batch,
    }
    say = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    say("program spans, ms per batch of the slice (count; threads):")
    for name, row in table["host_spans"].items():
        say(f"  {name:22s} {row['ms_per_batch']:9.3f} ({row['count']}; "
            f"{', '.join(row['threads'])})")
    say("device time by operation, ms per batch (share of busy) scope | "
        "source | bytes_accessed:")
    for r in table["device_ops"][:16]:
        say(f"  {r['hlo']:28s} {r['ms_per_batch']:9.4f} "
            f"({r['share_of_busy']:5.2f} %) {r['scope']} | "
            f"{os.path.basename(r['source'] or '-')} | {r['bytes_accessed']}")
    say(f"device idle {table['device_idle_ms_per_batch']:.3f} ms per batch, "
        f"by the innermost program span open meanwhile (threads overlap): "
        + ", ".join(f"{n} {v:.3f}" for n, v in
                    table["idle_ms_per_batch_by_innermost_span"].items())
        + f"; under none {table['idle_unexplained_ms_per_batch']:.3f}")
    if PROFILE_DIR in path:
        out = os.path.join(path.split(PROFILE_DIR)[0], "scopes.json")
        with open(out, "w") as f:
            json.dump(table, f, indent=1)
        say(f"scopes and spans written to {out}")


if __name__ == "__main__":
    report(reduce(sys.argv[1]), int(sys.argv[2]), sys.argv[1])
