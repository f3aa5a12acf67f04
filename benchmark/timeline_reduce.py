"""One timeline a batch, on one clock: the program's ``wf.*`` spans joined on
``pos`` across the prefetch thread, the drive thread and the device.

Every ``wf.*`` span of a batch carries the batch's offered position as its
``pos`` argument (``windflow_tpu/observability/tracing.py::POS_ATTR``), so the
profiler's file holds, per batch, when each stage of the served path began and
ended: ``wf.source.next|unpack|frame|h2d|put`` on the prefetch thread,
``wf.drive.ingest_wait``, ``wf.chain.push|dispatch|sync`` and
``wf.sink.consume|d2h|deliver`` on the drive thread.  ``span_reduce.py`` adds
those spans up by name, as means over the slice; this module joins them into
one row a batch and takes **medians over the rows**, which one stall of the
machine cannot move (ledger, PR 35, ``kpf.backlog``: ``push_ms`` 2.51 -> 9.62
from one 131 ms stall in 16 batches).

**The clock.**  ``tracing.py``, ``span_reduce.py`` and ``trace_reduce.py`` say
the program's spans and the device's operations are "on the clock of the
device plane".  They are not: the host plane's events (TraceMes, the runtime's
own) share one clock, and the device plane reads **early** against it by 1.3
to 2.0 ms in every trace this repository holds (a step's ``XLA Modules``
event starts 1.29-1.50 ms *before* the runtime's ``DoEnqueueProgram`` for it
and ends 1.90-2.08 ms before ``tpu::System::Execute=>Done``).  The offset is
not in the file, but causality bounds it: a device cannot start a program
before the host has enqueued it, and the host cannot learn of its end before
it ends.  So, over the steps of the slice,

    lo = max(anchor before - module start)
    hi = min(anchor after - module end)

and the device plane belongs ``delta = (lo + hi) / 2`` later, give or take
``(hi - lo) / 2``; ``hi - lo`` is the smallest launch latency plus the
smallest completion latency the slice saw.  Anchors the program gives:
``wf.chain.dispatch``'s start before, the earlier of ``wf.chain.sync``'s and
``wf.sink.d2h``'s end after.  Anchors the runtime gives, where the host plane
holds them under the names libtpu 0.0.34 writes, joined to the module by
``run_id``: ``DoEnqueueProgram``'s start before, ``Execute=>Done``'s start
(inside that run's ``CompleteCallbacks``) after.  The tighter of the two sets
each bound, and the report says which.

``reduce`` pairs the step program's modules (the module name that occurs once
per dispatch) with the dispatch spans in time order; of the pairings shifted
by -1, 0 and +1 it takes the one a single offset satisfies, the smallest
non-negative one.  A span cut by the slice's edge is absent, not clipped (a
TraceMe open when the session starts or stops is not recorded), so rows may
be partial and every number says over how many rows it was taken.  A program
without ``wf.chain.dispatch`` (a parent commit) gives no timeline and every
reader ``None``.

The first reader of a run writes the rows, the bounds with their anchors, the
medians and **device idle time by the innermost ``wf.*`` span open meanwhile,
with the device where the file has it and shifted by delta**, to standard
error and to ``.bench_trace/<cell>/timeline.json``.
"""

import json
import os
import statistics
import sys

import span_reduce
import xplane_meta
from trace_reduce import union

DISPATCH = "wf.chain.dispatch"
STAGES = ("wf.source.next", "wf.source.unpack", "wf.source.frame",
          "wf.source.h2d", "wf.source.put", "wf.drive.ingest_wait",
          "wf.chain.push", DISPATCH, "wf.chain.sync",
          "wf.sink.consume", "wf.sink.d2h", "wf.sink.deliver")
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"
DONE = "tpu::System::Execute=>Done"
COMPLETE = "CompleteCallbacks"
#: under this many rows (or paired steps) a reader gives None
MIN_ROWS = 8
SHIFTS = (0, -1, 1)
#: the two offsets are one value a slice, the other six a value a row
METRICS = ("batch_residence_ms", "batch_queue_dwell_ms", "chain_dispatch_ms",
           "step_round_trip_overhead_ms", "step_launch_ms",
           "step_done_to_host_ms", "device_clock_offset_ms",
           "device_clock_slack_ms")


def batch_rows(host_lines, lo, hi):
    """``{pos: {span name: (start_ns, end_ns, thread)}}`` of the ``wf.*`` spans
    that lie whole inside ``[lo, hi]``; the first of a name where a batch has
    two (a flushed batch pushed through a suffix of the chain)."""
    rows = {}
    for thread, events in host_lines:
        for e in events:
            pos = e["stats"].get("pos")
            if (pos is None or e["name"] not in STAGES
                    or e["start_ns"] < lo or e["end_ns"] > hi):
                continue
            rows.setdefault(pos, {}).setdefault(
                e["name"], (e["start_ns"], e["end_ns"], thread))
    return dict(sorted(rows.items()))


def step_modules(planes, n_steps):
    """The ``XLA Modules`` events of the step program, by start: the module
    name whose count is ``n_steps`` give or take the one a slice's edge cuts
    (EOS and warm-up programs are other names), the busiest where several
    are.  ``(events, None)`` or ``(None, reason)``."""
    by_name, devices = {}, 0
    for plane in planes:
        if not plane["name"].startswith(span_reduce.DEVICE_PLANE):
            continue
        for line in plane["lines"]:
            if line["name"] == MODULES_LINE and line["events"]:
                devices += 1
                for e in line["events"]:
                    by_name.setdefault(e["name"], []).append(e)
    if devices != 1:
        return None, f"{devices} device planes hold an {MODULES_LINE!r} line"
    fits = [evs for evs in by_name.values() if abs(len(evs) - n_steps) <= 1]
    if not fits:
        counts = {n: len(evs) for n, evs in by_name.items()}
        return None, (f"no module runs once per dispatch ({n_steps}): "
                      f"{counts}")
    best = max(fits, key=lambda evs: sum(e["end_ns"] - e["start_ns"]
                                         for e in evs))
    return sorted(best, key=lambda e: e["start_ns"]), None


def runtime_anchors(host_lines):
    """The runtime's own anchors by ``run_id``: ``({run_id: enqueue start},
    {run_id: Execute=>Done start})``; empty where the host plane holds no
    event of those names."""
    enqueued, done = {}, {}
    for _, events in host_lines:
        ends = [e for e in events if e["name"] == DONE]
        for e in events:
            run_id = e["stats"].get("run_id")
            if run_id is None:
                continue
            if e["name"] == ENQUEUE:
                enqueued.setdefault(run_id, e["start_ns"])
            elif e["name"] == COMPLETE:
                inside = [d["start_ns"] for d in ends
                          if e["start_ns"] <= d["start_ns"]
                          and d["end_ns"] <= e["end_ns"]]
                if inside:
                    done.setdefault(run_id, inside[0])
    return enqueued, done


def offset_bounds(pairs, rows, before, enqueued, done):
    """``(lo, hi)`` over ``pairs`` of (before span, module), each ``(ns, the
    anchor that set it, its pos)`` or None where no pair has such an anchor."""
    lo = hi = None
    for span, module in pairs:
        run_id = module["stats"].get("run_id")
        pos = span["stats"].get("pos")
        row = rows.get(pos, {})
        befores = [(span["start_ns"], before + " start")]
        if run_id in enqueued:
            befores.append((enqueued[run_id], ENQUEUE + " start"))
        afters = [(row[name][1], name + " end")
                  for name in ("wf.chain.sync", "wf.sink.d2h") if name in row]
        if run_id in done:
            afters.append((done[run_id], DONE + " start"))
        t, anchor = max(befores)
        if lo is None or t - module["start_ns"] > lo[0]:
            lo = (t - module["start_ns"], anchor, pos)
        if afters:
            t, anchor = min(afters)
            if hi is None or t - module["end_ns"] < hi[0]:
                hi = (t - module["end_ns"], anchor, pos)
    return lo, hi


def pair_steps(spans, modules, rows, before, enqueued, done):
    """The pairing of ``spans`` (the before-anchor's, by start) with
    ``modules`` that one offset satisfies: ``(pairs, lo, hi, None)``, or
    ``(None, None, None, reason)``."""
    tried, best = [], None
    for shift in SHIFTS:
        pairs = [(s, modules[i + shift]) for i, s in enumerate(spans)
                 if 0 <= i + shift < len(modules)]
        if len(pairs) < MIN_ROWS:
            tried.append(f"shift {shift}: {len(pairs)} pairs")
            continue
        lo, hi = offset_bounds(pairs, rows, before, enqueued, done)
        if hi is None:
            tried.append(f"shift {shift}: no anchor after any step")
            continue
        tried.append(f"shift {shift}: lo {lo[0] / 1e6:.3f} ms ({lo[1]}), "
                     f"hi {hi[0] / 1e6:.3f} ms ({hi[1]})")
        delta = (lo[0] + hi[0]) / 2
        if lo[0] <= hi[0] and delta >= 0 and (
                best is None or delta < best[0]):
            best = (delta, pairs, lo, hi)
    if best is None:
        return None, None, None, ("no pairing of steps and modules that one "
                                  "offset >= 0 satisfies: " + "; ".join(tried))
    return best[1], best[2], best[3], None


def innermost_spans(host_lines, lo, hi):
    """``{span name: [(start, end), ...]}``: the parts of the slice in which
    that ``wf.*`` span was the innermost one open on its thread, and the
    parts in which any was open: ``span_reduce.reduce``'s attribution."""
    innermost, any_span = {}, []
    for _, events in host_lines:
        evs = [e for e in events
               if e["name"].startswith(span_reduce.SPAN_PREFIX)
               and e["end_ns"] > lo and e["start_ns"] < hi]
        for e, own in zip(evs, span_reduce.self_intervals(evs, lo, hi)):
            innermost.setdefault(e["name"], []).extend(own)
            any_span.append((max(e["start_ns"], lo), min(e["end_ns"], hi)))
    return {n: union(own) for n, own in innermost.items()}, union(any_span)


def device_busy(planes):
    """The union of the device's operations, on the device plane's clock."""
    return union((e["start_ns"], e["end_ns"]) for plane in planes
                 if plane["name"].startswith(span_reduce.DEVICE_PLANE)
                 for line in plane["lines"]
                 if line["name"] == span_reduce.OPS_LINE
                 for e in line["events"])


def idle_by_span(busy, innermost, any_span, lo, hi, shift_ns):
    """Device idle time inside ``[lo, hi]`` with the device plane moved
    ``shift_ns`` later: in all, by innermost span, and under none (ns)."""
    moved = [(max(s + shift_ns, lo), min(e + shift_ns, hi)) for s, e in busy
             if e + shift_ns > lo and s + shift_ns < hi]
    edges = [lo] + [t for iv in moved for t in iv] + [hi]
    idle = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
    idle_ns = sum(e - s for s, e in idle)
    return {"idle_ns": idle_ns,
            "by_span": {n: span_reduce.overlap(idle, own)
                        for n, own in innermost.items()},
            "unexplained_ns": idle_ns - span_reduce.overlap(idle, any_span)}


def row_metrics(rows, steps, delta):
    """Per metric, ``[(pos, ms), ...]`` over the rows that hold both ends;
    ``steps`` is ``{pos: module}`` (empty without a pairing), ``delta`` the
    offset in ns or None."""
    out = {name: [] for name in METRICS if not name.startswith("device_")}

    def add(name, pos, ns):
        out[name].append((pos, ns / 1e6))

    for pos, row in rows.items():
        end = row.get("wf.sink.deliver", row.get("wf.sink.consume"))
        if "wf.source.next" in row and end is not None:
            add("batch_residence_ms", pos, end[1] - row["wf.source.next"][1])
        if "wf.source.put" in row and "wf.drive.ingest_wait" in row:
            add("batch_queue_dwell_ms", pos, row["wf.drive.ingest_wait"][1]
                - row["wf.source.put"][1])
        if DISPATCH not in row:
            continue
        start = row[DISPATCH][0]
        add("chain_dispatch_ms", pos, row[DISPATCH][1] - start)
        module = steps.get(pos)
        if module is None or "wf.sink.d2h" not in row:
            continue
        back = row["wf.sink.d2h"][1]
        overhead = (back - start) - (module["end_ns"] - module["start_ns"])
        add("step_round_trip_overhead_ms", pos, overhead)
        if delta is None:
            continue
        launch = module["start_ns"] + delta - start
        done = back - (module["end_ns"] + delta)
        # what is around the step is before it or after it, row for row
        assert abs(launch + done - overhead) < 1.0, (pos, launch, done)
        add("step_launch_ms", pos, launch)
        add("step_done_to_host_ms", pos, done)
    return out


def summary(values):
    """Median, quartiles, maximum and count of ``[(pos, ms), ...]``."""
    ms = [v for _, v in values]
    if not ms:
        return {"rows": 0}
    q = statistics.quantiles(ms, n=4) if len(ms) > 1 else [ms[0]] * 3
    return {"median": statistics.median(ms), "q1": q[0], "q3": q[2],
            "max": max(ms), "rows": len(ms)}


def reduce(path, before=DISPATCH):
    """The timeline of one ``.xplane.pb``.  ``before`` names the span whose
    start is the program's anchor before a step, ``wf.chain.dispatch``; an
    older trace gives its pairing and bounds through ``wf.chain.push`` or the
    harness's ``push`` (and no metric that reads the dispatch)."""
    planes = xplane_meta.read(path)
    host_lines = [(line["name"], line["events"]) for p in planes
                  if p["name"] == span_reduce.HOST_PLANE
                  for line in p["lines"]]
    slices = [e for _, events in host_lines for e in events
              if e["name"] == span_reduce.SLICE_NAME]
    if len(slices) != 1:
        raise RuntimeError(f"the trace holds {len(slices)} "
                           f"{span_reduce.SLICE_NAME!r} spans, expected one")
    lo, hi = slices[0]["start_ns"], slices[0]["end_ns"]
    spans = sorted((e for _, events in host_lines for e in events
                    if e["name"] == before and e["start_ns"] >= lo
                    and e["end_ns"] <= hi), key=lambda e: e["start_ns"])
    red = {"slice_ns": hi - lo, "slice_start_ns": lo, "before": before,
           "rows": {}, "steps": {}, "bounds": None, "per_row": {},
           "metrics": {}, "idle": None, "reason": None}
    if not spans:
        red["reason"] = f"the program emits no {before} span"
        return red
    rows = red["rows"] = batch_rows(host_lines, lo, hi)
    modules, red["reason"] = step_modules(planes, len(spans))
    pairs = None
    if modules is not None:
        red["runtime"] = runtime_anchors(host_lines)
        pairs, lo_b, hi_b, red["reason"] = pair_steps(
            spans, modules, rows, before, *red["runtime"])
    delta, per_slice = None, {}
    if pairs is not None:
        delta = (lo_b[0] + hi_b[0]) / 2
        red["steps"] = {s["stats"]["pos"]: m for s, m in pairs
                        if "pos" in s["stats"]}
        red["bounds"] = {"lo_ns": lo_b[0], "lo_anchor": lo_b[1],
                         "lo_pos": lo_b[2], "hi_ns": hi_b[0],
                         "hi_anchor": hi_b[1], "hi_pos": hi_b[2],
                         "delta_ns": delta, "pairs": len(pairs),
                         "module": modules[0]["name"]}
        per_slice = {name: {"median": ns / 1e6, "rows": len(pairs)}
                     for name, ns in (
                         ("device_clock_offset_ms", delta),
                         ("device_clock_slack_ms", hi_b[0] - lo_b[0]))}
        busy = device_busy(planes)
        innermost, any_span = innermost_spans(host_lines, lo, hi)
        red["idle"] = {side: idle_by_span(busy, innermost, any_span, lo, hi,
                                          shift)
                       for side, shift in (("unshifted", 0.0),
                                           ("shifted", delta))}
    red["per_row"] = row_metrics(rows, red["steps"], delta)
    red["metrics"] = dict({name: summary(values)
                           for name, values in red["per_row"].items()},
                          **per_slice)
    return red


def for_run(run):
    """The timeline of a traced run (``run.py``'s reader context, kept there
    for the readers of one run), or None where there is no trace; the first
    reader reports."""
    path = run.get("trace_path")
    if not path or not run.get("slice_batches"):
        return None
    if "timeline_reduce" not in run:
        run["timeline_reduce"] = reduce(path)
        report(run["timeline_reduce"], run["slice_batches"], path)
    return run["timeline_reduce"]


def metric(run, name):
    """The median of ``name`` over the slice's rows; None under ``MIN_ROWS``
    rows, without a trace, or for a program without ``wf.chain.dispatch``."""
    red = for_run(run)
    if red is None:
        return None
    row = red["metrics"].get(name)
    if row is None or row["rows"] < MIN_ROWS:
        return None
    return row["median"]


def table(red, slice_batches):
    """What ``timeline.json`` holds: times in ms from the slice's start, idle
    time per batch of the slice as ``scopes.json`` has it."""
    t0 = red["slice_start_ns"]
    out = {"slice_ms": red["slice_ns"] / 1e6, "before_anchor": red["before"],
           "reason": red["reason"], "bounds": None,
           "metrics": red["metrics"], "rows": [], "idle_ms": None}
    if red["bounds"] is not None:
        out["bounds"] = {
            (k[:-3] + "_ms" if k.endswith("_ns") else k):
            (v / 1e6 if k.endswith("_ns") else v)
            for k, v in red["bounds"].items()}
    per_row = {name: dict(values) for name, values in red["per_row"].items()}
    for pos, row in red["rows"].items():
        line = {"pos": pos, "spans": {
            name: {"start_ms": (s - t0) / 1e6, "end_ms": (e - t0) / 1e6,
                   "thread": thread} for name, (s, e, thread) in row.items()}}
        module = red["steps"].get(pos)
        if module is not None:
            run_id = module["stats"].get("run_id")
            # the module on the device plane's own clock, the runtime's two
            # events for it on the host's
            line["module"] = {"start_ms": (module["start_ns"] - t0) / 1e6,
                              "end_ms": (module["end_ns"] - t0) / 1e6,
                              "run_id": run_id}
            for key, at in zip(("enqueue_ms", "done_ms"), red["runtime"]):
                if run_id in at:
                    line["module"][key] = (at[run_id] - t0) / 1e6
        line["metrics"] = {name: values[pos]
                           for name, values in per_row.items()
                           if pos in values}
        out["rows"].append(line)
    if red["idle"] is not None:
        per_batch = 1e6 * slice_batches
        out["idle_ms"] = {side: {
            "idle": idle["idle_ns"] / per_batch,
            "by_innermost_span": dict(sorted(
                ((name, ns / per_batch)
                 for name, ns in idle["by_span"].items()),
                key=lambda kv: -kv[1])),
            "under_none": idle["unexplained_ns"] / per_batch}
            for side, idle in red["idle"].items()}
    return out


def report(red, slice_batches, path):
    say = lambda msg: print(msg, file=sys.stderr, flush=True)  # noqa: E731
    if red["reason"] is not None:
        say(f"timeline: {red['reason']}")
    if not red["rows"] and red["bounds"] is None:
        return
    out = table(red, slice_batches)
    b = out["bounds"]
    if b is not None:
        say(f"timeline: {b['pairs']} steps of {b['module']}; the device plane "
            f"reads early by {b['lo_ms']:.3f} ms ({b['lo_anchor']}, pos "
            f"{b['lo_pos']}) to {b['hi_ms']:.3f} ms ({b['hi_anchor']}, pos "
            f"{b['hi_pos']}): delta {b['delta_ms']:.3f}, slack "
            f"{b['hi_ms'] - b['lo_ms']:.3f}")
    say("timeline, ms a batch: median (first quartile, third; maximum; rows)")
    for name, row in out["metrics"].items():
        if "q1" in row:
            say(f"  {name:28s} {row['median']:8.3f} ({row['q1']:.3f}, "
                f"{row['q3']:.3f}; {row['max']:.3f}; {row['rows']})")
        elif row["rows"]:
            say(f"  {name:28s} {row['median']:8.3f} "
                f"(over {row['rows']} steps)")
    if out["idle_ms"] is not None:
        for side in ("unshifted", "shifted"):
            idle = out["idle_ms"][side]
            say(f"device idle {idle['idle']:.3f} ms a batch, device "
                f"{side}, by innermost span: " + ", ".join(
                    f"{n} {v:.3f}"
                    for n, v in idle["by_innermost_span"].items())
                + f"; under none {idle['under_none']:.3f}")
    if span_reduce.PROFILE_DIR in path:
        where = os.path.join(path.split(span_reduce.PROFILE_DIR)[0],
                             "timeline.json")
        with open(where, "w") as f:
            json.dump(out, f, indent=1)
        say(f"timeline written to {where}")


if __name__ == "__main__":
    report(reduce(sys.argv[1], *sys.argv[3:4]), int(sys.argv[2]), sys.argv[1])
