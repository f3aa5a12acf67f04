"""The benchmark's per-batch timeline (``benchmark/timeline_reduce.py`` and the
eight ``layer_metrics/`` over it), held to recorded traces.

``benchmark/testdata/kff_timeline.xplane.pb`` is the profiler's own file of one
traced ``kff.backlog`` run of this tree on the chip (``testdata/TIMELINE.md``),
with ``expected_timeline.json`` beside it: every reader gives what it gave when
the trace was taken.  The two older traces hold the pairing and the offset's
bounds to figures read by hand (ISSUE 36's table): they have no
``wf.chain.dispatch``, so every reader gives None from them, and their bounds
are read through ``wf.chain.push`` and the harness's ``push``."""

import copy
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import span_reduce  # noqa: E402
import timeline_reduce  # noqa: E402
import xplane_meta  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")
TRACE = os.path.join(TESTDATA, "kff_timeline.xplane.pb")
KCB_TRACE = os.path.join(TESTDATA, "kcb_spans.xplane.pb")
YSB_TRACE = os.path.join(TESTDATA, "ysb_slice.xplane.pb")
with open(os.path.join(TESTDATA, "expected_timeline.json")) as _f:
    EXPECTED = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
METRICS = sorted(timeline_reduce.METRICS)
#: read from the program's spans alone; the other five need the device plane
PROGRAM_SPAN = {"batch_residence_ms", "batch_queue_dwell_ms",
                "chain_dispatch_ms"}
CELLS = ["ysb.backlog", "kcb.backlog", "ysb_wmr.backlog", "kpf.backlog",
         "kff.backlog"]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_context(path):
    """What ``run.py`` hands a reader, as far as these readers look."""
    return {"trace_path": path, "slice_batches": EXPECTED["slice_batches"]}


@pytest.fixture(scope="module")
def timeline():
    return timeline_reduce.reduce(TRACE)


def test_the_eight_metrics_are_the_expected_ones():
    assert METRICS == sorted(EXPECTED["metrics"])


@pytest.mark.parametrize("name", METRICS)
def test_reader_gives_what_it_gave_when_the_trace_was_taken(name, capsys):
    value = reader(name).read(run_context(TRACE))
    assert value == pytest.approx(EXPECTED["metrics"][name], rel=1e-6)
    # the first (here: only) reader of a run reports
    assert "timeline" in capsys.readouterr().err


@pytest.mark.parametrize("name", METRICS)
def test_reader_is_declared_in_all_five_cells(name):
    (entry,) = [m for m in BENCHMARK["per_layer"] if m["name"] == name]
    assert entry["workloads"] == CELLS
    assert (entry["unit"], entry["better"]) == ("ms", "lower")
    assert entry["moves"] == "result_latency_p50_ms"
    assert entry["source"] == ("program_span" if name in PROGRAM_SPAN
                               else "device_trace")
    assert entry["layer"] in {m["layer"] for m in BENCHMARK["per_layer"][:6]}


@pytest.mark.parametrize("name", METRICS)
@pytest.mark.parametrize("path", [KCB_TRACE, YSB_TRACE],
                         ids=["kcb_spans", "ysb_slice"])
def test_reader_finds_nothing_without_the_dispatch_span(name, path):
    """The older traces are of programs without ``wf.chain.dispatch``, as a
    parent commit is: no timeline, every reader None, and no raise."""
    assert reader(name).read(run_context(path)) is None


def test_no_trace_no_number():
    assert timeline_reduce.for_run({"trace_path": None,
                                    "slice_batches": 4}) is None
    assert reader("step_launch_ms").read({"slice_batches": 4}) is None


def test_rows_join_the_threads_on_pos(timeline):
    rows = timeline["rows"]
    assert len(rows) >= EXPECTED["slice_batches"]
    assert list(rows) == sorted(rows)
    whole = [r for r in rows.values() if set(timeline_reduce.STAGES)
             - {"wf.chain.sync"} <= set(r)]
    assert len(whole) >= timeline_reduce.MIN_ROWS
    for row in whole:
        threads = {name: thread for name, (_, _, thread) in row.items()}
        assert len({threads[n] for n in threads if ".source." in n}) == 1
        drive = {threads[n] for n in threads if ".source." not in n}
        assert len(drive) == 1
        assert threads["wf.source.put"] not in drive
        # one batch, in order: pulled, framed, sent, queued, taken, pushed,
        # delivered
        order = ["wf.source.next", "wf.source.unpack", "wf.source.frame",
                 "wf.source.h2d", "wf.source.put"]
        ends = [row[n][1] for n in order]
        assert ends == sorted(ends)
        assert row["wf.drive.ingest_wait"][1] <= row["wf.chain.push"][0]
        push, dispatch = row["wf.chain.push"], row["wf.chain.dispatch"]
        assert push[0] <= dispatch[0] and dispatch[1] <= push[1]
        assert push[1] <= row["wf.sink.consume"][0]
        assert row["wf.sink.d2h"][1] <= row["wf.sink.deliver"][0]
    # the completion wait sits on its batch's row
    synced = [pos for pos, r in rows.items() if "wf.chain.sync" in r]
    assert synced == EXPECTED["by_hand"]["synced_pos"]
    # a span cut by the slice's edge is absent, not clipped
    assert any("wf.source.next" not in r for r in rows.values())


def test_launch_and_completion_add_up_to_the_round_trip_row_for_row(timeline):
    per_row = {name: dict(v) for name, v in timeline["per_row"].items()}
    overhead = per_row["step_round_trip_overhead_ms"]
    assert len(overhead) >= timeline_reduce.MIN_ROWS
    assert set(per_row["step_launch_ms"]) == set(overhead)
    for pos, ms in overhead.items():
        assert (per_row["step_launch_ms"][pos]
                + per_row["step_done_to_host_ms"][pos]) == pytest.approx(
                    ms, abs=1e-6)
        module = timeline["steps"][pos]
        row = timeline["rows"][pos]
        assert ms == pytest.approx(
            ((row["wf.sink.d2h"][1] - row["wf.chain.dispatch"][0])
             - (module["end_ns"] - module["start_ns"])) / 1e6, abs=1e-9)


def test_offset_obeys_causality_in_every_step(timeline):
    b = timeline["bounds"]
    assert b["pairs"] == EXPECTED["by_hand"]["steps"]
    assert 0 <= b["lo_ns"] <= b["delta_ns"] <= b["hi_ns"]
    assert b["lo_ns"] / 1e6 == pytest.approx(EXPECTED["by_hand"]["lo_ms"],
                                             abs=0.01)
    assert b["hi_ns"] / 1e6 == pytest.approx(EXPECTED["by_hand"]["hi_ms"],
                                             abs=0.01)
    assert (b["lo_anchor"], b["hi_anchor"]) == (
        EXPECTED["by_hand"]["lo_anchor"], EXPECTED["by_hand"]["hi_anchor"])
    for pos, module in timeline["steps"].items():
        row = timeline["rows"][pos]
        # on the host's clock no step starts before its dispatch does, and
        # none ends after the host has its results
        assert (module["start_ns"] + b["delta_ns"]
                >= row["wf.chain.dispatch"][0])
        assert module["end_ns"] + b["delta_ns"] <= row["wf.sink.d2h"][1]


#: ISSUE 36's reading of the two older traces (ms): the runtime's
#: ``DoEnqueueProgram`` sets ``lo``, its ``Execute=>Done`` sets ``hi``.  Its
#: "15 whole steps" of ``kcb_spans`` left out the slice's last, sampled push;
#: all 16 lie inside the slice and the sixteenth moves neither bound.
BY_HAND = {"kcb_spans": (KCB_TRACE, "wf.chain.push", 16, 1.373, 1.898),
           "ysb_slice": (YSB_TRACE, "push", 12, 1.495, 1.916)}


@pytest.mark.parametrize("which", sorted(BY_HAND))
def test_bounds_of_the_older_traces_match_what_was_read_by_hand(which):
    path, before, steps, lo_ms, hi_ms = BY_HAND[which]
    b = timeline_reduce.reduce(path, before=before)["bounds"]
    assert b["pairs"] == steps
    assert b["lo_ns"] / 1e6 == pytest.approx(lo_ms, abs=0.01)
    assert b["hi_ns"] / 1e6 == pytest.approx(hi_ms, abs=0.01)
    assert b["lo_anchor"] == timeline_reduce.ENQUEUE + " start"
    assert b["hi_anchor"] == timeline_reduce.DONE + " start"
    assert b["delta_ns"] == (b["lo_ns"] + b["hi_ns"]) / 2


@pytest.fixture(scope="module")
def kcb_steps():
    """``kcb_spans`` read through ``wf.chain.push``: (the reduction, its
    sixteen pushes by start, their modules, the runtime's anchors)."""
    red = timeline_reduce.reduce(KCB_TRACE, before="wf.chain.push")
    host = [(ln["name"], ln["events"]) for p in xplane_meta.read(KCB_TRACE)
            if p["name"] == span_reduce.HOST_PLANE for ln in p["lines"]]
    spans = sorted((e for _, events in host for e in events
                    if e["name"] == "wf.chain.push"),
                   key=lambda e: e["start_ns"])
    modules = [red["steps"][s["stats"]["pos"]] for s in spans]
    return red, spans, modules, timeline_reduce.runtime_anchors(host)


def test_program_anchors_alone_bound_the_offset_more_loosely(kcb_steps):
    """Without the runtime's events the program's own anchors still hold the
    offset, inside wider bounds that contain the tighter ones."""
    red, spans, modules, _ = kcb_steps
    pairs, lo, hi, reason = timeline_reduce.pair_steps(
        spans, modules, red["rows"], "wf.chain.push", {}, {})
    assert reason is None and len(pairs) == 16
    assert lo[1] == "wf.chain.push start" and hi[1] == "wf.sink.d2h end"
    assert lo[0] <= red["bounds"]["lo_ns"] <= red["bounds"]["hi_ns"] <= hi[0]


def test_a_pairing_off_by_one_step_is_refused(kcb_steps):
    """Modules paired with the wrong push leave no offset that every step's
    causality allows next to the runtime's anchors; the reduction finds the
    pairing that does, whichever end of the slice lost a span."""
    red, spans, modules, (enqueued, done) = kcb_steps
    for cut_spans, cut_modules in ((spans[1:], modules), (spans, modules[1:]),
                                   (spans[:-1], modules)):
        pairs, lo, hi, reason = timeline_reduce.pair_steps(
            cut_spans, cut_modules, red["rows"], "wf.chain.push", enqueued,
            done)
        assert reason is None
        assert all(red["steps"][s["stats"]["pos"]] is m for s, m in pairs)
        assert lo[0] / 1e6 == pytest.approx(1.373, abs=0.03)
    # and steps that match no push at all give no timeline, with a reason
    pairs, _, _, reason = timeline_reduce.pair_steps(
        spans[:10], modules[4:], red["rows"], "wf.chain.push", enqueued, done)
    assert pairs is None and "no pairing" in reason


def test_one_stall_moves_no_median(timeline):
    """Ledger, PR 35, ``kpf.backlog``: one 131 ms stall of the machine under
    ``push`` took the slice's mean ``push_ms`` from 2.51 to 9.62.  The same
    stall planted in one row of the timeline leaves every median where it
    was (and would have moved the mean by 131 / rows)."""
    rows = copy.deepcopy(timeline["rows"])
    victim = sorted(timeline["steps"])[len(timeline["steps"]) // 2]
    stall = 131e6
    for name, (start, end, thread) in rows[victim].items():
        # everything of that batch from the dispatch's return on is late
        if name in ("wf.chain.push", "wf.chain.dispatch"):
            rows[victim][name] = (start, end + stall, thread)
        elif name.startswith("wf.sink.") or name == "wf.chain.sync":
            rows[victim][name] = (start + stall, end + stall, thread)
    delta = timeline["bounds"]["delta_ns"]
    before = timeline_reduce.row_metrics(timeline["rows"], timeline["steps"],
                                         delta)
    after = timeline_reduce.row_metrics(rows, timeline["steps"], delta)
    moved = 0
    for name in before:
        a = timeline_reduce.summary(before[name])
        b = timeline_reduce.summary(after[name])
        assert a["rows"] == b["rows"] >= timeline_reduce.MIN_ROWS
        spread = a["q3"] - a["q1"]
        assert abs(b["median"] - a["median"]) <= spread, name
        mean = lambda v: sum(x for _, x in v) / len(v)      # noqa: E731
        if mean(after[name]) - mean(before[name]) > 131 / a["rows"] - 1e-6:
            moved += 1
    assert moved >= 3       # the means of residence, dispatch, round trip


def test_unshifted_idle_attribution_is_span_reduce_s(timeline):
    red = span_reduce.reduce(TRACE)
    mine = timeline["idle"]["unshifted"]
    assert mine["idle_ns"] == pytest.approx(red["idle_ns"], rel=1e-9)
    assert mine["unexplained_ns"] == pytest.approx(
        red["idle_unexplained_ns"], abs=1.0)
    assert set(mine["by_span"]) == set(red["idle_by_span"])
    for name, ns in red["idle_by_span"].items():
        assert mine["by_span"][name] == pytest.approx(ns, abs=1.0), name
    # moved to the host's clock the idle time is the same (but for what the
    # slice's edges cut) and sits under other spans
    shifted = timeline["idle"]["shifted"]
    assert shifted["idle_ns"] == pytest.approx(mine["idle_ns"], rel=0.02)
    assert (shifted["by_span"]["wf.chain.dispatch"]
            > mine["by_span"]["wf.chain.dispatch"])


def test_the_report_is_written_beside_scopes_json(tmp_path, timeline, capsys):
    cell = tmp_path / "kff.backlog"
    where = cell / "plugins" / "profile" / "2026_10_05"
    where.mkdir(parents=True)
    path = str(where / "vm.xplane.pb")
    timeline_reduce.report(timeline, EXPECTED["slice_batches"], path)
    with open(cell / "timeline.json") as f:
        out = json.load(f)
    assert out["bounds"]["delta_ms"] == pytest.approx(
        EXPECTED["metrics"]["device_clock_offset_ms"], rel=1e-6)
    assert len(out["rows"]) == len(timeline["rows"])
    assert set(out["idle_ms"]) == {"unshifted", "shifted"}
    for name in METRICS:
        assert out["metrics"][name]["median"] == pytest.approx(
            EXPECTED["metrics"][name], rel=1e-6)
        assert out["metrics"][name]["rows"] >= timeline_reduce.MIN_ROWS
    err = capsys.readouterr().err
    assert "device shifted" in err and "device unshifted" in err
