"""The benchmark's readers of the program's spans and scopes
(``benchmark/xplane_meta.py``, ``benchmark/span_reduce.py``, the
``layer_metrics/`` over them), held to a recorded trace of ``kcb.backlog``:
``benchmark/testdata/kcb_spans.xplane.pb`` with ``expected_spans.json``.

``xplane_meta`` decodes the file's wire format itself, so it is held to
``jax.profiler.ProfileData`` on everything both can see; the reduction is
held to what it gave when the trace was taken and to figures read by hand
from it (``expected_spans.json``'s ``by_hand``)."""

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
import xplane_meta  # noqa: E402

TESTDATA = os.path.join(BENCH, "testdata")
TRACE = os.path.join(TESTDATA, "kcb_spans.xplane.pb")
YSB_TRACE = os.path.join(TESTDATA, "ysb_slice.xplane.pb")
with open(os.path.join(TESTDATA, "expected_spans.json")) as _f:
    EXPECTED = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCHMARK = json.load(_f)
NEW_METRICS = sorted(EXPECTED["metrics"])


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_context(path):
    """What ``run.py`` hands a reader, as far as the new readers look."""
    return {"trace_path": path, "slice_batches": EXPECTED["slice_batches"]}


@pytest.mark.parametrize("path", [TRACE, YSB_TRACE],
                         ids=["kcb_spans", "ysb_slice"])
def test_xplane_meta_agrees_with_profile_data(path):
    from jax.profiler import ProfileData
    mine = {p["name"]: p for p in xplane_meta.read(path)}
    n_events = 0
    for plane in ProfileData.from_file(path).planes:
        their_lines = list(plane.lines)
        my_lines = mine[plane.name]["lines"]
        assert [ln.name for ln in their_lines] == [ln["name"] for ln in my_lines]
        for line, my_line in zip(their_lines, my_lines):
            theirs = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
            got = [(e["name"], e["start_ns"], e["end_ns"] - e["start_ns"])
                   for e in my_line["events"]]
            assert len(got) == len(theirs)
            for (n1, s1, d1), (n2, s2, d2) in zip(got, theirs):
                assert n1 == n2
                assert s1 == pytest.approx(s2, abs=1.0)
                assert d1 == pytest.approx(d2, abs=1.0)
            n_events += len(got)
    assert n_events > 1000


def test_xplane_meta_reads_arguments_and_metadata():
    planes = {p["name"]: p for p in xplane_meta.read(TRACE)}
    host = [e for ln in planes["/host:CPU"]["lines"] for e in ln["events"]]
    h2d = [e for e in host if e["name"] == "wf.source.h2d"]
    assert h2d and {e["stats"]["bytes"] for e in h2d} == {
        EXPECTED["by_hand"]["h2d_bytes"]}
    ops = [e for ln in planes["/device:TPU:0"]["lines"]
           if ln["name"] == "XLA Ops" for e in ln["events"]]
    sort = [e for e in ops if e["name"].startswith(
        EXPECTED["by_hand"]["sort_hlo"] + " = ")]
    assert sort
    assert sort[0]["meta"]["tf_op"] == EXPECTED["by_hand"]["sort_tf_op"]
    assert sort[0]["meta"]["source"].endswith(
        EXPECTED["by_hand"]["sort_source"])


def test_reduction_matches_what_was_read_by_hand():
    red = span_reduce.reduce(TRACE)
    by_hand = EXPECTED["by_hand"]
    assert red["spans"]["wf.chain.push"]["count"] == by_hand["pushes"]
    assert red["spans"]["wf.chain.sync"]["count"] == by_hand["syncs"]
    assert red["spans"]["wf.chain.sync"]["ns"] == pytest.approx(
        by_hand["sync_ns"], rel=1e-6)
    assert red["spans"]["wf.source.unpack"]["threads"] == [
        by_hand["prefetch_thread"]]
    # busy time is trace_reduce's too: one union, two readers (ProfileData
    # hands out whole nanoseconds, the file holds picoseconds)
    import trace_reduce
    old = trace_reduce.reduce(TRACE, span_reduce.SLICE_NAME, ())
    assert red["busy_ns"] / 1e9 == pytest.approx(old["busy_s"], rel=1e-5)
    assert red["slice_ns"] / 1e9 == pytest.approx(old["window_s"], rel=1e-5)
    assert sum(r["ns"] for r in red["device_ops"]) == pytest.approx(
        red["busy_ns"], rel=1e-9)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_gives_what_it_gave_when_the_trace_was_taken(name):
    value = reader(name).read(run_context(TRACE))
    assert value == pytest.approx(EXPECTED["metrics"][name], rel=1e-6)


def test_every_new_metric_is_declared_with_a_reader():
    declared = {m["name"]: m for m in BENCHMARK["per_layer"]}
    for name in NEW_METRICS:
        assert declared[name]["workloads"] == ["ysb.backlog", "kcb.backlog"]
        assert declared[name]["source"] in ("program_span", "device_trace")
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH, "layer_metrics"))
               if f.endswith(".py")}
    assert readers == set(declared)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_finds_nothing_in_a_program_without_spans(name):
    """PR 24's recorded trace is of a program with no ``wf.*`` span and no
    operator scope, as a parent commit is: every reader returns None."""
    assert reader(name).read(run_context(YSB_TRACE)) is None


def test_every_heavy_operation_has_a_phase_and_a_source_line():
    red = span_reduce.reduce(TRACE)
    heavy = [r for r in red["device_ops"] if r["ns"] > 0.01 * red["busy_ns"]]
    assert len(heavy) >= 8
    for r in heavy:
        assert r["operator"] == EXPECTED["by_hand"]["window_operator"], r["hlo"]
        assert r["phase"] in span_reduce.PHASES, r["hlo"]
        assert r["source"] and ".py:" in r["source"], r["hlo"]


def test_no_trace_no_number():
    assert span_reduce.for_run({"trace_path": None, "slice_batches": 4}) is None
    assert reader("chain_push_ms").read({"slice_batches": 4}) is None
