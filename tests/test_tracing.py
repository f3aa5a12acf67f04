"""Per-batch causal tracing: deterministic trace ids across every driver and
under supervised restart, flight-recorder mechanics, histogram exemplars, the
Chrome trace-event export schema (wf_trace.py end-to-end), the critical-path
report's restart/shed attribution on a chaos run, the WF108 validator check,
the buffered EventJournal mode, and xprof_trace session hardening."""

import json
import os
import subprocess
import sys
import threading

import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.batch import trace_meta
from windflow_tpu.observability import (EventJournal, LogHistogram,
                                        TraceConfig, Tracer, read_journal)
from windflow_tpu.observability import tracing
from windflow_tpu.runtime.faults import FaultPlan, FaultSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOTAL, BATCH = 256, 32


def _source():
    return wf.Source(lambda i: {"v": i.astype(jnp.int32)}, total=TOTAL,
                     name="gen")


def _ops():
    return [wf.Map(lambda t: {"v": t.v * 2}, name="dbl")]


def _cfg(tmp_path, sub, **kw):
    kw.setdefault("run_id", "t")
    return TraceConfig(out_dir=str(tmp_path / sub), **kw)


def _ingest_ids(trace_dir):
    recs, meta = tracing.load_flight(str(trace_dir))
    return [r["tid"] for r in recs if r["kind"] == "ingest"], recs, meta


def _assert_no_orphan_begins(recs):
    open_b = {}
    for r in recs:
        k = (r["tid"], r["stage"])
        if r["kind"] == "begin":
            open_b[k] = open_b.get(k, 0) + 1
        elif r["kind"] == "end":
            open_b[k] = open_b.get(k, 0) - 1
    orphans = {k: v for k, v in open_b.items() if v}
    assert not orphans, orphans


# ------------------------------------------------------------ id minting

def test_mint_trace_id_pure_and_decodable():
    a = tracing.mint_trace_id("run", 0, 7)
    assert a == tracing.mint_trace_id("run", 0, 7)      # pure
    assert tracing.trace_pos(a) == 7
    assert a != tracing.mint_trace_id("run", 1, 7)      # stream-namespaced
    assert a != tracing.mint_trace_id("other", 0, 7)    # run-namespaced


def test_trace_config_resolve_conventions(monkeypatch):
    assert TraceConfig.resolve(False) is None
    monkeypatch.delenv("WF_TRACE", raising=False)
    assert TraceConfig.resolve(None) is None            # off by default
    monkeypatch.setenv("WF_TRACE", "0")
    assert TraceConfig.resolve(None) is None
    monkeypatch.setenv("WF_TRACE", "1")
    assert TraceConfig.resolve(None).out_dir == "wf_trace"
    monkeypatch.setenv("WF_TRACE", "/tmp/x")
    assert TraceConfig.resolve(None).out_dir == "/tmp/x"
    monkeypatch.setenv("WF_TRACE_SAMPLE", "16")
    assert TraceConfig.resolve(True).sample_every == 16
    with pytest.raises(ValueError):
        TraceConfig(sample_every=0)
    with pytest.raises(ValueError):
        TraceConfig(ids="wall-clock")


# --------------------------------------------- determinism across drivers

def test_trace_ids_identical_across_drivers(tmp_path):
    """The SAME workload under Pipeline / ThreadedPipeline / PipeGraph (push
    and threaded) mints byte-identical ingest id sequences."""
    wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: None), batch_size=BATCH,
                trace=_cfg(tmp_path, "p")).run()
    ids_p, recs_p, _ = _ingest_ids(tmp_path / "p")

    wf.ThreadedPipeline(_source(), [_ops()], wf.Sink(lambda v: None),
                        batch_size=BATCH, pin=False,
                        trace=_cfg(tmp_path, "tp")).run()
    ids_t, recs_t, _ = _ingest_ids(tmp_path / "tp")

    g = wf.PipeGraph("g", batch_size=BATCH, trace=_cfg(tmp_path, "g"))
    g.add_source(_source()).add(_ops()[0]).add_sink(wf.Sink(lambda v: None))
    g.run()
    ids_g, _, _ = _ingest_ids(tmp_path / "g")

    g2 = wf.PipeGraph("g2", batch_size=BATCH, trace=_cfg(tmp_path, "gt"))
    g2.add_source(_source()).add(_ops()[0]).add_sink(wf.Sink(lambda v: None))
    g2.run(threaded=True)
    ids_gt, _, _ = _ingest_ids(tmp_path / "gt")

    assert len(ids_p) == TOTAL // BATCH
    assert ids_p == ids_t == ids_g == ids_gt
    _assert_no_orphan_begins(recs_p)
    _assert_no_orphan_begins(recs_t)
    # the threaded driver records the full causal chain: ring enqueue/
    # dequeue around every hop
    kinds = {r["kind"] for r in recs_t}
    assert {"ingest", "enq", "deq", "begin", "end"} <= kinds


def test_trace_ids_stable_under_supervised_restart(tmp_path):
    """A FaultPlan restart replays positions — the replayed batches re-mint
    the SAME ids (dedup == fault-free sequence), no orphan begin-spans
    survive recovery, and every service-histogram exemplar is a minted id."""
    wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: None), batch_size=BATCH,
                trace=_cfg(tmp_path, "ref")).run()
    ids_ref, _, _ = _ingest_ids(tmp_path / "ref")

    plan = FaultPlan(seed=7, faults=[FaultSpec(site="chain.step",
                                               kind="error", at=[4])])
    sp = wf.SupervisedPipeline(_source(), _ops(), wf.Sink(lambda v: None),
                               batch_size=BATCH, checkpoint_every=2,
                               faults=plan, trace=_cfg(tmp_path, "sup"))
    sp.run()
    assert sp.restarts >= 1
    ids_sup, recs, meta = _ingest_ids(tmp_path / "sup")
    assert len(ids_sup) > len(ids_ref)          # replay re-ingested batches
    dedup = list(dict.fromkeys(ids_sup))
    assert dedup == ids_ref
    _assert_no_orphan_begins(recs)
    minted = set(ids_sup)
    for op in sp.chain.ops:
        for rec in op.get_StatsRecords():
            for ex in rec.service_hist.exemplars.values():
                assert ex in minted             # exemplar ids stable


def test_supervised_rejects_sequence_ids(tmp_path):
    sp = wf.SupervisedPipeline(_source(), _ops(), batch_size=BATCH,
                               trace=_cfg(tmp_path, "seq", ids="sequence"))
    with pytest.raises(ValueError, match="position"):
        sp.run()


def test_sampling_is_positional(tmp_path):
    wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: None), batch_size=BATCH,
                trace=_cfg(tmp_path, "s", sample_every=4)).run()
    _, recs, meta = _ingest_ids(tmp_path / "s")
    poss = [r["pos"] for r in recs if r["kind"] == "ingest"]
    assert poss == [0, 4]
    assert meta["minted"] == 2
    # untraced batches leave NO records at all
    assert {tracing.trace_pos(r["tid"]) for r in recs
            if r["tid"]} == {0, 4}


def test_tracing_off_leaves_no_state(tmp_path):
    """Off (the default): no active tracer, no sidecar attr on batches, no
    output files — the hot path is today's exact code."""
    out = []
    wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: out.append(v)),
                batch_size=BATCH).run()
    assert tracing.get_active() is None
    assert not (tmp_path / "wf_trace").exists()
    b = next(iter(_source().batches(BATCH)))
    assert trace_meta(b) is None
    assert tracing.tid_of(b) is None


def test_results_identical_with_tracing_on(tmp_path):
    import numpy as np
    ref, traced = [], []
    wf.Pipeline(_source(), _ops(),
                wf.Sink(lambda v: ref.append(v)), batch_size=BATCH).run()
    wf.Pipeline(_source(), _ops(),
                wf.Sink(lambda v: traced.append(v)), batch_size=BATCH,
                trace=_cfg(tmp_path, "same")).run()
    assert len(ref) == len(traced)
    for a, b in zip(ref, traced):
        if a is None or b is None:
            assert a is b
            continue
        np.testing.assert_array_equal(np.asarray(a["payload"]["v"]),
                                      np.asarray(b["payload"]["v"]))


# --------------------------------------------------------- flight recorder

def test_flight_recorder_ring_wraps_bounded():
    tr = Tracer(TraceConfig(out_dir="/tmp/unused", ring_capacity=8,
                            run_id="w"), "w")
    class B:                                  # any object takes the sidecar
        pass
    for i in range(50):
        b = B()
        tr.ingest(b, i)
    recs = tr.records()
    assert len(recs) == 8                     # bounded
    assert [r["pos"] for r in recs] == list(range(42, 50))   # newest kept
    assert tr.meta()["dropped"] == 42


def test_flight_recorder_per_thread_segments():
    tr = Tracer(TraceConfig(out_dir="/tmp/unused", run_id="mt"), "mt")
    class B:
        pass
    def work(stream):
        for i in range(20):
            b = B()
            tr.ingest(b, i, stream=stream)
    ts = [threading.Thread(target=work, args=(s,)) for s in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    recs = tr.records()
    assert len(recs) == 80
    assert len({r["thread"] for r in recs}) == 4
    assert [r["t"] for r in recs] == sorted(r["t"] for r in recs)


def test_abort_open_closes_spans_with_reason():
    tr = Tracer(TraceConfig(out_dir="/tmp/unused", run_id="a"), "a")
    class B:
        pass
    b = B()
    tr.ingest(b, 0)
    span = tr.service(b, "chain")
    assert span is not None
    assert tr.abort_open("restore") == 1
    span.done()                               # late done after abort: no-op
    recs = tr.records()
    ends = [r for r in recs if r["kind"] == "end"]
    assert len(ends) == 1 and ends[0]["aborted"] == "restore"
    _assert_no_orphan_begins(recs)


def test_abort_open_sweeps_dead_worker_segments():
    """A step_timeout watchdog worker that died mid-span (graph supervisor
    with a timeout runs the push in a transient thread): after the join, the
    driver-thread abort_open closes the dead thread's spans too — but never
    touches a LIVE foreign thread's open spans."""
    tr = Tracer(TraceConfig(out_dir="/tmp/unused", run_id="dw"), "dw")
    class B:
        pass
    def worker():
        b = B()
        tr.ingest(b, 0)
        tr.service(b, "pipe0")                # dies without done()
    t = threading.Thread(target=worker)
    t.start()
    t.join()
    gate = threading.Event()
    def live_worker():
        b = B()
        tr.ingest(b, 1)
        tr.service(b, "pipe1")
        gate.wait(5.0)
    lt = threading.Thread(target=live_worker)
    lt.start()
    import time as _t
    for _ in range(100):                      # wait for live span to open
        if any(s.open_spans and s.owner is lt for s in tr._segments):
            break
        _t.sleep(0.01)
    assert tr.abort_open("restore") == 1      # dead worker swept, live kept
    gate.set()
    lt.join()
    recs = tr.records()
    aborted = [r for r in recs if r.get("aborted")]
    assert len(aborted) == 1 and aborted[0]["stage"] == "pipe0"


# ------------------------------------------------------ histogram exemplars

def test_log_histogram_exemplars():
    h = LogHistogram()
    for i, s in enumerate((1e-5, 1e-5, 1e-3)):
        h.record(s, exemplar=100 + i)
    # p50 falls in the 10us bucket (last exemplar there: 101), p99 in the
    # 1ms bucket (exemplar 102)
    assert h.exemplar(50) == 101
    assert h.exemplar(99) == 102
    assert h.summary_us()["p99_exemplar"] == 102
    h2 = LogHistogram()
    h2.record(1e-4)                           # no exemplar passed
    assert h2.exemplar(99) is None
    assert "p99_exemplar" not in h2.summary_us()


def test_snapshot_p99_exemplar_names_a_minted_batch(tmp_path):
    mon = str(tmp_path / "mon")
    wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: None), batch_size=BATCH,
                monitoring=mon, trace=_cfg(tmp_path, "ex")).run()
    snap = json.load(open(os.path.join(mon, "snapshot.json")))
    ids, _, _ = _ingest_ids(tmp_path / "ex")
    ex = snap["e2e_latency_us"].get("p99_exemplar")
    assert ex is not None and ex in set(ids)


# ------------------------------------- Chrome export + wf_trace.py smoke

def _validate_chrome_trace(trace):
    assert "traceEvents" in trace and isinstance(trace["traceEvents"], list)
    stacks = {}
    last_ts = None
    for e in trace["traceEvents"]:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in e, (key, e)
        assert e["ts"] >= 0
        if last_ts is not None:
            assert e["ts"] >= last_ts         # monotonic export order
        last_ts = e["ts"]
        if e["ph"] == "B":
            stacks.setdefault((e["pid"], e["tid"]), []).append(e)
        elif e["ph"] == "E":
            assert stacks.get((e["pid"], e["tid"])), \
                f"E without B on track {e}"
            stacks[(e["pid"], e["tid"])].pop()
    dangling = {k: v for k, v in stacks.items() if v}
    assert not dangling, f"unmatched B events: {dangling}"


def test_wf_trace_cli_end_to_end(tmp_path):
    """Tier-1 smoke: run a small traced+monitored graph, then drive
    scripts/wf_trace.py over the artifacts and validate the export against
    the Chrome trace-event schema (required keys, monotonic ts, matched
    B/E pairs)."""
    mon = str(tmp_path / "mon")
    td = tmp_path / "tr"
    g = wf.PipeGraph("smoke", batch_size=BATCH, monitoring=mon,
                     trace=_cfg(tmp_path, "tr"))
    g.add_source(_source()).add(_ops()[0]).add_sink(wf.Sink(lambda v: None))
    g.run(threaded=True)
    out = tmp_path / "trace.json"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "wf_trace.py"),
         "--trace-dir", str(td), "--monitoring-dir", mon,
         "--out", str(out), "--report"],
        capture_output=True, text=True, cwd=str(tmp_path))
    assert r.returncode == 0, r.stderr
    assert "wrote" in r.stdout and "windflow trace report" in r.stdout
    _validate_chrome_trace(json.load(open(out)))


def test_wf_trace_cli_missing_inputs_exit_2(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "wf_trace.py"),
         "--trace-dir", str(tmp_path / "nope")],
        capture_output=True, text=True)
    assert r.returncode == 2
    assert "cannot load flight recorder" in r.stderr


@pytest.mark.chaos
def test_report_attributes_restart_and_shed(tmp_path):
    """Acceptance: a supervised chaos run (one injected restart + admission
    shedding) — the report attributes the affected batches to restart/shed
    phases and its p99 exemplar matches the snapshot histogram bucket."""
    mon = str(tmp_path / "mon")
    g = wf.PipeGraph("chaos", batch_size=BATCH, monitoring=mon,
                     trace=_cfg(tmp_path, "tr"),
                     control=dict(autotune=False, backpressure=False,
                                  admission=True, refill_per_batch=24.0,
                                  burst_tuples=40.0))
    g.add_source(_source()).add(_ops()[0]).add_sink(wf.Sink(lambda v: None))
    plan = FaultPlan(seed=3, faults=[FaultSpec(site="chain.step",
                                               kind="error", at=[3])])
    g.run_supervised(checkpoint_every=4, faults=plan)
    assert g.supervised_restarts >= 1

    recs, meta = tracing.load_flight(str(tmp_path / "tr"))
    events = read_journal(os.path.join(mon, "events.jsonl"))
    snap = json.load(open(os.path.join(mon, "snapshot.json")))
    rep = tracing.critical_path_report(recs, events, snap, meta)
    assert "RESTART-AFFECTED" in rep
    assert "restart/restore" in rep
    # the deterministic position bucket shed batches; the journal names them
    shed = sorted(e["pos"] for e in events if e["event"] == "shed")
    assert shed and f"shed" in rep
    for p in shed:
        assert str(p) in rep
    # p99 exemplar line present and consistent with the snapshot
    ex = snap["e2e_latency_us"].get("p99_exemplar")
    assert ex is not None
    assert f"{int(ex):#x}" in rep
    _assert_no_orphan_begins(recs)
    # journal shed events carry the shed positions; the trace ids decode
    # back to positions, closing the loop
    ids, _, _ = _ingest_ids(tmp_path / "tr")
    assert set(shed) <= {tracing.trace_pos(t) for t in ids}


# ---------------------------------------------------------- WF108 validator

def test_validator_wf108_sequence_ids_under_supervision(tmp_path):
    from windflow_tpu.analysis import validate
    sp = wf.SupervisedPipeline(_source(), _ops(), batch_size=BATCH,
                               trace=TraceConfig(ids="sequence"))
    rep = validate(sp)
    assert "WF108" in rep.codes()
    assert any("sequence" in d.message for d in rep.errors)
    # position ids (the default) are clean
    sp2 = wf.SupervisedPipeline(_source(), _ops(), batch_size=BATCH,
                                trace=TraceConfig())
    assert "WF108" not in validate(sp2).codes()
    # live drivers may use sequence ids
    p = wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: None),
                    batch_size=BATCH, trace=TraceConfig(ids="sequence"))
    assert "WF108" not in validate(p).codes()
    # explicit trace= override wins over the stored argument
    assert "WF108" in validate(p, supervised=True,
                               trace=TraceConfig(ids="sequence")).codes()


def test_validator_wf108_bad_env_sample(monkeypatch):
    from windflow_tpu.analysis import validate
    monkeypatch.setenv("WF_TRACE", "1")
    monkeypatch.setenv("WF_TRACE_SAMPLE", "zero")
    p = wf.Pipeline(_source(), _ops(), wf.Sink(lambda v: None),
                    batch_size=BATCH)
    rep = validate(p)
    assert "WF108" in rep.codes()


# ------------------------------------------------- EventJournal flush modes

def test_journal_buffered_mode_flushes_on_interval_and_close(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = EventJournal(path, flush_interval=10)
    for i in range(4):
        j.event("launch", push=i)
    # buffered: nothing hit the disk yet (4 < 10, no error events)
    assert os.path.getsize(path) == 0
    j.close()                                 # close always flushes
    assert len(read_journal(path)) == 4

    path2 = str(tmp_path / "j2.jsonl")
    j2 = EventJournal(path2, flush_interval=3)
    for i in range(3):
        j2.event("launch", push=i)
    assert len(read_journal(path2)) == 3      # interval crossed -> flushed
    j2.close()


def test_journal_buffered_mode_flushes_errors_immediately(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = EventJournal(path, flush_interval=1000)
    j.event("launch", push=0)
    assert os.path.getsize(path) == 0
    j.event("restart_exhausted", error="Boom")
    # an error-carrying record flushes the buffered tail immediately
    assert len(read_journal(path)) == 2
    j.close()


def test_journal_default_stays_per_event(tmp_path):
    path = str(tmp_path / "j.jsonl")
    j = EventJournal(path)
    j.event("launch", push=0)
    assert len(read_journal(path)) == 1       # visible without close
    j.close()


# ------------------------------------------------- xprof session hardening

def test_xprof_trace_nested_session_clear_error(tmp_path, monkeypatch):
    import windflow_tpu.stats as stats
    calls = []
    # (xprof_trace passes ProfileOptions: the Python tracer is off)
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d, profiler_options: calls.append(("start", d)))
    monkeypatch.setattr("jax.profiler.stop_trace",
                        lambda: calls.append(("stop",)))
    with stats.xprof_trace(str(tmp_path / "a")):
        with pytest.raises(RuntimeError, match="already active"):
            with stats.xprof_trace(str(tmp_path / "b")):
                pass
    # the guard cleared: a fresh session opens fine afterwards
    with stats.xprof_trace(str(tmp_path / "c")):
        pass
    assert calls == [("start", str(tmp_path / "a")), ("stop",),
                     ("start", str(tmp_path / "c")), ("stop",)]


def test_xprof_trace_external_session_chained_error(tmp_path, monkeypatch):
    import windflow_tpu.stats as stats

    def boom(d, profiler_options):
        raise RuntimeError("Only one profile may be run at a time.")
    monkeypatch.setattr("jax.profiler.start_trace", boom)
    with pytest.raises(RuntimeError, match="another profiler session") as ei:
        with stats.xprof_trace(str(tmp_path / "x")):
            pass
    assert isinstance(ei.value.__cause__, RuntimeError)
    # the guard did not latch: a later (now-working) session is allowed
    monkeypatch.setattr("jax.profiler.start_trace",
                        lambda d, profiler_options: None)
    monkeypatch.setattr("jax.profiler.stop_trace", lambda: None)
    with stats.xprof_trace(str(tmp_path / "y")):
        pass
