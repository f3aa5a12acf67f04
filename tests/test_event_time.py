"""Event-time observability: telemetry-on byte-identity across all four
drivers (plain / threaded / supervised / graph-supervised, under FaultPlan
restarts), the watermark/occupancy/lateness
snapshot + Prometheus + topology surfaces, ``recommend_delay`` driving a
skewed stream's OLD drops to zero end-to-end through ``wf_state.py``, the
trace report's per-batch service attribution, and the ``wf_state.py`` 0/2
exit contract without JAX."""

import json
import os
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.nexmark import make_query
from windflow_tpu.observability import MonitoringConfig, event_time as et
from windflow_tpu.runtime.faults import FaultPlan, FaultSpec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WF_STATE = os.path.join(REPO, "scripts", "wf_state.py")

TOTAL = 300
I32 = jnp.int32


def run_query(name, driver="plain", monitoring=False, **kw):
    src, ops = make_query(name, TOTAL)
    rows = []

    def cb(view):
        if view is None:
            return
        rows.append((np.asarray(view["key"]).tolist(),
                     np.asarray(view["id"]).tolist(),
                     np.asarray(view["ts"]).tolist()))
    sink = wf.Sink(cb)
    if driver == "plain":
        wf.Pipeline(src, ops, sink, batch_size=64, monitoring=monitoring,
                    **kw).run()
    elif driver == "threaded":
        # ThreadedPipeline has no monitoring= kwarg: env-driven (the caller
        # monkeypatches WF_MONITORING/WF_MONITORING_EVENT_TIME)
        wf.ThreadedPipeline(src, [ops], sink, batch_size=64, **kw).run()
    elif driver == "supervised":
        wf.SupervisedPipeline(src, ops, sink, batch_size=64,
                              checkpoint_every=2, backoff_base=0.001,
                              backoff_cap=0.01, **kw).run()
    elif driver == "graph-supervised":
        g = wf.PipeGraph(batch_size=64, monitoring=monitoring)
        mp = g.add_source(src)
        for op in ops:
            mp.add(op)
        mp.add_sink(sink)
        g.run_supervised(checkpoint_every=2, backoff_base=0.001,
                         backoff_cap=0.01, **kw)
    return rows


def _cfg(tmp_path, sub="mon"):
    return MonitoringConfig(out_dir=str(tmp_path / sub), event_time=True,
                            interval_s=30.0)


def _snapshot(tmp_path, sub="mon"):
    with open(tmp_path / sub / "snapshot.json") as f:
        return json.load(f)


# ------------------------------------------------- bucket math / device unit

def test_bucket_math_host_device_agree():
    import jax
    vals = [0, 1, 2, 3, 4, 7, 8, 100, 1023, 1024, (1 << 30) + 5]
    wm = 1 << 30
    ts = jnp.asarray([wm - v for v in vals], I32)
    hist = et.lateness_update(et.lateness_init(), wm, ts,
                              jnp.ones((len(vals),), jnp.bool_))
    counts = np.asarray(jax.device_get(hist))
    want = np.zeros(et.NB, np.int64)
    for v in vals:
        want[et.bucket_of(v)] += 1
    assert counts.tolist() == want.tolist()


def test_lateness_update_respects_mask():
    hist = et.lateness_update(et.lateness_init(), 10,
                              jnp.asarray([0, 5, 10], I32),
                              jnp.asarray([False, True, False]))
    counts = np.asarray(hist)
    assert counts.sum() == 1 and counts[et.bucket_of(5)] == 1


def test_recommend_delay_quantiles():
    counts = [0] * et.NB
    counts[0] = 90                       # 90 on-time
    counts[3] = 9                        # 9 in [4, 7]
    counts[5] = 1                        # 1 in [16, 31]
    assert et.recommend_delay(counts, 0.50) == 0
    assert et.recommend_delay(counts, 0.99) == 7
    assert et.recommend_delay(counts, 1.0) == 31
    assert et.recommend_delay([0] * et.NB, 0.99) == 0
    s = et.summarize(counts)
    assert s["total"] == 100 and s["p99"] == 7 and s["max"] == 31
    assert s["recommend_delay_p99"] == 7


def test_bucket_upper_covers_bucket():
    for v in (0, 1, 2, 3, 8, 100, 12345):
        assert et.bucket_upper(et.bucket_of(v)) >= v


# ------------------------------------------ telemetry-on byte-identity

@pytest.mark.parametrize("name", ["q3_enrich_join", "q4_interval_join",
                                  "q5_session"])
def test_event_time_on_is_byte_identical_plain(name, tmp_path):
    base = run_query(name)
    assert run_query(name, monitoring=_cfg(tmp_path)) == base


def test_event_time_on_byte_identical_across_all_four_drivers(
        tmp_path, monkeypatch):
    name = "q5_session"
    base = run_query(name)
    assert run_query(name, monitoring=_cfg(tmp_path, "plain")) == base
    assert run_query(name, "graph-supervised",
                     monitoring=_cfg(tmp_path, "graph")) == base
    # threaded + supervised resolve the toggle from the env
    monkeypatch.setenv("WF_MONITORING", str(tmp_path / "env"))
    monkeypatch.setenv("WF_MONITORING_EVENT_TIME", "1")
    assert run_query(name, "threaded") == base
    assert run_query(name, "supervised") == base


@pytest.mark.chaos
@pytest.mark.parametrize("name", ["q4_interval_join", "q5_session"])
def test_event_time_on_byte_identical_under_faultplan(name, tmp_path,
                                                      monkeypatch):
    base = run_query(name)
    plan = FaultPlan([FaultSpec("chain.step", at=[3, 5])], seed=7)
    monkeypatch.setenv("WF_MONITORING", str(tmp_path / "sup"))
    monkeypatch.setenv("WF_MONITORING_EVENT_TIME", "1")
    assert run_query(name, "supervised", faults=plan) == base
    monkeypatch.delenv("WF_MONITORING")
    monkeypatch.delenv("WF_MONITORING_EVENT_TIME")
    assert run_query(name, "graph-supervised",
                     monitoring=_cfg(tmp_path, "graph"),
                     faults=plan) == base


# -------------------------------------------------- snapshot surfaces

#: stateful event-time operators per query -> section keys the snapshot
#: must carry (the watermark/occupancy/lateness acceptance surface)
_SECTION_KEYS = {
    "q3_enrich_join": {"watermark_ts", "occupancy_pct", "pending_depth",
                       "lateness"},
    "q4_interval_join": {"watermark_ts", "l_fill_pct", "r_fill_pct",
                         "evict_frontier_l_ts", "lateness"},
    "q5_session": {"watermark_ts", "open_sessions", "occupancy_pct",
                   "lateness"},
    "q6_topn": {"occupancy_pct", "topn_evictions"},
    "q7_distinct": {"watermark_ts", "occupancy_pct", "pending_depth"},
}


@pytest.mark.parametrize("name", sorted(_SECTION_KEYS))
def test_every_stateful_query_snapshot_carries_event_time_sections(
        name, tmp_path):
    run_query(name, monitoring=_cfg(tmp_path))
    snap = _snapshot(tmp_path)
    secs = {r["name"]: r["event_time"] for r in snap["operators"]
            if "event_time" in r}
    assert secs, f"{name}: no event_time sections in snapshot"
    merged = set()
    for sec in secs.values():
        merged |= set(sec)
    missing = _SECTION_KEYS[name] - merged
    assert not missing, f"{name}: missing {missing} in {merged}"
    # graph-level frontier whenever any op carries a watermark
    if any("watermark_ts" in sec for sec in secs.values()):
        assert "min_watermark_ts" in snap.get("event_time", {})


def test_stage_counters_in_rows_and_prometheus(tmp_path):
    run_query("q5_session", monitoring=_cfg(tmp_path))
    snap = _snapshot(tmp_path)
    row = [r for r in snap["operators"]
           if r["name"] == "nexmark_session"][0]
    assert row["counters"]["sessions_closed"] > 0
    with open(tmp_path / "mon" / "metrics.prom") as f:
        prom = f.read()
    assert "# HELP windflow_stage_sessions_closed_total" in prom
    assert "# TYPE windflow_stage_sessions_closed_total counter" in prom
    assert 'windflow_stage_sessions_closed_total{graph=' in prom
    assert "# TYPE windflow_event_time_watermark gauge" in prom
    assert "# HELP windflow_event_time_lateness_p99" in prom
    assert "windflow_event_time_min_watermark" in prom


def test_stage_counters_reject_unregistered_names():
    op = wf.SessionWindow(lambda t: t.key,
                          wf.WindowSpec.session(2), num_keys=4)
    with pytest.raises(ValueError, match="STAGE_COUNTERS"):
        op._publish_stage_counters({"not_a_registered_name": 1})


def test_event_time_names_registered():
    from windflow_tpu.observability.names import (
        EVENT_TIME_GAUGES, JOURNAL_EVENTS, STAGE_COUNTERS, STAGE_GAUGES)
    assert "lateness_drop" in JOURNAL_EVENTS
    for n in ("sessions_closed", "topn_evictions", "match_drops",
              "arch_drops", "overflow_drops", "old_drops"):
        assert n in STAGE_COUNTERS
    assert "join_table_version" in STAGE_GAUGES
    for n in ("watermark", "lateness_p99", "min_watermark", "skew"):
        assert n in EVENT_TIME_GAUGES


def test_off_path_state_is_unchanged():
    """event_time off must leave the state pytrees byte-for-byte today's —
    the zero-added-device-work contract the perf-gate pins enforce."""
    src, ops = make_query("q3_enrich_join", TOTAL)
    from windflow_tpu.runtime.pipeline import CompiledChain
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=64)
    assert "lat_hist" not in chain.states[0]
    src2, ops2 = make_query("q3_enrich_join", TOTAL)
    chain2 = CompiledChain(ops2, src2.payload_spec(), batch_capacity=64,
                           event_time=True)
    assert "lat_hist" in chain2.states[0]
    # the toggle must not stick to reused operator instances: rebuilding an
    # OFF chain over the same ops drops the histograms again
    chain3 = CompiledChain(ops2, src2.payload_spec(), batch_capacity=64,
                           event_time=False)
    assert "lat_hist" not in chain3.states[0]
    # and the perf-gate/bench builders stay hermetic under the env toggle
    import os
    os.environ["WF_MONITORING"], os.environ["WF_MONITORING_EVENT_TIME"] = \
        "1", "1"
    try:
        from windflow_tpu.analysis.perfgate import _build_mp_matrix
        chain4 = _build_mp_matrix()[0]
        assert not chain4.event_time
    finally:
        del os.environ["WF_MONITORING"]
        del os.environ["WF_MONITORING_EVENT_TIME"]


# --------------------------------------- graph topology: edge skew export

def test_graph_edge_skew_in_snapshot_and_topology(tmp_path):
    mon = _cfg(tmp_path)
    g = wf.PipeGraph(batch_size=32, monitoring=mon)
    mk = lambda: wf.Source(lambda i: {"side": (i % 2).astype(I32),
                                      "v": (i * 1).astype(I32)},
                           total=128, num_keys=4, ts_fn=lambda i: i // 2)
    a, b = g.add_source(mk()), g.add_source(mk())
    m = a.join_with(b, wf.IntervalJoin(lambda t: t.side == 1, 0, 4))
    m.add_sink(wf.Sink(lambda v: None))
    g.run()
    snap = _snapshot(tmp_path)
    assert "event_time" in snap
    assert "min_watermark_ts" in snap["event_time"]
    from windflow_tpu.observability import topology_dot, topology_json
    tj = topology_json(g, snap)
    skews = snap["event_time"].get("edge_skew_ts")
    if skews:      # present when both endpoint pipes carry watermarks
        assert any("watermark_skew_ts" in e for e in tj["edges"])
        assert "skew=" in topology_dot(g, snap)


# ------------------------ lateness forensics: recommend_delay -> zero drops

LAG = 5


def _skewed_source():
    """Two keys sharing one event clock, key 1 lagging LAG ticks behind —
    the cross-key skew that makes a global-time TB window drop OLD."""
    return wf.Source(lambda i: {"v": jnp.ones((), I32)}, total=256,
                     num_keys=2, key_fn=lambda i: i % 2,
                     ts_fn=lambda i: jnp.where(
                         i % 2 == 0, i // 2,
                         jnp.maximum(i // 2 - LAG, 0)))


def _run_skewed_window(delay, monitoring=False):
    spec = wf.WindowSpec(4, 4, wf.win_type_t.TB, delay)
    op = wf.Win_SeqFFAT(lambda t: 1, jnp.add, spec=spec, num_keys=2,
                        name="skewed_win")
    wf.Pipeline(_skewed_source(), [op], wf.Sink(lambda v: None),
                batch_size=32, monitoring=monitoring).run()
    return op


def test_recommend_delay_drives_old_drops_to_zero_via_wf_state(tmp_path):
    """THE acceptance loop: a skewed stream drops OLD at delay=0; the
    wf_state.py lateness report recommends a delay; applying it drives
    ``tuples_dropped_old`` to zero."""
    mon = str(tmp_path / "skew")
    op = _run_skewed_window(0, MonitoringConfig(out_dir=mon,
                                                event_time=True,
                                                interval_s=30.0))
    assert op.get_StatsRecords()[0].tuples_dropped_old > 0
    out = subprocess.run(
        [sys.executable, WF_STATE, "--monitoring-dir", mon,
         "--q", "1.0", "--json"],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    data = json.loads(out.stdout)
    rec = data["recommendations"]["skewed_win/in"]["recommend_delay"]
    assert rec >= LAG
    op2 = _run_skewed_window(rec, MonitoringConfig(
        out_dir=str(tmp_path / "skew2"), event_time=True, interval_s=30.0))
    assert op2.get_StatsRecords()[0].tuples_dropped_old == 0


def test_lateness_drop_journal_events(tmp_path):
    mon = str(tmp_path / "mon")
    _run_skewed_window(0, MonitoringConfig(out_dir=mon, event_time=True,
                                           interval_s=30.0))
    from windflow_tpu.observability import read_journal
    events = read_journal(os.path.join(mon, "events.jsonl"))
    drops = [e for e in events if e["event"] == "lateness_drop"]
    assert drops, "no lateness_drop events journaled"
    assert drops[0]["op"] == "skewed_win"
    assert drops[0]["kind"] == "old_drops"
    assert sum(e["n"] for e in drops) == drops[-1]["total"]


def test_session_lateness_section_recommends_covering_delay(tmp_path):
    run_query("q5_session", monitoring=_cfg(tmp_path))
    snap = _snapshot(tmp_path)
    sec = [r for r in snap["operators"]
           if r["name"] == "nexmark_session"][0]["event_time"]
    summ = sec["lateness"]["in"]
    assert summ["total"] > 0
    assert et.recommend_delay(summ["counts"], 1.0) >= summ["p99"]


# ------------------------------------------- wf_state.py CLI contract

def _poisoned_jax_dir(tmp_path):
    d = tmp_path / "nojax"
    d.mkdir(exist_ok=True)
    (d / "jax.py").write_text("raise ImportError('wf_state must not "
                              "import jax')\n")
    return str(d)


def test_wf_state_exit_0_and_report_without_jax(tmp_path):
    mon = str(tmp_path / "mon")
    _run_skewed_window(0, MonitoringConfig(out_dir=mon, event_time=True,
                                           interval_s=0.05))
    env = dict(os.environ, PYTHONPATH=_poisoned_jax_dir(tmp_path))
    out = subprocess.run([sys.executable, WF_STATE,
                          "--monitoring-dir", mon],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    assert "watermark propagation map" in out.stdout
    assert "state-pressure trends" in out.stdout
    assert "lateness report" in out.stdout
    assert "skewed_win" in out.stdout


def test_wf_state_exit_2_on_missing_inputs(tmp_path):
    env = dict(os.environ, PYTHONPATH=_poisoned_jax_dir(tmp_path))
    out = subprocess.run([sys.executable, WF_STATE, "--monitoring-dir",
                          str(tmp_path / "nope")],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2
    assert "cannot load snapshots" in out.stderr


def test_wf_state_exit_2_on_bad_quantile(tmp_path):
    out = subprocess.run([sys.executable, WF_STATE, "--q", "1.5"],
                         capture_output=True, text=True)
    assert out.returncode == 2


# ----------------------------------- per-batch service attribution

def test_spans_charge_each_batch_its_own_service_time():
    from windflow_tpu.observability.tracing import _batch_lifecycles
    recs = []
    # two batches through the same stage, then a second visit of the first
    recs.append({"t": 0.000, "tid": 11, "stage": "chain", "kind": "begin"})
    recs.append({"t": 0.008, "tid": 11, "stage": "chain", "kind": "end"})
    recs.append({"t": 0.020, "tid": 15, "stage": "chain", "kind": "begin"})
    recs.append({"t": 0.024, "tid": 15, "stage": "chain", "kind": "end"})
    recs.append({"t": 0.030, "tid": 11, "stage": "chain", "kind": "begin"})
    recs.append({"t": 0.031, "tid": 11, "stage": "chain", "kind": "end"})
    lives = _batch_lifecycles(recs)
    assert lives[11]["service"]["chain"] == pytest.approx(0.009, rel=1e-6)
    assert lives[11]["attempts"]["chain"] == 2
    assert lives[15]["service"]["chain"] == pytest.approx(0.004, rel=1e-6)


def test_wf_trace_report_renders_lateness_drops(tmp_path):
    from windflow_tpu.observability import TraceConfig, tracing
    mon = str(tmp_path / "mon")
    spec = wf.WindowSpec(4, 4, wf.win_type_t.TB, 0)
    op = wf.Win_SeqFFAT(lambda t: 1, jnp.add, spec=spec, num_keys=2,
                        name="skewed_win")
    wf.Pipeline(_skewed_source(), [op], wf.Sink(lambda v: None),
                batch_size=32,
                monitoring=MonitoringConfig(out_dir=mon, event_time=True,
                                            interval_s=30.0),
                trace=TraceConfig(out_dir=str(tmp_path / "tr"))).run()
    from windflow_tpu.observability import read_journal
    records, meta = tracing.load_flight(str(tmp_path / "tr"))
    events = read_journal(os.path.join(mon, "events.jsonl"))
    report = tracing.critical_path_report(records, events, None, meta)
    assert "event-time drops" in report
    assert "skewed_win" in report and "old_drops" in report
