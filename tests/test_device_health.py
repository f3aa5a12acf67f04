"""Runtime health layer (PR 11): HBM memory ledger, compile/retrace
telemetry, device-time attribution, fleet snapshot federation — plus the
off-path hermeticity contract (health off = byte-for-byte today's compiled
programs and results across all four drivers) and the wf_health.py CLI
exit/shape pins."""

import importlib.util
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.nexmark import make_query
from windflow_tpu.observability import (EventJournal, MetricsRegistry,
                                        MonitoringConfig,
                                        device_health as dh,
                                        read_journal, set_journal)
from windflow_tpu.runtime.pipeline import CompiledChain

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOTAL = 300
I32 = jnp.int32


@pytest.fixture(autouse=True)
def _clean_ledger():
    """No test may leak an active ledger/journal into the next."""
    yield
    dh.set_active(None)
    set_journal(None)


def _cfg(tmp_path, sub="mon", **kw):
    kw.setdefault("health", True)
    kw.setdefault("interval_s", 30.0)
    return MonitoringConfig(out_dir=str(tmp_path / sub), **kw)


def _snapshot(tmp_path, sub="mon"):
    with open(tmp_path / sub / "snapshot.json") as f:
        return json.load(f)


def run_q3(driver="plain", monitoring=False, **kw):
    """The Nexmark enrich-join (q3) through one of the four drivers,
    returning the sink rows — the acceptance workload of this layer."""
    src, ops = make_query("q3_enrich_join", TOTAL)
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
    elif driver == "graph":
        g = wf.PipeGraph(batch_size=64, monitoring=monitoring)
        mp = g.add_source(src)
        for op in ops:
            mp.add(op)
        mp.add_sink(sink)
        g.run()
    elif driver == "graph-threaded":
        g = wf.PipeGraph(batch_size=64, monitoring=monitoring)
        mp = g.add_source(src)
        for op in ops:
            mp.add(op)
        mp.add_sink(sink)
        g.run(threaded=True)
    elif driver == "graph-supervised":
        g = wf.PipeGraph(batch_size=64, monitoring=monitoring)
        mp = g.add_source(src)
        for op in ops:
            mp.add(op)
        mp.add_sink(sink)
        g.run_supervised(checkpoint_every=2, backoff_base=0.001,
                         backoff_cap=0.01)
    return rows


def _small_chain(batch=64):
    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=512,
                    num_keys=4)
    chain = CompiledChain([wf.Map(lambda t: {"v": t.v * 2})],
                          src.payload_spec(), batch_capacity=batch)
    return src, chain


# ------------------------------------------------------- registry lockstep


def test_health_gauges_registry_lockstep():
    from windflow_tpu.observability.metrics import _HEALTH_HELP
    from windflow_tpu.observability.names import HEALTH_GAUGES
    assert set(_HEALTH_HELP) == set(HEALTH_GAUGES)


# --------------------------------------------------------- snapshot shape


def test_health_off_no_section(tmp_path):
    run_q3(monitoring=_cfg(tmp_path, health=False))
    snap = _snapshot(tmp_path)
    assert "health" not in snap


def test_health_snapshot_journal_prometheus(tmp_path):
    """THE acceptance shape: a Nexmark join run's snapshot carries HBM
    devices + per-op state footprints, the journal records every compile
    with cause/key/duration/cost, and the Prometheus exposition renders
    the health gauges with HELP/TYPE."""
    run_q3(monitoring=_cfg(tmp_path))
    snap = _snapshot(tmp_path)
    h = snap["health"]
    assert h["devices"] and h["devices"][0]["device"].startswith("cpu")
    assert h["live_buffer_count"] > 0
    # the stateful join table shows up with a real footprint
    sb = h["state_bytes"]
    assert any(b > 0 for b in sb.values()), sb
    assert h["compile"]["compiles"] >= 1
    assert h["compile"]["retraces_unexpected"] == 0
    assert "chain" in h["device_time"]
    assert h["device_time"]["chain"]["samples"] >= 1
    ev = read_journal(str(tmp_path / "mon" / "events.jsonl"))
    comps = [e for e in ev if e["event"] == "compile"]
    assert len(comps) == h["compile"]["compiles"]
    for e in comps:
        assert e["cause"] in ("push", "warm", "autotune_prewarm")
        assert e["kind"] == "step"
        assert e["cache_key"] and e["compile_s"] > 0
        # AOT cost columns land on the CPU backend
        assert e["flops"] >= 0 and e["bytes_accessed"] > 0
        assert e["argument_bytes"] > 0
    assert h["executables"]                 # footprints folded in
    prom = open(tmp_path / "mon" / "metrics.prom").read()
    assert "# TYPE windflow_health_compiles gauge" in prom
    assert "windflow_health_state_bytes{" in prom
    assert "windflow_health_device_ms{" in prom
    # topology export carries the memory ledger annotations (pipeline
    # exports "stages"; a PipeGraph would export "nodes" with op lists)
    topo = json.load(open(tmp_path / "mon" / "topology.json"))
    assert "health" in topo
    assert any("state_bytes" in st for st in topo["stages"])


# ----------------------------------------------- compile/retrace ledger


def test_retrace_counters_and_detector(tmp_path):
    led = dh.HealthLedger(cost_analysis=False)
    dh.set_active(led)
    j = EventJournal(str(tmp_path / "events.jsonl"))
    set_journal(j)
    src, chain = _small_chain()
    b = next(iter(src.batches(64)))
    chain.push(b)
    assert (led.traces, led.retraces, led.retraces_unexpected) == (1, 0, 0)
    # forced re-trace via capacity change: the retrace counter fires
    chain.warm(128)
    assert (led.traces, led.retraces, led.retraces_unexpected) == (2, 1, 0)
    # a warm executable silently recompiled (cache cleared): UNEXPECTED
    chain._steps[0].clear_cache()
    chain.push(b)
    assert led.retraces_unexpected == 1
    j.close()
    ev = read_journal(str(tmp_path / "events.jsonl"))
    kinds = [(e["event"], e.get("cause"), e.get("retrace"),
              e.get("unexpected")) for e in ev
             if e["event"] in ("compile", "retrace_unexpected")]
    assert ("retrace_unexpected", "push", False, True) in kinds
    causes = [e["cause"] for e in ev if e["event"] == "compile"]
    assert causes == ["push", "warm", "push"]
    # same cache key for the unexpected retrace as the original compile
    comp_keys = [e["cache_key"] for e in ev if e["event"] == "compile"]
    assert comp_keys[0] == comp_keys[2]


def test_autotune_prewarm_cause_overrides():
    led = dh.HealthLedger(cost_analysis=False)
    dh.set_active(led)
    _src, chain = _small_chain()
    with dh.cause("autotune_prewarm"):
        chain.warm(64)
    pend = []  # committed already by warm; check via the compile log
    sec = led.snapshot_section()
    assert sec["compile_log"][-1]["cause"] == "autotune_prewarm"
    assert not pend


def test_supervised_restore_clears_pending():
    led = dh.HealthLedger(cost_analysis=False)
    dh.set_active(led)
    led.note_trace("chain", 0, "step", "sig-abandoned")
    dh.clear_pending()
    led.commit_pending(1.0)         # nothing left to charge
    assert led.snapshot_section()["compile_log"] == []
    # the counters still saw the trace (it DID happen)
    assert led.traces == 1


def test_kernel_resolve_journaled(tmp_path):
    led = dh.HealthLedger(cost_analysis=False)
    dh.set_active(led)
    j = EventJournal(str(tmp_path / "events.jsonl"))
    set_journal(j)
    from windflow_tpu.ops import registry
    impl = registry.resolve_impl("lookup", spec_key="health-test")
    j.close()
    ev = read_journal(str(tmp_path / "events.jsonl"))
    res = [e for e in ev if e["event"] == "kernel_resolve"]
    assert res and res[0]["kernel"] == "lookup" and res[0]["impl"] == impl
    assert led.kernel_resolves == 1


# ------------------------------------------------ device-time attribution


def test_service_sampling_and_dispatch_bound():
    led = dh.HealthLedger(sample_every=2)
    # every Nth sampled point records: 1st no, 2nd yes, 3rd no, 4th yes
    assert [led.service_sample() for _ in range(4)] == [False, True,
                                                       False, True]
    led.note_service("pipe0", dispatch_s=0.004, device_s=0.005)
    led.note_service("pipe1", dispatch_s=0.001, device_s=0.020)
    sec = led.snapshot_section()
    assert sec["device_time"]["pipe0"]["dispatch_ratio"] == 0.8
    assert "pipe0" in sec["dispatch_bound"]          # >= 0.5: candidate
    assert "pipe1" not in sec["dispatch_bound"]      # 0.05: device-bound


def test_trace_report_renders_dispatch_bound():
    from windflow_tpu.observability.tracing import critical_path_report
    snap = {"health": {
        "device_time": {"pipe0": {"device_ms": 5.0, "dispatch_ms": 4.0,
                                  "samples": 3, "dispatch_ratio": 0.8}},
        "dispatch_bound": {"pipe0": 0.8},
        "compile": {"compiles": 2, "retraces": 1, "retraces_unexpected": 0,
                    "compile_s_total": 0.5},
    }}
    out = critical_path_report([], [], snap, None)
    assert "DISPATCH-BOUND" in out and "pipe0" in out
    assert "compile ledger: 2 compiles" in out


# ------------------------------------------------------- state footprints


def test_state_footprints_match_shapes():
    src, ops = make_query("q3_enrich_join", TOTAL)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=64)
    fp = chain.state_footprints()
    for op, st in zip(chain.ops, chain.states):
        want = sum(
            int(np.prod(getattr(leaf, "shape", ()))
                * jnp.dtype(getattr(leaf, "dtype", "float32")).itemsize)
            for leaf in jax.tree.leaves(st))
        assert fp[op.getName()] == want
    assert sum(fp.values()) > 0


# -------------------------------------------------- off-path hermeticity


def test_ledger_observes_trace_off_path():
    """The ledger hooks are trace-time host side effects: lowering with the
    ledger active must be OBSERVED by it (traces recorded) while leaving
    the device program untouched.  Program identity itself is pinned by the
    shared toggle-OFF fingerprint gate (test_program_fingerprint.py); this
    keeps only the observes-the-trace half, which that gate cannot see."""
    src, chain = _small_chain()
    b = next(iter(src.batches(64)))
    led = dh.HealthLedger(cost_analysis=False)
    dh.set_active(led)
    chain._step_fn(0).lower(tuple(chain.states), b).as_text()
    dh.set_active(None)
    assert led.traces >= 1            # the hook DID observe the trace


@pytest.mark.parametrize("driver", ["plain", "graph", "graph-threaded",
                                    "graph-supervised"])
def test_health_on_results_byte_identical(tmp_path, driver, monkeypatch):
    """Mirror of PR 9's off-path pin: WF_MONITORING_HEALTH on must not
    change a single result byte through any of the four drivers."""
    base = run_q3(driver)
    monkeypatch.setenv("WF_MONITORING_HEALTH", "1")
    on = run_q3(driver, monitoring=_cfg(tmp_path, sub=f"m-{driver}"))
    assert on == base


def test_perfgate_builders_hermetic_under_env(monkeypatch):
    """The hermetic gate's chains must not consult the health env — pins
    byte-identical whatever the caller's environment says."""
    monkeypatch.setenv("WF_MONITORING", "1")
    monkeypatch.setenv("WF_MONITORING_HEALTH", "1")
    from windflow_tpu.analysis.perfgate import _build_mp_matrix
    chain = _build_mp_matrix()[0]
    # no ledger was activated (Monitor never ran), so nothing was recorded
    assert dh.get_active() is None
    assert not chain.event_time


# ---------------------------------------------------------- WF113 checks


def test_wf113_health_without_monitoring(monkeypatch):
    src, chain = _small_chain()
    p = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v})],
                    wf.Sink(lambda v: None), batch_size=64)
    from windflow_tpu.analysis import validate
    monkeypatch.setenv("WF_MONITORING_HEALTH", "1")
    r = validate(p)
    assert "WF113" in r.codes() and r.errors
    monkeypatch.setenv("WF_MONITORING", "1")
    r = validate(p)
    assert "WF113" not in r.codes()
    monkeypatch.setenv("WF_HEALTH_SAMPLE", "0")
    r = validate(p)
    assert "WF113" in r.codes()
    monkeypatch.setenv("WF_HEALTH_SAMPLE", "abc")
    r = validate(p)
    assert "WF113" in r.codes()
    monkeypatch.setenv("WF_HEALTH_SAMPLE", "4")
    r = validate(p)
    assert "WF113" not in r.codes()


# ----------------------------------------- reporter atomicity (satellite)


def test_reporter_never_serves_torn_files(tmp_path):
    """A reader polling snapshot.json / metrics.prom while the reporter
    rewrites them every 50 ms must never observe a torn (unparseable or
    empty) file — the tmp+fsync+os.replace contract."""
    from windflow_tpu.observability.reporter import Reporter
    reg = MetricsRegistry("torn-test", health=True)
    src, chain = _small_chain()
    reg.register_chain("chain", chain)
    rep = Reporter(reg, str(tmp_path), interval_s=0.05)
    rep.start()
    try:
        deadline = time.monotonic() + 0.6
        reads = 0
        while time.monotonic() < deadline:
            sj = tmp_path / "snapshot.json"
            if sj.exists():
                text = sj.read_text()
                assert text.strip(), "torn/empty snapshot.json served"
                json.loads(text)                      # must always parse
                reads += 1
            pm = tmp_path / "metrics.prom"
            if pm.exists():
                assert pm.read_text().strip(), "torn/empty metrics.prom"
    finally:
        rep.stop()
    assert reads > 0 and rep.ticks >= 2
    assert not list(tmp_path.glob("*.tmp*")), "tmp debris left behind"


def test_loader_tolerates_torn_jsonl(tmp_path):
    good = {"graph": "g", "operators": [], "totals": {}}
    with open(tmp_path / "snapshots.jsonl", "w") as f:
        f.write(json.dumps(good) + "\n")
        f.write('{"graph": "g", "oper')          # torn mid-append
    latest, series = dh.load_snapshots(str(tmp_path))
    assert latest == good and len(series) == 1
    with open(tmp_path / "events.jsonl", "w") as f:
        f.write(json.dumps({"event": "eos"}) + "\n")
        f.write('{"event": "comp')
    assert dh.load_journal(str(tmp_path)) == [{"event": "eos"}]


# ------------------------------------------------------- fleet federation


def _host_snap(wm, occ, compiles, tuples):
    return {
        "graph": "g", "wall_time": 1.0, "uptime_s": 2.0,
        "operators": [{"name": "join", "inputs_received": tuples,
                       "counters": {"overflow_drops": 1},
                       "service_time_us": {"p99": 100.0 * compiles,
                                           "samples": 4},
                       "event_time": {"watermark_ts": wm,
                                      "occupancy_pct": occ}}],
        "totals": {"inputs_received": tuples},
        "queues": {"src->0": occ},
        "recovery": {"restarts": 1},
        "control": {"counters": {"shed_batches": 2}},
        "e2e_latency_us": {"p99": 50.0, "samples": 3},
        "event_time": {"min_watermark_ts": wm,
                       "frontier_operator": "join",
                       "edge_skew_ts": {"0->1": wm}},
        "health": {
            "devices": [{"device": "tpu:0", "kind": "v5e",
                         "bytes_in_use": 10, "bytes_limit": 100,
                         "headroom_bytes": 90}],
            "state_bytes": {"join": 1000},
            "compile": {"compiles": compiles, "retraces": 0,
                        "retraces_unexpected": 0, "compile_s_total": 0.1},
            "device_time": {"pipe0": {"device_ms": 10.0, "dispatch_ms": 8.0,
                                      "samples": 2}},
        },
    }


def test_merge_snapshots_fleet_semantics():
    a, b = _host_snap(10, 40, 3, 100), _host_snap(7, 90, 2, 50)
    m = dh.merge_snapshots([a, b], hosts=["h0", "h1"])
    assert m["merged_from"] == 2
    assert [h["host"] for h in m["hosts"]] == ["h0", "h1"]
    # counters summed
    assert m["totals"]["inputs_received"] == 150
    op = m["operators"][0]
    assert op["inputs_received"] == 150
    assert op["counters"]["overflow_drops"] == 2
    # watermark frontier = MIN (slowest host), pressure = MAX (worst host)
    assert m["event_time"]["min_watermark_ts"] == 7
    assert m["event_time"]["frontier_host"] == "h1"
    assert op["event_time"]["watermark_ts"] == 7
    assert op["event_time"]["occupancy_pct"] == 90
    assert m["queues"]["src->0"] == 90
    # percentiles: worst host + summed samples
    assert op["service_time_us"]["p99"] == 300.0
    assert op["service_time_us"]["samples"] == 8
    # health: devices host-tagged, counters summed, ratio recomputed
    h = m["health"]
    assert {d["device"] for d in h["devices"]} == {"h0/tpu:0", "h1/tpu:0"}
    assert h["compile"]["compiles"] == 5
    assert h["state_bytes"]["join"] == 2000
    assert h["device_time"]["pipe0"]["samples"] == 4
    assert h["device_time"]["pipe0"]["dispatch_ratio"] == 0.8
    assert "pipe0" in h["dispatch_bound"]
    assert m["recovery"]["restarts"] == 2
    assert m["control"]["counters"]["shed_batches"] == 4


def test_merge_tolerates_partial_host():
    """A host whose snapshot is missing whole sections (torn mid-upgrade,
    or a seed-era emitter) still folds — the merge never KeyErrors, it
    just contributes nothing to the sections it lacks."""
    full = _host_snap(10, 40, 3, 100)
    partial = {"graph": "g", "operators": [
        {"name": "join", "inputs_received": 7}]}
    m = dh.merge_snapshots([full, partial], hosts=["h0", "h1"])
    assert m["merged_from"] == 2
    assert m["totals"]["inputs_received"] == 100      # full host only
    assert m["operators"][0]["inputs_received"] == 107
    assert m["queues"]["src->0"] == 40
    assert m["event_time"]["frontier_host"] == "h0"
    assert len(m["health"]["devices"]) == 1
    # and in the other order (partial host first sets the fold's seed)
    m2 = dh.merge_snapshots([partial, full], hosts=["h1", "h0"])
    assert m2["operators"][0]["inputs_received"] == 107


def test_merge_duplicate_host_tags_disambiguated():
    """Two --merge dirs with the same basename must not fold into one
    host's rows — duplicate tags get a #N suffix so host-tagged sections
    (devices, hosts) keep every host's data."""
    snaps = [_host_snap(10, 40, 1, 10), _host_snap(9, 50, 1, 20),
             _host_snap(8, 60, 1, 30)]
    m = dh.merge_snapshots(snaps, hosts=["mon", "mon", "mon"])
    assert [h["host"] for h in m["hosts"]] == ["mon", "mon#2", "mon#3"]
    assert {d["device"] for d in m["health"]["devices"]} == {
        "mon/tpu:0", "mon#2/tpu:0", "mon#3/tpu:0"}
    assert m["totals"]["inputs_received"] == 60


def test_merge_seed_era_schema_reads_as_zero():
    """Seed-era snapshots carry no schema field: they fold as version 0,
    and mixing them with stamped hosts flags — never silently folds —
    the disagreement."""
    old, new = _host_snap(1, 1, 1, 1), _host_snap(1, 1, 1, 1)
    new["schema"] = dh.SNAPSHOT_SCHEMA
    m = dh.merge_snapshots([old, new], hosts=["h0", "h1"])
    assert m["schema"] == dh.SNAPSHOT_SCHEMA
    assert m["schema_mismatch"] == {"h0": 0, "h1": dh.SNAPSHOT_SCHEMA}
    # an all-seed-era fleet agrees with itself: version 0, no flag
    m0 = dh.merge_snapshots([_host_snap(1, 1, 1, 1)] * 2,
                            hosts=["h0", "h1"])
    assert m0["schema"] == 0 and "schema_mismatch" not in m0


def test_merge_monitoring_dirs_torn_host(tmp_path):
    """A host dir whose snapshots.jsonl was torn mid-append (the host
    died writing) still merges: the torn tail is dropped by the loader,
    the series aligns to the shortest host, the journal concatenates."""
    for name, ticks, torn in (("ha", 3, False), ("hb", 2, True)):
        d = tmp_path / name
        d.mkdir()
        with open(d / "snapshots.jsonl", "w") as f:
            for i in range(ticks):
                s = _host_snap(10 + i, 40, 1, 10 * (i + 1))
                s["wall_time"] = float(i)
                f.write(json.dumps(s) + "\n")
            if torn:
                f.write('{"graph": "g", "oper')       # died mid-write
        with open(d / "events.jsonl", "w") as f:
            f.write(json.dumps({"event": "eos", "wall": float(ticks)})
                    + "\n")
    merged, series, journal = dh.merge_monitoring_dirs(
        [str(tmp_path / "ha"), str(tmp_path / "hb")])
    assert merged["merged_from"] == 2
    assert [h["host"] for h in merged["hosts"]] == ["ha", "hb"]
    assert len(series) == 2                           # min(3, 2 whole lines)
    assert merged["totals"]["inputs_received"] == 30 + 20
    assert [e["event"] for e in journal] == ["eos", "eos"]


def test_headroom_risk_flags():
    devs = [{"device": "tpu:0", "headroom_bytes": 5, "bytes_limit": 100},
            {"device": "tpu:1", "headroom_bytes": 50, "bytes_limit": 100},
            {"device": "cpu:0"}]
    assert dh.headroom_risks(devs) == ["tpu:0"]


# ------------------------------------------------------------ the CLIs


def _load_cli(name):
    spec = importlib.util.spec_from_file_location(
        f"wf_cli_{name}", os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_wf_health_cli_merge_and_exit_contract(tmp_path, capsys):
    """THE acceptance loop: a health-on join run, its artifacts duplicated
    as a second 'host', merged by wf_health.py --json — ledger + merged
    provenance render; missing inputs exit 2."""
    import shutil
    run_q3(monitoring=_cfg(tmp_path, sub="h0"))
    shutil.copytree(tmp_path / "h0", tmp_path / "h1")
    cli = _load_cli("wf_health")
    rc = cli.main(["--merge", str(tmp_path / "h0"), str(tmp_path / "h1"),
                   "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    data = json.loads(out)
    assert data["merged_from"] == 2
    assert [h["host"] for h in data["hosts"]] == ["h0", "h1"]
    h = data["health"]
    assert h["compile"]["compiles"] >= 2          # summed across hosts
    assert h["state_bytes"]
    assert len(h["devices"]) == 2 * len(jax.local_devices())
    # human report renders every section
    rc = cli.main(["--merge", str(tmp_path / "h0"), str(tmp_path / "h1")])
    out = capsys.readouterr().out
    assert rc == 0
    for want in ("HBM memory ledger", "compile/retrace ledger",
                 "device-time attribution", "state footprints"):
        assert want in out
    # single-dir mode + exit contract
    rc = cli.main(["--monitoring-dir", str(tmp_path / "h0")])
    assert rc == 0
    capsys.readouterr()
    rc = cli.main(["--monitoring-dir", str(tmp_path / "nope")])
    assert rc == 2


def test_wf_state_cli_merge(tmp_path, capsys):
    import shutil
    run_q3(monitoring=_cfg(tmp_path, sub="h0", event_time=True))
    shutil.copytree(tmp_path / "h0", tmp_path / "h1")
    cli = _load_cli("wf_state")
    rc = cli.main(["--merge", str(tmp_path / "h0"), str(tmp_path / "h1"),
                   "--json"])
    out = capsys.readouterr().out
    assert rc == 0
    data = json.loads(out)
    assert data["merged_from"] == 2 and len(data["hosts"]) == 2
