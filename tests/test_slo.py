"""SLO engine (PR 15): burn-rate alerting, the OK->WARN->PAGE state
machine, automatic incident forensic bundles, Reporter retention, the
wf_slo.py CLI contract, and the off-path hermeticity pins (slo= on vs off
byte-identical across all four drivers; compiled programs untouched)."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.nexmark import make_query
from windflow_tpu.observability import (MonitoringConfig, set_journal,
                                        device_health as dh,
                                        slo_engine as slo)
from windflow_tpu.runtime.faults import (FaultPlan, FaultSpec,
                                         reset_counters)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WF_SLO_CLI = os.path.join(REPO, "scripts", "wf_slo.py")
WF_HEALTH_CLI = os.path.join(REPO, "scripts", "wf_health.py")
WF_STATE_CLI = os.path.join(REPO, "scripts", "wf_state.py")

TOTAL = 300


@pytest.fixture(autouse=True)
def _clean_hooks():
    yield
    dh.set_active(None)
    set_journal(None)


def _poisoned_jax_dir(tmp_path):
    d = tmp_path / "nojax"
    d.mkdir(exist_ok=True)
    (d / "jax.py").write_text("raise ImportError('wf_slo must not import "
                              "jax')\n")
    return str(d)


def _lat_spec(**kw):
    base = dict(name="latency", signal="e2e_p99_ms", target=30.0,
                objective=0.5, fast_window=3, slow_window=6,
                warn_burn=1.0, page_burn=2.0)
    base.update(kw)
    return slo.SLOSpec(**base)


def _snap_p99(p99_ms, samples=5):
    """Synthetic snapshot carrying one windowed e2e latency observation."""
    return {"graph": "t", "operators": [],
            "e2e_latency_us": {"p99": p99_ms * 1e3, "p99_tick": p99_ms * 1e3,
                               "samples": samples, "samples_tick": samples}}


# ------------------------------------------------------- registry lockstep


def test_slo_gauges_registry_lockstep():
    from windflow_tpu.observability.metrics import _SLO_HELP
    from windflow_tpu.observability.names import SLO_GAUGES
    assert set(_SLO_HELP) == set(SLO_GAUGES)


def test_slo_events_registered():
    from windflow_tpu.observability.names import JOURNAL_EVENTS
    assert "slo_page" in JOURNAL_EVENTS
    assert "slo_recover" in JOURNAL_EVENTS
    from windflow_tpu.observability.names import RECOVERY_COUNTERS
    assert "recovery_seconds" in RECOVERY_COUNTERS


# --------------------------------------------------------- spec resolution


def test_resolve_specs_forms(tmp_path):
    assert slo.resolve_specs(None) is None
    assert slo.resolve_specs(False) is None
    assert slo.resolve_specs("") is None
    assert slo.resolve_specs("0") is None
    assert [s.name for s in slo.resolve_specs(True)] == \
        [s.name for s in slo.default_specs()]
    assert [s.name for s in slo.resolve_specs("1")] == \
        [s.name for s in slo.default_specs()]
    inline = '[{"name": "x", "signal": "drop_ratio", "target": 0.5}]'
    specs = slo.resolve_specs(inline)
    assert specs[0].name == "x" and specs[0].signal == "drop_ratio"
    p = tmp_path / "specs.json"
    p.write_text(json.dumps({"specs": [{"name": "y",
                                        "signal": "recovery_s",
                                        "target": 2.0}]}))
    assert slo.resolve_specs(str(p))[0].name == "y"
    specs = slo.resolve_specs([_lat_spec(), {"name": "z",
                                             "signal": "retrace_rate",
                                             "target": 0.0}])
    assert [s.name for s in specs] == ["latency", "z"]
    with pytest.raises(ValueError):
        slo.resolve_specs('{"specs": 17}')
    with pytest.raises(ValueError):
        slo.resolve_specs([{"name": "q", "signal": "drop_ratio",
                            "target": 1, "bogus_field": 2}])
    with pytest.raises(ValueError):
        slo.resolve_specs([3])


def test_monitoring_config_env_resolution(monkeypatch):
    monkeypatch.setenv("WF_MONITORING", "1")
    monkeypatch.setenv("WF_SLO", "1")
    assert MonitoringConfig.resolve(None).slo is True
    monkeypatch.setenv("WF_SLO", "0")
    assert MonitoringConfig.resolve(None).slo is False
    monkeypatch.setenv("WF_SLO", '[{"name":"a","signal":"drop_ratio",'
                                 '"target":1}]')
    cfg = MonitoringConfig.resolve(None)
    assert slo.resolve_specs(cfg.slo)[0].name == "a"
    monkeypatch.setenv("WF_SLO_COOLDOWN_S", "7.5")
    monkeypatch.setenv("WF_SLO_MAX_INCIDENTS", "3")
    monkeypatch.setenv("WF_SNAPSHOT_KEEP", "11")
    cfg = MonitoringConfig.resolve(None)
    assert cfg.slo_cooldown_s == 7.5
    assert cfg.slo_max_incidents == 3
    assert cfg.snapshot_keep == 11
    monkeypatch.setenv("WF_SNAPSHOT_KEEP", "0")
    assert MonitoringConfig.resolve(None).snapshot_keep is None
    monkeypatch.setenv("WF_SNAPSHOT_KEEP", "-2")
    with pytest.raises(ValueError):
        MonitoringConfig.resolve(None)


def test_spec_problems():
    assert slo.spec_problems(_lat_spec()) == []
    assert any("unknown signal" in p for p in
               slo.spec_problems(_lat_spec(signal="nope")))
    assert any("fast_window" in p for p in
               slo.spec_problems(_lat_spec(fast_window=6, slow_window=6)))
    assert any("objective" in p for p in
               slo.spec_problems(_lat_spec(objective=1.0)))
    assert any("warn_burn" in p for p in
               slo.spec_problems(_lat_spec(warn_burn=3.0, page_burn=2.0)))
    assert any("mode" in p for p in
               slo.spec_problems(_lat_spec(mode="sideways")))
    with pytest.raises(ValueError):
        slo.SLOEngine([_lat_spec(signal="nope")], out_dir=None)
    with pytest.raises(ValueError):
        slo.SLOEngine([_lat_spec(), _lat_spec()], out_dir=None)  # dup name


# ------------------------------------------------- burn / state machine


def test_transient_spike_warns_sustained_burn_pages():
    """THE multi-window contract: a spike that fills only the fast window
    WARNs and clears; a burn sustained across the slow window PAGEs."""
    eng = slo.SLOEngine([_lat_spec()], out_dir=None, journal=False)
    for _ in range(6):
        eng.observe(_snap_p99(1.0))
    assert eng.report()["latency"]["state"] == "ok"
    # 2-tick transient spike: fast window (3) burns, slow window (6) does
    # not reach page_burn -> WARN, never PAGE
    states = []
    for _ in range(2):
        states.append(eng.observe(_snap_p99(500.0))["slo"]["latency"]
                      ["state"])
    assert states[-1] == "warn"
    for _ in range(4):
        states.append(eng.observe(_snap_p99(1.0))["slo"]["latency"]
                      ["state"])
    assert states[-1] == "ok"
    assert "page" not in states
    # sustained: every tick violating -> both windows saturate -> PAGE
    for _ in range(6):
        st = eng.observe(_snap_p99(500.0))["slo"]["latency"]["state"]
    assert st == "page"
    rep = eng.report()["latency"]
    assert rep["pages"] == 1 and rep["burning"]
    # sticky until the FAST window is clean, then OK + slo_recover
    st = eng.observe(_snap_p99(500.0))["slo"]["latency"]["state"]
    assert st == "page"
    for _ in range(3):
        st = eng.observe(_snap_p99(1.0))["slo"]["latency"]["state"]
    assert st == "ok"
    trs = [(t["from"], t["to"]) for t in eng.report()["latency"]
           ["transitions"]]
    assert ("ok", "warn") in trs and ("page", "ok") in trs


def test_signal_absent_does_not_advance_window():
    """None observations (sub-system off / no traffic) neither violate nor
    clear — the SLO idles in its current state."""
    eng = slo.SLOEngine([_lat_spec()], out_dir=None, journal=False)
    for _ in range(8):
        eng.observe(_snap_p99(500.0))
    assert eng.report()["latency"]["state"] == "page"
    for _ in range(10):
        eng.observe({"graph": "t", "operators": [],
                     "e2e_latency_us": {"p99": 1.0, "samples": 5,
                                        "samples_tick": 0,
                                        "p99_tick": 0.0}})
    assert eng.report()["latency"]["state"] == "page"


def test_min_mode_signal_hbm_headroom():
    spec = slo.SLOSpec("headroom", "hbm_headroom_pct", target=20.0,
                       objective=0.5, fast_window=2, slow_window=4)
    eng = slo.SLOEngine([spec], out_dir=None, journal=False)

    def snap(pct):
        return {"graph": "t", "operators": [],
                "health": {"devices": [{"device": "d0",
                                        "bytes_limit": 100,
                                        "headroom_bytes": int(pct)}]}}
    for _ in range(4):
        eng.observe(snap(50))
    assert eng.report()["headroom"]["state"] == "ok"
    for _ in range(4):
        eng.observe(snap(5))
    assert eng.report()["headroom"]["state"] == "page"


def test_drop_ratio_differences_cumulative_counters():
    spec = slo.SLOSpec("drops", "drop_ratio", target=0.1, objective=0.5,
                       fast_window=2, slow_window=4)
    eng = slo.SLOEngine([spec], out_dir=None, journal=False)

    def snap(dropped, offered):
        return {"graph": "t",
                "operators": [{"name": "op", "inputs_received": offered,
                               "counters": {"overflow_drops": dropped}}],
                "totals": {"tuples_dropped_old": 0}}
    eng.observe(snap(0, 100))
    row = eng.observe(snap(0, 200))["slo"]["drops"]
    assert row["signal"] == 0.0
    # 50 new drops over 100 new offered = 0.5 per-tick ratio, even though
    # the cumulative ratio is only 50/300
    row = eng.observe(snap(50, 300))["slo"]["drops"]
    assert row["signal"] == pytest.approx(0.5)


# --------------------------------------------------- incident forensics


def test_page_capture_cooldown_and_cap(tmp_path):
    """Rate limit under a page storm: one bundle per cooldown window, a
    hard cap per run, every suppression counted — and every bundle commits
    via manifest-last."""
    clock = {"t": 0.0}
    eng = slo.SLOEngine([_lat_spec(fast_window=2, slow_window=4)],
                        out_dir=str(tmp_path), cooldown_s=60.0,
                        max_incidents=2, journal=False,
                        clock=lambda: clock["t"])

    def page_cycle():
        for _ in range(4):
            eng.observe(_snap_p99(500.0))
        for _ in range(2):
            eng.observe(_snap_p99(1.0))

    page_cycle()                      # page 1: captured
    page_cycle()                      # page 2: inside cooldown -> suppressed
    bundles, torn = slo.list_incidents(str(tmp_path))
    assert len(bundles) == 1 and not torn
    assert eng.incidents_suppressed == 1
    clock["t"] = 120.0                # past cooldown
    page_cycle()                      # page 3: captured (cap = 2 reached)
    clock["t"] = 300.0
    page_cycle()                      # page 4: over max_incidents
    bundles, _ = slo.list_incidents(str(tmp_path))
    assert len(bundles) == 2
    assert eng.report()["latency"]["pages"] == 4
    assert eng.incidents_suppressed == 2
    man = bundles[-1]
    assert man["slo"] == "latency" and not man["missing"]
    for fname in man["files"]:
        assert os.path.getsize(os.path.join(man["path"], fname)) > 0
    burn = json.load(open(os.path.join(man["path"], "burn.json")))
    assert burn["slo"] == "latency" and burn["timeline"]
    cfgj = json.load(open(os.path.join(man["path"], "config.json")))
    assert "env" in cfgj


def test_torn_bundle_detected(tmp_path):
    eng = slo.SLOEngine([_lat_spec(fast_window=2, slow_window=4)],
                        out_dir=str(tmp_path), journal=False,
                        clock=lambda: 0.0)
    for _ in range(4):
        eng.observe(_snap_p99(500.0))
    bundles, torn = slo.list_incidents(str(tmp_path))
    assert len(bundles) == 1 and not torn
    # a crash mid-capture = bundle directory without a committed manifest
    os.unlink(os.path.join(bundles[0]["path"], "manifest.json"))
    bundles, torn = slo.list_incidents(str(tmp_path))
    assert not bundles and len(torn) == 1
    summ = slo.incidents_summary(str(tmp_path))
    assert summ["count"] == 0 and summ["torn"] == 1


# --------------------------------------------- THE chaos acceptance loop


def _chaos_run(mon, trace_dir):
    """queue.stall chaos through the monitored threaded driver: a stalled
    phase that saturates both burn windows, then a healthy tail the fast
    window recovers on."""
    spec = [{"name": "latency", "signal": "e2e_p99_ms", "target": 30.0,
             "objective": 0.5, "fast_window": 3, "slow_window": 6,
             "warn_burn": 1.0, "page_burn": 2.0}]
    cfg = MonitoringConfig(out_dir=mon, interval_s=0.02, slo=spec,
                           e2e_sample_every=1)
    plan = FaultPlan([
        FaultSpec("queue.stall", kind="stall", stall_s=0.05,
                  at=list(range(6, 60))),
        FaultSpec("queue.stall", kind="stall", stall_s=0.002,
                  at=list(range(60, 500))),
    ], seed=3)
    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)},
                    total=420 * 32, num_keys=4)
    rows = []
    from windflow_tpu.observability import TraceConfig
    tp = wf.ThreadedPipeline(
        src, [[wf.Map(lambda t: {"v": t.v * 2})]],
        wf.Sink(lambda v: rows.append(0) if v is not None else None),
        batch_size=32, queue_capacity=2, faults=plan, monitoring=cfg,
        trace=TraceConfig(out_dir=trace_dir))
    tp.run()
    return rows


def test_acceptance_queue_stall_pages_and_recovers(tmp_path):
    """THE acceptance loop: an injected queue.stall drives the latency SLO
    OK -> WARN -> PAGE, exactly one cooldown-limited bundle lands with a
    schema-valid Chrome trace + journal tail, and recovery flips
    PAGE -> OK — with the wf_slo.py exit contract 1-on-burning /
    0-after-recovery over the same artifacts."""
    mon = str(tmp_path / "mon")
    rows = _chaos_run(mon, str(tmp_path / "trace"))
    assert len(rows) == 420            # every batch delivered

    series = [json.loads(l) for l in open(os.path.join(mon,
                                                       "snapshots.jsonl"))]
    states = [s["slo"]["latency"]["state"] for s in series if "slo" in s]
    # strictly OK -> WARN -> PAGE -> OK, in order
    assert states[0] == "ok"
    i_warn = states.index("warn")
    i_page = states.index("page")
    assert i_warn < i_page
    assert states[-1] == "ok"
    assert "page" not in states[states.index("ok", i_page):]

    ev = [json.loads(l) for l in open(os.path.join(mon, "events.jsonl"))]
    assert [e["event"] for e in ev if e["event"].startswith("slo_")] == \
        ["slo_page", "slo_recover"]

    # exactly ONE committed bundle (cooldown-limited), fully valid
    bundles, torn = slo.list_incidents(mon)
    assert len(bundles) == 1 and not torn
    man = bundles[0]
    assert man["slo"] == "latency" and not man["missing"]
    assert {"sections.json", "burn.json", "journal_tail.jsonl",
            "trace.json", "config.json"} <= set(man["files"])
    # schema-valid Chrome trace: event list with matched B/E pairs
    chrome = json.load(open(os.path.join(man["path"], "trace.json")))
    evs = chrome["traceEvents"]
    assert isinstance(evs, list) and evs
    b = sum(1 for e in evs if e["ph"] == "B")
    e_ = sum(1 for e in evs if e["ph"] == "E")
    assert b == e_ and b > 0
    assert all("ts" in e for e in evs)
    # journal tail parses line-by-line
    tail = [json.loads(l) for l in
            open(os.path.join(man["path"], "journal_tail.jsonl"))]
    assert tail and all("event" in e for e in tail)
    sections = json.load(open(os.path.join(man["path"], "sections.json")))
    assert sections["slo"]["latency"]["state"] == "page"

    # wf_slo exit contract over the SAME artifacts: a prefix ending inside
    # the burn exits 1; the full recovered series exits 0 — both without
    # jax on the path
    burn_dir = tmp_path / "burnwin"
    burn_dir.mkdir()
    lines = open(os.path.join(mon, "snapshots.jsonl")).readlines()
    with open(burn_dir / "snapshots.jsonl", "w") as f:
        f.writelines(lines[:i_page + 2])
    specf = tmp_path / "spec.json"
    specf.write_text(json.dumps([{
        "name": "latency", "signal": "e2e_p99_ms", "target": 30.0,
        "objective": 0.5, "fast_window": 3, "slow_window": 6,
        "warn_burn": 1.0, "page_burn": 2.0}]))
    env = dict(os.environ, PYTHONPATH=_poisoned_jax_dir(tmp_path))
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          str(burn_dir), "--specs", str(specf)],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 1, out.stderr
    assert "BURNING" in out.stdout
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          mon, "--specs", str(specf), "--json"],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 0, out.stderr
    data = json.loads(out.stdout)
    assert data["burning"] == []
    assert data["report"]["latency"]["pages"] == 1
    assert len(data["incidents"]) == 1

    # the sibling CLIs cross-reference the forensics
    for cli in (WF_HEALTH_CLI, WF_STATE_CLI):
        out = subprocess.run([sys.executable, cli, "--monitoring-dir", mon,
                              "--json"],
                             capture_output=True, text=True, env=env)
        assert out.returncode == 0, out.stderr
        inc = json.loads(out.stdout)["incidents"]
        assert inc["count"] == 1
        assert inc["last"]["slo"] == "latency"
        out = subprocess.run([sys.executable, cli, "--monitoring-dir", mon,
                              "--report", "incidents"],
                             capture_output=True, text=True, env=env)
        assert out.returncode == 0
        assert "triggered by SLO 'latency'" in out.stdout


# ------------------------------------------------ off-path hermeticity


def run_q3(driver="plain", monitoring=False):
    """The Nexmark enrich-join through one of the four drivers (the
    test_device_health acceptance workload), sink rows returned."""
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
        wf.Pipeline(src, ops, sink, batch_size=64,
                    monitoring=monitoring).run()
    else:
        g = wf.PipeGraph(batch_size=64, monitoring=monitoring)
        mp = g.add_source(src)
        for op in ops:
            mp.add(op)
        mp.add_sink(sink)
        if driver == "graph":
            g.run()
        elif driver == "graph-threaded":
            g.run(threaded=True)
        elif driver == "graph-supervised":
            g.run_supervised(checkpoint_every=2, backoff_base=0.001,
                             backoff_cap=0.01)
    return rows


@pytest.mark.parametrize("driver", ["plain", "graph", "graph-threaded",
                                    "graph-supervised"])
def test_slo_on_results_byte_identical(tmp_path, driver):
    """slo= on must not change a single result byte through any of the four
    drivers — the engine is Reporter-thread work only."""
    base = run_q3(driver)
    cfg = MonitoringConfig(out_dir=str(tmp_path / f"m-{driver}"),
                           interval_s=30.0, slo=True)
    on = run_q3(driver, monitoring=cfg)
    assert on == base


# WF_SLO's program-identity pin (formerly an ad-hoc HLO-text comparison
# here) lives in the shared toggle-OFF fingerprint gate:
# tests/test_program_fingerprint.py, TOGGLES["slo"].


# ------------------------------------------------- windowed e2e latency


def test_e2e_p99_tick_windows_per_snapshot():
    """The per-tick e2e percentile reads ONLY the samples recorded since
    the previous snapshot — the recovery signal the cumulative p99 cannot
    provide."""
    from windflow_tpu.observability import MetricsRegistry
    reg = MetricsRegistry("t")
    for _ in range(20):
        reg.record_e2e(0.500)
    s1 = reg.snapshot()
    assert "samples_tick" not in s1["e2e_latency_us"]   # no prev tick yet
    for _ in range(20):
        reg.record_e2e(0.001)
    s2 = reg.snapshot()
    e2e = s2["e2e_latency_us"]
    assert e2e["samples_tick"] == 20
    # cumulative p99 still remembers the slow phase; the tick p99 is fast
    assert e2e["p99"] > 100e3
    assert e2e["p99_tick"] < 10e3
    s3 = reg.snapshot()
    assert s3["e2e_latency_us"]["samples_tick"] == 0


# --------------------------------------------------- reporter retention


def test_snapshot_keep_rotation(tmp_path):
    from windflow_tpu.observability import MetricsRegistry, Reporter
    reg = MetricsRegistry("t")
    rep = Reporter(reg, str(tmp_path), interval_s=30.0, snapshot_keep=5)
    # amortized rotation: the file is bounded at 2N-1 lines (trim back to
    # N once it reaches 2N — trimming every tick past N would rewrite the
    # whole series per second on a long-running service), and every trim
    # keeps the NEWEST ticks
    for i in range(1, 25):
        rep.emit()
        n = len(open(tmp_path / "snapshots.jsonl").readlines())
        assert n <= 2 * 5 - 1
        # exact sawtooth: grows to 2N-1, trims to N on the 2N-th append
        assert n == (i if i < 10 else 5 + (i - 10) % 5)
    lines = open(tmp_path / "snapshots.jsonl").readlines()
    kept = [json.loads(l) for l in lines]
    assert all(s["graph"] == "t" for s in kept)
    ticks = [s["uptime_s"] for s in kept]
    assert ticks == sorted(ticks)
    # a fresh reporter over the same dir resumes the line count: keeps the
    # bound, never re-grows past 2N-1
    rep2 = Reporter(reg, str(tmp_path), interval_s=30.0, snapshot_keep=5)
    for _ in range(12):
        rep2.emit()
    assert len(open(tmp_path / "snapshots.jsonl").readlines()) <= 2 * 5 - 1
    # unlimited default: no rotation
    rep3 = Reporter(reg, str(tmp_path / "unl"), interval_s=30.0)
    for _ in range(8):
        rep3.emit()
    assert len(open(tmp_path / "unl" / "snapshots.jsonl").readlines()) == 8


def test_reporter_survives_engine_failure(tmp_path, capsys):
    """A broken signal extractor must not kill the tick — but the engine
    whose whole job is alerting must not die SILENTLY either: the snapshot
    records the error + count and the FIRST failure warns on stderr."""
    from windflow_tpu.observability import MetricsRegistry, Reporter

    class _Boom:
        def observe(self, snap):
            raise RuntimeError("bad extractor")

    reg = MetricsRegistry("t")
    rep = Reporter(reg, str(tmp_path), interval_s=30.0, slo_engine=_Boom())
    rep.emit()
    rep.emit()
    assert rep.slo_errors == 2
    with open(tmp_path / "snapshot.json") as f:
        snap = json.load(f)
    assert snap["slo_error"]["count"] == 2
    assert "RuntimeError" in snap["slo_error"]["error"]
    err = capsys.readouterr().err
    assert err.count("burn-rate alerting is degraded") == 1


# ------------------------------------------------------- fleet federation


def test_merge_snapshots_folds_slo_sections():
    a = {"graph": "g", "operators": [],
         "slo": {"latency": {"state": "ok", "code": 0, "burn_fast": 0.2,
                             "burn_slow": 0.1, "signal": 5.0,
                             "target": 30.0, "pages": 0}}}
    b = {"graph": "g", "operators": [],
         "slo": {"latency": {"state": "page", "code": 2, "burn_fast": 3.0,
                             "burn_slow": 2.5, "signal": 80.0,
                             "target": 30.0, "pages": 2}}}
    c = {"graph": "g", "operators": [],
         "slo": {"latency": {"state": "warn", "code": 1, "burn_fast": 1.5,
                             "burn_slow": 0.5, "signal": 40.0,
                             "target": 30.0, "pages": 1}}}
    m = dh.merge_snapshots([a, b, c], hosts=["h0", "h1", "h2"])
    row = m["slo"]["latency"]
    assert row["state"] == "page" and row["code"] == 2    # worst state wins
    assert row["worst_host"] == "h1"
    assert row["burn_fast"] == 3.0 and row["burn_slow"] == 2.5   # MAX
    assert row["pages"] == 3
    assert row["pages_by_host"] == {"h1": 2, "h2": 1}     # host-tagged
    assert row["signal"] == 80.0                  # the worst host's value
    # min-sense signal: the paging host's LOW value must win — a blanket
    # MAX would report the HEALTHIEST host's headroom on a paging row
    d = {"graph": "g", "operators": [],
         "slo": {"headroom": {"state": "page", "code": 2, "burn_fast": 4.0,
                              "burn_slow": 3.0, "signal": 3.0,
                              "target": 10.0, "pages": 1}}}
    e = {"graph": "g", "operators": [],
         "slo": {"headroom": {"state": "ok", "code": 0, "burn_fast": 0.0,
                              "burn_slow": 0.0, "signal": 85.0,
                              "target": 10.0, "pages": 0}}}
    row2 = dh.merge_snapshots([d, e], hosts=["h0", "h1"])["slo"]["headroom"]
    assert row2["signal"] == 3.0 and row2["worst_host"] == "h0"
    assert row2["burn_fast"] == 4.0 and row2["state"] == "page"


# ------------------------------------------- supervisor recovery surface


def test_recovery_seconds_counter_from_restore(tmp_path):
    reset_counters()
    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=16 * 32,
                    num_keys=4)
    got = []
    p = wf.SupervisedPipeline(
        src, [wf.Map(lambda t: {"v": t.v * 2})],
        wf.Sink(lambda v: got.append(0) if v is not None else None),
        batch_size=32, checkpoint_every=4, max_restarts=3,
        backoff_base=0.0,
        faults=FaultPlan([FaultSpec("chain.step", at=[5])], seed=1))
    p.run()
    from windflow_tpu.runtime import faults as _faults
    c = _faults.counters()
    assert c["restarts"] >= 1
    assert c["recovery_seconds"] > 0.0


# ------------------------------------------------------------ WF116 pins


def test_wf116_env_on_monitoring_off(monkeypatch):
    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=256,
                    num_keys=4)
    p = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v})],
                    wf.Sink(lambda v: None), batch_size=64)
    from windflow_tpu.analysis import validate
    monkeypatch.setenv("WF_SLO", "1")
    r = validate(p)
    assert "WF116" in r.codes() and r.errors
    monkeypatch.setenv("WF_MONITORING", "1")
    r = validate(p)
    assert "WF116" not in r.codes()


@pytest.mark.parametrize("bad,frag", [
    ([{"name": "x", "signal": "nope", "target": 1}], "unknown signal"),
    ([{"name": "x", "signal": "e2e_p99_ms", "target": 1,
       "fast_window": 8, "slow_window": 4}], "fast_window"),
    ([{"name": "x", "signal": "e2e_p99_ms", "target": 1},
      {"name": "x", "signal": "drop_ratio", "target": 1}], "duplicate"),
    ("[not json", "does not resolve"),
])
def test_wf116_bad_specs(bad, frag):
    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=256,
                    num_keys=4)
    from windflow_tpu.analysis import validate
    cfg = MonitoringConfig(slo=bad)
    p = wf.Pipeline(src, [wf.Map(lambda t: {"v": t.v})],
                    wf.Sink(lambda v: None), batch_size=64, monitoring=cfg)
    r = validate(p)
    msgs = [d.message for d in r.diagnostics if d.code == "WF116"]
    assert msgs and any(frag in m for m in msgs), msgs


def test_wf116_in_explain_rules():
    from windflow_tpu.analysis.lint import RULES
    assert "WF116" in RULES and RULES["WF116"][0] == "error"


# ------------------------------------------------------------ CLI pins


def test_wf_slo_exit_2_contracts(tmp_path):
    env = dict(os.environ, PYTHONPATH=_poisoned_jax_dir(tmp_path))
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          str(tmp_path / "nope")],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2
    assert "cannot load snapshots" in out.stderr
    # malformed spec set is a usage error, not a crash
    mon = tmp_path / "m"
    mon.mkdir()
    (mon / "snapshots.jsonl").write_text(
        json.dumps({"graph": "t", "operators": []}) + "\n")
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          str(mon), "--specs", "[notjson"],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2
    assert "cannot resolve" in out.stderr
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps([{"name": "x", "signal": "nope",
                                "target": 1}]))
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          str(mon), "--specs", str(bad)],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2
    assert "WF116" in out.stderr
    # an EMPTY spec set is unusable input (2), never "burning" (1): an
    # automation caller must not read an empty spec file as an incident
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          str(mon), "--specs", "[]"],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2
    assert "empty" in out.stderr
    # duplicate SLO names are a spec typo (2), never "burning" (1)
    dup = json.dumps([{"name": "a", "signal": "e2e_p99_ms", "target": 10},
                      {"name": "a", "signal": "e2e_p99_ms", "target": 20}])
    out = subprocess.run([sys.executable, WF_SLO_CLI, "--monitoring-dir",
                          str(mon), "--specs", dup],
                         capture_output=True, text=True, env=env)
    assert out.returncode == 2
    assert "duplicate" in out.stderr
