#!/usr/bin/env python3
"""wf_health — runtime-health inspection CLI (HBM / compile / device time).

Reads a monitoring run's artifacts (``snapshot.json`` + ``snapshots.jsonl``
time series + ``events.jsonl``) produced with the health sub-toggle on and
renders:

- the **HBM memory ledger**: per-device bytes in use / limit / headroom with
  ``[HEADROOM-RISK]`` trend flags (the ``wf_state.py`` OVERFLOW-RISK
  convention applied to device memory), live-buffer totals, per-operator
  state-pytree footprints, and executable footprints;
- the **compile/retrace ledger**: compile counters (fresh / shape-retrace /
  UNEXPECTED retraces of warm executables) plus the journaled compile
  sequence — cause, cache key, duration, AOT cost flops/bytes — and any
  ``retrace_unexpected`` / ``kernel_resolve`` events;
- **device-time attribution**: sampled host-dispatch vs device milliseconds
  per stage with the dispatch-bound classifier — stages whose host overhead
  is >= 50% of their device time are the fusion candidates for whole-graph
  single-dispatch (ROADMAP item 2).

**Fleet federation**: ``--merge DIR [DIR...]`` folds N per-host monitoring
directories (or ``snapshots.jsonl`` paths) into one fleet view — counters
summed, watermark frontier min'd, pressure max'd, per-host provenance kept
(``device_health.merge_snapshots``), ahead of the multi-host arc.

Produce the inputs with::

    WF_MONITORING=1 WF_MONITORING_HEALTH=1 python my_run.py
    python scripts/wf_health.py --monitoring-dir wf_monitoring

Stdlib only (``observability/device_health.py`` + ``journal.py`` are loaded
by file path — the ``wf_trace.py`` convention), so this works on any box the
artifacts were copied to, without JAX installed.

Exit codes: 0 = report rendered, 2 = missing/unreadable inputs or usage
error (``tests/test_device_health.py`` pins the contract).
"""

import argparse
import importlib.util
import json
import os
import sys
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_device_health():
    """Load observability/device_health.py (and the journal module its
    relative import names, plus slo.py for the incident-bundle readers) by
    file path under a synthetic package — no windflow_tpu package import,
    no JAX."""
    obs = os.path.join(REPO, "windflow_tpu", "observability")
    pkg = sys.modules.get("wf_obs")
    if pkg is None:
        pkg = types.ModuleType("wf_obs")
        pkg.__path__ = [obs]
        sys.modules["wf_obs"] = pkg
    for name in ("journal", "device_health", "slo"):
        if f"wf_obs.{name}" in sys.modules:
            continue
        spec = importlib.util.spec_from_file_location(
            f"wf_obs.{name}", os.path.join(obs, f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules[f"wf_obs.{name}"] = mod
        spec.loader.exec_module(mod)
        setattr(pkg, name, mod)
    return sys.modules["wf_obs.device_health"], sys.modules["wf_obs.slo"]


def _fmt_bytes(n):
    if n is None:
        return "—"
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024.0
    return f"{n:.1f} TiB"


# ------------------------------------------------------------ report pieces


def memory_report(snap, series):
    lines = ["== HBM memory ledger =="]
    sec = snap.get("health") or {}
    devices = sec.get("devices") or []
    if not devices and not sec:
        lines.append("  (no health section — run with WF_MONITORING=1 "
                     "WF_MONITORING_HEALTH=1 / MonitoringConfig("
                     "health=True))")
        return lines
    # headroom trend over the series (first/last/min per device)
    trend = {}
    for s in series or [snap]:
        for d in (s.get("health") or {}).get("devices", []):
            if d.get("headroom_bytes") is not None:
                trend.setdefault(d.get("device", "?"), []).append(
                    d["headroom_bytes"])
    risky = set(sec.get("headroom_risk") or [])
    for d in devices:
        label = d.get("device", "?")
        bits = [f"kind={d.get('kind', '?')}"]
        if d.get("bytes_in_use") is not None:
            bits.append(f"in_use={_fmt_bytes(d['bytes_in_use'])}")
        if d.get("bytes_limit") is not None:
            bits.append(f"limit={_fmt_bytes(d['bytes_limit'])}")
        if d.get("headroom_bytes") is not None:
            bits.append(f"headroom={_fmt_bytes(d['headroom_bytes'])}")
            vals = trend.get(label, [d["headroom_bytes"]])
            bits.append(f"(min over run {_fmt_bytes(min(vals))})")
        flag = "  [HEADROOM-RISK]" if label in risky else ""
        if (d.get("headroom_bytes") is None
                and d.get("bytes_in_use") is None):
            bits.append("(no memory_stats on this backend)")
        lines.append(f"  {label:<16} " + "  ".join(bits) + flag)
    if sec.get("live_buffer_count") is not None:
        lines.append(f"  live buffers: {sec['live_buffer_count']} arrays, "
                     f"{_fmt_bytes(sec.get('live_buffer_bytes'))}")
    sb = sec.get("state_bytes") or {}
    if sb:
        lines.append("  per-operator state footprints:")
        for name, n in sorted(sb.items(), key=lambda kv: -kv[1]):
            lines.append(f"    {name:<28} {_fmt_bytes(n)}")
    # tiered-state cross-reference: when headroom is at risk, name WHICH
    # table to shrink — per-operator hot occupancy beside its HBM footprint
    # (a hot table far below 100% is reclaimable headroom; one pegged at
    # 100% with spill movement is already doing its job)
    tiers = [(row.get("name", "?"), row["event_time"]["tier"])
             for row in snap.get("operators", [])
             if isinstance((row.get("event_time") or {}).get("tier"), dict)]
    if tiers and (risky or sb):
        lines.append("  tiered tables (hot occupancy vs footprint — the "
                     "HEADROOM-RISK shrink candidates):")
        for name, t in sorted(
                tiers, key=lambda kv: -(sb.get(kv[0], 0) or 0)):
            bits = []
            if t.get("hot_used") is not None:
                bits.append(f"hot={t.get('hot_used')}/{t.get('hot_slots')}"
                            + (f" ({t['hot_pct']}%)"
                               if t.get("hot_pct") is not None else ""))
            if t.get("cold_keys") is not None:
                bits.append(f"cold={t['cold_keys']} keys")
            for k in ("state_spills", "state_readmits"):
                if t.get(k):
                    bits.append(f"{k.split('_')[1]}={t[k]}")
            if name in sb:
                bits.append(f"hbm={_fmt_bytes(sb[name])}")
            lines.append(f"    {name:<28} " + "  ".join(bits))
    exes = sec.get("executables") or {}
    if exes:
        lines.append("  executable footprints (cache key: arg/out/temp/"
                     "code bytes):")
        for key, row in sorted(exes.items()):
            lines.append(
                f"    {key} {row.get('label', '?')}/{row.get('kind', '?')}"
                f"  arg={_fmt_bytes(row.get('argument_bytes'))}"
                f"  out={_fmt_bytes(row.get('output_bytes'))}"
                f"  temp={_fmt_bytes(row.get('temp_bytes'))}"
                f"  code={_fmt_bytes(row.get('code_bytes'))}")
    return lines


def compile_report(snap, journal):
    lines = ["== compile/retrace ledger =="]
    comp = (snap.get("health") or {}).get("compile") or {}
    if comp:
        lines.append(
            f"  {comp.get('compiles', 0)} compiles: "
            f"{comp.get('retraces', 0)} shape retraces "
            f"(capacity/K switches), "
            f"{comp.get('retraces_unexpected', 0)} UNEXPECTED retraces "
            f"(warm executables silently recompiled), "
            f"{comp.get('compile_s_total', 0)} s total, "
            f"{comp.get('kernel_resolves', 0)} kernel resolutions")
    compiles = [e for e in journal if e.get("event") == "compile"]
    if compiles:
        lines.append("  compile journal (cause / stage / key / cost):")
        for e in compiles:
            cost = ""
            if e.get("flops") is not None:
                cost = (f"  {e['flops'] / 1e6:.2f} Mflop"
                        f"/{(e.get('bytes_accessed') or 0) / 1e6:.2f} MB")
            shape = f" cap={e['capacity']}" if e.get("capacity") else ""
            kind = ("RETRACE" if e.get("retrace")
                    else ("UNEXPECTED" if e.get("unexpected") else "compile"))
            lines.append(
                f"    {e.get('label', '?'):<10} {e.get('kind', '?'):<5} "
                f"{kind:<10} cause={e.get('cause', '?'):<17} "
                f"key={e.get('cache_key', '?')}{shape} "
                f"{e.get('compile_s', 0):.3f}s{cost}")
    unexpected = [e for e in journal
                  if e.get("event") == "retrace_unexpected"]
    if unexpected:
        lines.append("  UNEXPECTED retraces (warm executables re-traced "
                     "under an identical signature):")
        for e in unexpected:
            lines.append(f"    {e.get('label', '?')}/{e.get('kind', '?')} "
                         f"key={e.get('cache_key', '?')} "
                         f"cause={e.get('cause', '?')}")
    resolves = [e for e in journal if e.get("event") == "kernel_resolve"]
    if resolves:
        lines.append(f"  kernel resolutions: " + "  ".join(
            f"{e.get('kernel')}->{e.get('impl')}" for e in resolves[:8])
            + (" …" if len(resolves) > 8 else ""))
    if len(lines) == 1:
        lines.append("  (no compile records — health off, or nothing "
                     "compiled while the ledger was active)")
    return lines


def device_time_report(snap):
    lines = ["== device-time attribution (dispatch-bound classifier) =="]
    sec = snap.get("health") or {}
    dt = sec.get("device_time") or {}
    if not dt:
        lines.append("  (no sampled device-time points — health off or the "
                     "run was too short to hit a sampled push)")
        return lines
    bound = sec.get("dispatch_bound") or {}
    for stage, row in sorted(dt.items(),
                             key=lambda kv: -(kv[1].get("dispatch_ratio")
                                              or 0.0)):
        ratio = row.get("dispatch_ratio")
        flag = ("  [DISPATCH-BOUND -> fusion candidate]"
                if stage in bound else "")
        lines.append(
            f"  {stage:<24} device={row.get('device_ms', 0):10.3f} ms  "
            f"host-dispatch={row.get('dispatch_ms', 0):10.3f} ms  "
            f"samples={row.get('samples', 0):<5} "
            f"ratio={ratio if ratio is not None else '—'}{flag}")
    if bound:
        lines.append(f"  {len(bound)} dispatch-bound stage(s): the host "
                     f"loop, not the device, is their ceiling — the "
                     f"whole-graph fusion candidates (ROADMAP item 2)")
    return lines


def shard_report(snap, journal):
    """Per-shard supervision health: one row per shard (host-tagged in a
    fleet merge — the keys name WHICH shard is hot), occupancy + restart
    counts + last recovery duration + reshard movements, plus the journal's
    shard_restore/reshard timeline tail."""
    lines = ["shard supervision"]
    shards = snap.get("shards") or {}
    if not shards:
        lines.append("  (no shards section — run the supervised driver "
                     "with shards=N / WF_SHARDS=N and monitoring on)")
        return lines
    hot = max(shards, key=lambda k: shards[k].get("occupancy_tuples", 0))
    lines.append(f"  {len(shards)} shard(s); hottest: {hot} "
                 f"({shards[hot].get('occupancy_tuples', 0)} tuples)")
    lines.append(f"  {'shard':>12} {'tuples':>10} {'restarts':>8} "
                 f"{'recov_ms':>9} {'dead':>5} {'moves':>6} {'pos':>6}")
    for k in sorted(shards, key=lambda x: (len(x), x)):
        r = shards[k]
        flag = "  [HOT]" if k == hot and len(shards) > 1 else ""
        lines.append(
            f"  {k:>12} {r.get('occupancy_tuples', 0):>10} "
            f"{r.get('restarts', 0):>8} "
            f"{r.get('last_recovery_s', 0.0) * 1e3:>9.2f} "
            f"{r.get('dead_letters', 0):>5} {r.get('reshard_moves', 0):>6} "
            f"{r.get('committed_pos', 0):>6}{flag}")
    # reshard spans emit begin+end records — keep one line per reshard
    # (the wf_state.py shard_section convention)
    ev = [e for e in journal
          if e.get("event") in ("shard_restore", "reshard")
          and e.get("phase") != "end"]
    if ev:
        lines.append(f"  recovery/reshard events: {len(ev)} "
                     f"(last {min(5, len(ev))}):")
        for e in ev[-5:]:
            if e.get("event") == "shard_restore":
                lines.append(f"    shard_restore shard={e.get('shard')} "
                             f"at={e.get('at_batch')} "
                             f"replay_from={e.get('replay_from')} "
                             f"error={e.get('error')}")
            else:
                lines.append(f"    reshard {e.get('from_shards')}->"
                             f"{e.get('to_shards')} at={e.get('at_pos')} "
                             f"moves={e.get('moves')}"
                             + (" DISCARDED" if e.get("discarded") else ""))
    return lines


def incidents_report(slo_mod, mon_dir):
    """Cross-reference to the SLO engine's forensic bundles (count, last
    incident path + triggering SLO, torn captures) — read from the bundle
    manifests under ``<mon_dir>/incidents`` (``slo.incidents_summary``)."""
    lines = ["== incidents (SLO forensic bundles) =="]
    summ = slo_mod.incidents_summary(mon_dir)
    if not summ["count"] and not summ["torn"]:
        lines.append("  (none captured — enable with WF_SLO=1 / "
                     "MonitoringConfig(slo=...); analyze with "
                     "scripts/wf_slo.py)")
        return lines
    lines.append(f"  {summ['count']} committed bundle(s)"
                 + (f", {summ['torn']} TORN (crash mid-capture)"
                    if summ["torn"] else ""))
    last = summ.get("last")
    if last:
        lines.append(f"  last: {last['path']}")
        lines.append(f"        triggered by SLO {last.get('slo')!r} "
                     f"(state {last.get('state')})")
    return lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="wf_health",
        description="windflow_tpu runtime-health CLI (HBM ledger, "
                    "compile/retrace ledger, device-time attribution, "
                    "fleet merge)")
    ap.add_argument("--monitoring-dir", default="wf_monitoring",
                    help="monitoring output directory (snapshot.json + "
                         "snapshots.jsonl + events.jsonl)")
    ap.add_argument("--merge", nargs="+", default=None, metavar="DIR",
                    help="merge N per-host monitoring directories (or "
                         "snapshots.jsonl paths) into one fleet view "
                         "instead of reading --monitoring-dir")
    ap.add_argument("--report", choices=("all", "memory", "compile",
                                         "device-time", "shards",
                                         "incidents"),
                    default="all",
                    help="which section(s) to render (default all)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output: the (merged) snapshot's "
                         "health section + provenance")
    args = ap.parse_args(argv)

    try:
        dh, slo_mod = _load_device_health()
    except (OSError, ImportError, SyntaxError) as e:
        print(f"wf_health: cannot load observability/device_health.py from "
              f"{REPO!r}: {type(e).__name__}: {e}\n"
              f"(keep scripts/wf_health.py next to its windflow_tpu tree — "
              f"it reuses the ledger/merge helpers by file path)",
              file=sys.stderr)
        return 2
    try:
        if args.merge:
            snap, series, journal = dh.merge_monitoring_dirs(args.merge)
        else:
            snap, series = dh.load_snapshots(args.monitoring_dir)
            journal = dh.load_journal(args.monitoring_dir)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        where = args.merge or args.monitoring_dir
        print(f"wf_health: cannot load snapshots from {where!r}: "
              f"{type(e).__name__}: {e}\n"
              f"(run with WF_MONITORING=1 WF_MONITORING_HEALTH=1, or "
              f"monitoring=MonitoringConfig(health=True))",
              file=sys.stderr)
        return 2

    if args.json:
        out = {"graph": snap.get("graph"),
               "health": snap.get("health") or {},
               "shards": snap.get("shards") or {},
               "snapshots": len(series),
               "journal_events": len(journal)}
        if not args.merge:
            out["incidents"] = slo_mod.incidents_summary(args.monitoring_dir)
        if snap.get("hosts"):
            out["hosts"] = snap["hosts"]
            out["merged_from"] = snap.get("merged_from")
        if snap.get("schema_mismatch"):
            out["schema_mismatch"] = snap["schema_mismatch"]
        print(json.dumps(out, indent=1, sort_keys=True))
        return 0
    head = (f"wf_health: merged {snap.get('merged_from')} host(s): "
            + ", ".join(h.get("host", "?") for h in snap.get("hosts", []))
            if args.merge else
            f"wf_health: {args.monitoring_dir!r}")
    print(f"{head} — graph {snap.get('graph', '?')!r}, {len(series)} "
          f"snapshot(s), {len(journal)} journal event(s)")
    if snap.get("schema_mismatch"):
        # merge_snapshots flags mixed snapshot generations, never folds
        # them silently — keep the flag visible at the top of the report
        print(f"wf_health: MIXED-SCHEMA fleet — per-host snapshot schema "
              f"versions differ: "
              f"{json.dumps(snap['schema_mismatch'], sort_keys=True)}")
    blocks = []
    if args.report in ("all", "memory"):
        blocks.append(memory_report(snap, series))
    if args.report in ("all", "compile"):
        blocks.append(compile_report(snap, journal))
    if args.report in ("all", "device-time"):
        blocks.append(device_time_report(snap))
    if args.report == "shards" or (args.report == "all"
                                   and snap.get("shards")):
        blocks.append(shard_report(snap, journal))
    if args.report in ("all", "incidents"):
        if args.merge:
            # per-host forensics: a merged fleet view has no single
            # incidents/ directory — say so when incidents were asked for
            # explicitly instead of rendering nothing (indistinguishable
            # from "no incidents on the fleet")
            if args.report == "incidents":
                blocks.append(
                    ["== incidents (SLO forensic bundles) ==",
                     "  (not available in the --merge fleet view — "
                     "bundles live under each host's own "
                     "<monitoring_dir>/incidents/; run wf_health "
                     "against each host's dir)"])
        else:
            blocks.append(incidents_report(slo_mod, args.monitoring_dir))
    for b in blocks:
        print()
        print("\n".join(b))
    return 0


if __name__ == "__main__":
    sys.exit(main())
