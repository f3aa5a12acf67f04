"""Central registries of journal event names and metric/counter names.

THE single source of truth for every name the runtime emits into the
observability plane: journal events/spans (``journal.record``/``journal.span``),
process-wide recovery counters (``runtime.faults.bump``), control-plane
counters/gauges (``control._state.bump``/``set_gauge``).  The emitting modules
import their name tables from here, and the static-analysis linter
(``windflow_tpu/analysis/lint.py``) checks every emission call site against
these registries — a typo'd event name (``"chekpoint"``) or an undeclared
counter fails tier-1 instead of silently forking the metric namespace.

Pure data, stdlib only, imported by ``runtime``/``control``/``analysis`` —
this module must never import anything from the package (the linter parses it
with ``ast`` so it can run without JAX installed).

Adding a name: add it here AND emit it — the linter flags emissions missing
from the registry; an unused registry entry is harmless (names outlive call
sites across refactors).
"""

from __future__ import annotations

#: every journal event name emitted via ``journal.record``/``EventJournal.
#: event`` and every span name opened via ``journal.span`` (spans appear as
#: ``phase=begin/end`` pairs under the same name)
JOURNAL_EVENTS = (
    # observability lifecycle (observability/__init__.py Monitor)
    "monitoring_start", "monitoring_end",
    # compiled-chain hot path (runtime/pipeline.py): a sampled push
    "launch",
    # EOS protocol (runtime/pipeline.py, runtime/pipegraph.py)
    "eos", "eos_flush", "eos_propagate",
    # ordering buffer (parallel/ordering.py, via its _journal_release wrapper)
    "ordering_flush", "ordering_close_channel",
    # supervision / recovery (runtime/supervisor.py, runtime/faults.py,
    # runtime/checkpoint.py, runtime/threaded.py)
    "checkpoint", "restore",                       # spans
    "checkpoint_invalid", "checkpoint_fallback",
    "restart_exhausted", "dead_letter", "backoff",
    "watchdog_timeout", "watchdog_stale",
    "fault_injected",
    # control plane (control/admission.py, control/governor.py,
    # control/autotune.py, runtime/supervisor.py warm start)
    "shed", "throttle", "throttle_end",
    "capacity_switch", "tuning_converged", "tuning_warm_start",
    # per-batch causal tracing lifecycle (observability/tracing.py Tracer)
    "trace_start", "trace_end",
    # event-time forensics (runtime/pipeline.py CompiledChain, event_time
    # monitoring only): a stateful operator's drop counter advanced — the
    # record carries (op, kind, n) plus the PR 5 trace coordinates
    # (tid/pos) of the sampled batch the readback rode, so wf_trace.py /
    # wf_state.py join drops to traced batches
    "lateness_drop",
    # runtime health ledger (observability/device_health.py, health
    # monitoring only): "compile" = one jit trace of a CompiledChain
    # step program (cause, cache key, compile duration, AOT cost
    # flops/bytes); "retrace_unexpected" = the live retrace detector — a
    # warm executable re-traced under an ALREADY-TRACED signature (jit
    # cache eviction/clear, the WF102/WF109 hazard caught at runtime);
    # "kernel_resolve" = a per-backend kernel registry resolution
    # (ops/registry.py) observed while the ledger was active
    "compile", "retrace_unexpected", "kernel_resolve",
    # tiered keyed state (state/tiered.py TieredTable, maintain cadence):
    # "spill" = outbox rows settled into the host store, "readmit" = cold
    # rows handed back to the device tier on probe miss — both carry
    # (table, n, total); emitted on the driver thread only
    "spill", "readmit",
    # shard-local supervision (runtime/supervisor.py ShardedSupervisor):
    # "shard_restore" = ONE shard restored + replayed its own key range
    # while peers kept serving (shard id + replay extent: replay_from/
    # at_batch); "reshard" = a live re-sharding span (from_shards/
    # to_shards/moves/at_pos; discarded=True marks an in-flight handoff
    # manifest dropped on restore — replay re-derives the move)
    "shard_restore", "reshard",
    # SLO engine (observability/slo.py, Reporter-tick evaluation):
    # "slo_page" = an SLO's multi-window burn crossed page_burn on BOTH
    # windows (slo/signal/value/target/burn_fast/burn_slow/tick — incident
    # capture follows, rate-limited); "slo_recover" = a warned/paged SLO
    # returned to OK (from_state says which)
    "slo_page", "slo_recover",
    # fleet telemetry plane (observability/fleet.py):
    # "telemetry_connect"/"telemetry_lost" = the host agent's sender thread
    # (re)gained / dropped its aggregator connection (host/endpoint) — a
    # flapping link shows as a connect/lost train in the HOST journal;
    # "fleet_host_join"/"fleet_host_leave" = the AGGREGATOR saw a new host
    # tag's first frame / a host stream close (host, mon_dir on join)
    "telemetry_connect", "telemetry_lost",
    "fleet_host_join", "fleet_host_leave",
    # self-driving remediation (control/remediation.py, evaluated on the
    # Reporter tick in live mode / at commit barriers in supervised mode):
    # "remediation_apply" = a policy action fired an actuator (action/
    # actuator/slo + burn or barrier pos + setpoint details);
    # "remediation_skip" = an action wanted to fire but was held back —
    # reason says why (cooldown, run/action budget, damped, unbound
    # actuator, gate, arbitration loss to auto-reshard); "tuning_reclimb" =
    # a converged autotuner was un-converged to re-explore its ladder
    "remediation_apply", "remediation_skip", "tuning_reclimb",
    # serving front-end (serving/runtime.py ServingRuntime):
    # "serving_start"/"serving_end" frame one service run (endpoint +
    # tenant ids / batch + swap totals); "graph_swap" is BOTH the
    # quiesce->warm->cutover span around a zero-downtime chain swap AND
    # the point records inside it (applied=True with carried_state/
    # warmed/quiesce_ms, or rejected=True for an unregistered wire swap)
    "serving_start", "serving_end", "graph_swap",
)

#: flight-recorder record kinds (``observability/tracing.py``; the
#: ``flight.jsonl`` schema consumed by ``scripts/wf_trace.py``) — listed here
#: so tooling has one source of truth beside the journal/counter names
TRACE_RECORD_KINDS = ("ingest", "enq", "deq", "begin", "end")

#: flight-recorder stage labels minted OUTSIDE driver loops (driver stages
#: and ring edges are named by the drivers themselves: "chain", "seg<i>",
#: "pipe<i>", "sink", and the edge labels of ``PipeGraph._iter_edges`` /
#: ``ThreadedPipeline.edge_names``)
TRACE_STAGES = ("ingest",)

#: stage-label *families* (prefix + variable suffix): governor throttle
#: episodes record on ``governor:<edge>`` pseudo-stages
#: (``control/governor.py``) — match by prefix, not equality
TRACE_STAGE_PREFIXES = ("governor:",)

#: process-wide recovery counters (``runtime/faults.py``; surfaced in the
#: metrics snapshot under ``"recovery"`` and in Prometheus as
#: ``windflow_recovery_<name>_total``)
RECOVERY_COUNTERS = (
    "restarts", "backoff_sleeps", "backoff_seconds",
    "dead_letters", "watchdog_timeouts", "faults_injected",
    "checkpoint_saves", "checkpoint_corrupt_skipped",
    "checkpoint_fallbacks",
    # cumulative seconds spent inside supervisor restore spans (whole-domain
    # AND shard-local) — the per-tick delta is the SLO engine's
    # "recovery_s" signal (observability/slo.py)
    "recovery_seconds",
)

#: process-wide control-plane counters (``control/_state.py``; snapshot
#: ``"control"`` section, Prometheus ``windflow_control_<name>_total``)
CONTROL_COUNTERS = (
    "admitted_batches", "admitted_tuples", "shed_batches", "shed_tuples",
    "throttle_events", "throttle_seconds", "capacity_switches",
    "tuning_decisions", "tuning_cache_hits",
    # nexmark-class operator family (operators/session.py, operators/
    # rank.py): sessions closed by the data-dependent triggerer, and
    # leaderboard candidates evicted by the top-N rank merge
    "sessions_closed", "topn_evictions",
    # self-driving remediation (control/remediation.py): policy actions
    # that fired an actuator, and actions held back (cooldown / budget /
    # damping / unbound / gate / arbitration)
    "remediation_actions", "remediation_skips",
)

#: control-plane gauges (``control/_state.py::set_gauge``; Prometheus
#: ``windflow_control_<name>``)
CONTROL_GAUGES = (
    "chosen_capacity",
    # versioned join-state table (ops/lookup.py join_table_*): applied
    # upsert count of the most recently synced table (last-write-wins
    # across tables, the chosen_capacity convention)
    "join_table_version",
    # actuator setpoints (PR 17 remediation observability): current
    # admission bucket refill rate (control/admission.py, updated by
    # scale_rate), governor high/low queue-depth watermarks
    # (control/governor.py), and the tiered hot-capacity target the run
    # was built with (operators/join.py / operators/rank.py tier wiring,
    # last-write-wins across tables) — so remediation deltas are
    # observable before/after each action
    "bucket_rate", "governor_high_watermark", "governor_low_watermark",
    "hot_capacity",
    # advisory remediation recommendations (control/remediation.py):
    # geometry-baked setpoints (tiered hot capacity, watermark delay) are
    # traced constants, so their actuators gauge a recommendation for the
    # next restart instead of mutating a live trace
    "remediation_hot_capacity", "remediation_recommended_delay",
)

#: what the archive engine (``operators/win_seq.py``) publishes, and the stages
#: of a pattern that runs two of them (``Pane_Farm``) and publishes each
#: engine's under ``<stage>_<name>``; ``ARCHIVE_ENGINE_DROPS`` are the ones
#: that count lost tuples (``slo.py::_drop_total``)
ARCHIVE_ENGINE_COUNTERS = ("archive_overwrites", "old_drops",
                           "windows_undelivered_at_eos", "archive_runs_written")
ARCHIVE_ENGINE_GAUGES = ("archive_slots", "fired_window_budget",
                         "archive_run_len", "archive_run_rows",
                         "archive_run_groups", "owner_compare_cells")
ARCHIVE_ENGINE_DROPS = ("archive_overwrites",)
PANE_STAGES = ("plq", "wlq")

#: per-STAGE counters exported in the metrics snapshot's operator rows
#: (``row["counters"]``) and in Prometheus as
#: ``windflow_stage_<name>_total`` with HELP/TYPE lines — the PR 8 operator
#: counters promoted from process-wide totals to a uniform per-operator
#: surface.  Operators publish them via ``Basic_Operator.
#: _publish_stage_counters`` (which validates against this tuple, the
#: WF240/241 one-source-of-truth discipline); ``metrics.py`` renders ONLY
#: registered names.
STAGE_COUNTERS = (
    "sessions_closed",     # operators/session.py: sessions the triggerer closed
    "topn_evictions",      # operators/rank.py: leaderboard candidates evicted
    "match_drops",         # operators/join.py IntervalJoin: per-probe overflow
    "arch_drops",          # operators/join.py IntervalJoin: archive overwrites
    "overflow_drops",      # ops/lookup.py JoinTable: pending-ring/table drops
    "old_drops",           # session/win_seqffat OLD straggler drops (also in
    #                        tuples_dropped_old — here beside the other drops)
    # tiered keyed state (state/ + the per-operator tier wiring): device
    # rows spilled to the outbox, cold rows re-admitted on probe miss, and
    # host-store rows retired by watermark compaction
    "state_spills", "state_readmits", "state_compactions",
    # operators/win_seq.py (the archive engine, and the patterns built on it):
    # ring slots overwritten while an unfired window still needed their tuple,
    # and open windows the last EOS flush call left behind (0 once the driver
    # has flushed until None); ring rows the sorted-order inserts wrote, per
    # table (beside tuples_in: how many row writes replaced how many lanes)
    "archive_overwrites", "windows_undelivered_at_eos", "archive_runs_written",
    # operators/win_seqffat.py, global-time path: lanes folded into a ring slot
    # whose pane had not fired (their pane lay ffat_pane_slots or more past
    # the first unfired one); it publishes windows_undelivered_at_eos too;
    # batches whose pane fold (keyed_pane_fold: the counts, and the values
    # that ride them) took the whole batch's exact scatters (a chunk held
    # more stragglers than the partial branch scatters); batches that took the
    # partial branch (a chunk spanned too many panes: its stragglers were
    # scattered), and those stragglers; lanes folded after a window holding
    # them had fired (delay > 0 only: they count in the windows still open);
    # on the per-key time-based path ffat_ring_overruns too (a lane's pane
    # ffat_pane_slots or more past its key's first unfired one), the three
    # fold counters where its fold rides the contraction, and the largest
    # per-key watermark less the smallest, in ticks
    "ffat_ring_overruns", "ffat_fold_fallbacks", "ffat_fold_partials",
    "ffat_fold_spill_lanes", "ffat_late_lanes", "ffat_key_clock_spread",
    # operators/win_seqffat.py, any path whose combine is called on whole
    # structs (not add, maximum or minimum): the (key, pane) runs its
    # ordered fold combined, summed over batches
    "ffat_ordered_runs",
    # operators/win_patterns.py::Pane_Farm: its two engines' counters, each
    # under its stage's prefix (``plq_old_drops``, ``wlq_archive_overwrites``)
    *(f"{stage}_{counter}" for stage in PANE_STAGES
      for counter in ARCHIVE_ENGINE_COUNTERS),
)

#: per-stage gauges (same surface, ``windflow_stage_<name>`` gauge form)
STAGE_GAUGES = (
    "join_table_version",  # applied upsert count of the op's own JoinTable
    # tiered keyed state: hot-table occupancy (slots in use) and cold-tier
    # key count — the per-operator tier_occupancy pair wf_state.py trends
    # and wf_health.py cross-references against the HBM headroom gauge
    "tier_hot_used", "tier_cold_keys",
    # operators/win_seqffat.py, set at bind_geometry: the keys and the ring
    # slots per key; count-based windows: the (key, pane) runs one batch may
    # hold (the size the sorted-order insert compacts to and writes);
    # time-based windows: fired_window_budget (below)
    "ffat_run_budget", "ffat_keys", "ffat_pane_slots",
    # operators/win_seq.py, set at bind_geometry: the archive ring's slots per
    # key and the fired windows one batch may emit (win_seqffat.py, time-based:
    # a key on the global-time path); the slots of one ring row
    # as the insert moves them, and the rows one batch may write per table; at
    # the first insert: the gathers of the sorted columns a pass issues (one
    # slice a row each; 1 where every column shares a buffer)
    "archive_slots", "fired_window_budget", "archive_run_len", "archive_run_rows",
    "archive_run_groups",
    # both window engines, once the fired-window budget is settled: the rows x
    # keys cells a step compares to find the key of every row it lists (ring
    # rows or pane runs of the insert, fired windows), 0 where the lists kept
    # the binary search (ops/segment.py::enumerate_runs); win_seqffat.py off
    # the global-time path, with it: the ring lanes a step's emit reads as
    # whole key rows (fired windows x ring slots), 0 where it takes each
    # window's panes one element at a time
    "owner_compare_cells", "ffat_emit_row_lanes",
    # Pane_Farm's two engines' budgets, a prefix a stage
    *(f"{stage}_{gauge}" for stage in PANE_STAGES
      for gauge in ARCHIVE_ENGINE_GAUGES),
)

#: per-operator event-time gauges of the watermark propagation map
#: (``metrics.py``: snapshot ``event_time`` sections -> Prometheus
#: ``windflow_event_time_<name>``; only registered names are rendered).
#: ``min_watermark`` and ``skew`` are graph-level (the frontier + per-edge
#: watermark skew of the topology export).
EVENT_TIME_GAUGES = (
    "watermark",           # operator event-time frontier (max ts applied)
    "lag", "occupancy_pct", "pending_depth", "open_sessions",
    "oldest_open_age", "archive_fill_pct",
    "lateness_p50", "lateness_p99",        # lateness histogram quantiles
    "min_watermark", "skew",               # graph frontier + per-edge skew
)

#: per-SHARD gauges of the ``shards`` snapshot section (the shard-local
#: supervision layer's health surface: ``SupervisedPipeline.shard_report``
#: -> ``MetricsRegistry.attach_shards`` -> snapshot ``shards`` rows,
#: rendered per shard by ``scripts/wf_health.py``/``wf_state.py`` and
#: folded HOST-TAGGED (never summed — the fleet view must name WHICH
#: shard is hot) by ``device_health.merge_snapshots``)
SHARD_GAUGES = (
    "occupancy_tuples",     # live tuples this shard processed since commit
    "restarts",             # shard-local recoveries (global restarts excluded)
    "last_recovery_s",      # duration of the most recent shard restore+replay
    "dead_letters",         # sub-batches this shard quarantined
    "reshard_moves",        # times this shard's key range changed in a reshard
    "committed_pos",        # stream position of the shard's last commit
)

#: runtime-health gauges of the ``health`` snapshot section
#: (``MonitoringConfig.health`` / ``WF_MONITORING_HEALTH``;
#: ``metrics.py::_prometheus_health`` renders ONLY registered names — its
#: local HELP map is checked against this tuple at import, the
#: EVENT_TIME_GAUGES lockstep discipline).  The ``hbm_*`` family renders as
#: ``windflow_hbm_<name>`` (per device), the rest as
#: ``windflow_health_<name>`` (graph-/operator-/stage-labelled).
HEALTH_GAUGES = (
    "hbm_headroom_bytes",      # per device: bytes_limit - bytes_in_use —
    #                            THE eviction signal for tiered state
    "hbm_bytes_in_use", "hbm_bytes_limit",
    "live_buffer_bytes", "live_buffer_count",
    "state_bytes",             # per operator: state-pytree footprint
    "compiles", "retraces", "retraces_unexpected",  # compile ledger totals
    "compile_seconds",
    "device_ms", "dispatch_ms",                     # per stage label
    "dispatch_ratio",          # host dispatch / device time — >= 0.5 names
    #                            a fusion candidate (dispatch-bound edge)
)

#: per-SLO gauges of the ``slo`` snapshot section (``observability/slo.py``
#: SLOEngine, evaluated inside the Reporter tick; ``metrics.py::
#: _prometheus_slo`` renders ONLY registered names as
#: ``windflow_slo_<name>{graph,slo=...}`` — its local HELP map is checked
#: against this tuple at import, the HEALTH_GAUGES lockstep discipline).
#: Folded by ``device_health.merge_snapshots`` as worst-state-wins (code
#: MAX), burn rates MAX, pages summed + host-tagged.
SLO_GAUGES = (
    "state",            # health state code: 0 ok, 1 warn, 2 page
    "burn_fast",        # error-budget burn over the fast window
    "burn_slow",        # error-budget burn over the slow window
    "signal",           # latest observed signal value
    "target",           # the spec's target threshold
    "pages",            # PAGE transitions this run
)

#: gauges of the host-side ``telemetry`` snapshot section
#: (``observability/fleet.py`` TelemetryAgent.stats(), present only when
#: ``MonitoringConfig.telemetry`` is on; ``metrics.py::
#: _prometheus_telemetry`` renders ONLY registered names as
#: ``windflow_telemetry_<name>{graph=...}`` — its local HELP map is checked
#: against this tuple at import, the SLO_GAUGES lockstep discipline)
TELEMETRY_GAUGES = (
    "frames_sent",      # frames delivered to the aggregator socket
    "frames_dropped",   # frames evicted by the bounded drop-oldest outbox
    "reconnects",       # successful reconnects after a lost aggregator
    "outbox_depth",     # frames queued right now (bounded by the outbox)
    "connected",        # 1 = live aggregator connection, 0 = not
)

#: gauges of the aggregator-side ``fleet`` snapshot section
#: (``observability/fleet.py`` FleetAggregator, stamped into every merged
#: fleet snapshot and rendered as ``windflow_fleet_<name>{graph=...}`` by
#: ``fleet.render_prometheus`` — ``fleet._FLEET_HELP`` is pinned against
#: this tuple by ``tests/test_fleet.py``, the path-loadable analogue of the
#: import-time lockstep check)
FLEET_GAUGES = (
    "hosts_connected",  # hosts with a live telemetry stream right now
    "hosts_seen",       # distinct host tags seen since the serve started
    "frames_received",  # telemetry frames decoded across all hosts
    "frames_torn",      # frames lost to torn/corrupt wire data (resync'd)
    "ticks",            # fleet merge ticks emitted
)

#: run-level gauges of the ``serving`` snapshot section
#: (``serving/runtime.py`` ServingRuntime.serving_section ->
#: ``MetricsRegistry.attach_serving``; ``metrics.py::_prometheus_serving``
#: renders ONLY registered names as ``windflow_serving_<name>{graph=...}``
#: — its local HELP map is checked against this tuple at import, the
#: SLO_GAUGES lockstep discipline).  Counters summed, never host-tagged,
#: by ``device_health.merge_snapshots`` (``swaps_applied`` across hosts is
#: a fleet total like ``frames_torn``).
SERVING_GAUGES = (
    "swaps_applied",     # zero-downtime graph_swap cutovers completed
    "swaps_rejected",    # wire swap frames naming an unregistered graph
    "frames_decoded",    # intact WFS1 record frames ingested
    "frames_torn",       # bytes resync'd past (torn client / garbage)
    "frames_dup",        # reconnect-overlap frames deduped by tenant seq
    "clients_seen",      # ingest connections accepted since start
    "unknown_offered",   # batches from tenant ids nobody declared
)

#: per-TENANT gauges of the ``serving.tenants`` snapshot rows
#: (``serving/tenants.py`` TenantRegistry.counters; rendered as
#: ``windflow_tenant_<name>{graph,tenant=...}`` — the SHARD_GAUGES
#: per-label discipline; folded SUMMED per tenant id across hosts by
#: ``device_health.merge_snapshots``, so one tenant's fleet-wide shed
#: pressure is one series)
TENANT_GAUGES = (
    "offered",           # batches this tenant offered to its bucket
    "admitted",          # batches its controller admitted
    "shed",              # batches its controller shed
    "shed_tuples",       # tuple capacity those shed batches carried
    "rate",              # the bucket's live refill rate (remediation moves it)
    # per-tenant e2e latency (MetricsRegistry.record_tenant_e2e LogHistograms,
    # sampled on the serving drive loop beside the run-level e2e sample; rows
    # only carry these keys once the tenant has samples, so the off path stays
    # byte-identical).  Percentile folds are MAX across hosts (the PR 10 e2e
    # convention), samples summed, exemplar from the worst host.
    "e2e_p50_ms", "e2e_p95_ms", "e2e_p99_ms",
    "e2e_p99_tick_ms",   # windowed p99 over the last reporter tick — THE
    #                      tenant_e2e_p99_ms SLO signal's read (cumulative
    #                      p99 can never recover after a stall)
    "e2e_samples", "e2e_samples_tick",
    "e2e_p99_exemplar",  # trace id of a batch observed in the p99 bucket
)

#: kernel families selectable through the per-backend kernel registry
#: (``ops/registry.py``).  The linter (WF250) checks every literal kernel
#: name passed to ``register_kernel``/``resolve_impl`` against this tuple —
#: a typo'd kernel name would silently fork the selection/autotune namespace
#: (its env overrides, tuning-cache entries, and WF109 trace records would
#: never match the real kernel's).  The perf gate's proxy microbenchmarks
#: also enumerate this tuple, so a registered-but-unbenchmarked kernel fails
#: ``tests/test_perfgate.py``.
KERNELS = (
    "lookup",           # ops/lookup.py table_lookup (factored path)
    "ordering_merge",   # parallel/ordering.py bitonic merge/sort network
    "segment_fold",     # ops/segment.py segment_fold (window fold path)
    "join_probe",       # ops/lookup.py join_probe (stream-table join)
)

#: non-kernel proxy-microbench families the hermetic perf gate must ALSO
#: cover (``analysis/perfgate.py::compare``: a family without a proxy row is
#: a coverage finding, the KERNELS convention).
PERF_PROXY_FAMILIES = (
    # "pane_fold_counts" times ops/histogram.py keyed_pane_fold with no
    # value leaf: the count lift of Key_FFAT's global-time insert
    "pane_fold_counts",
    # "join" times the full versioned JoinTable step (upsert + registry
    # probe, ops/lookup.py join_table_*) — the probe kernels keep their
    # microbench or tests/test_perfgate.py fails coverage
    "join",
    # "spill" times the tiered-state eviction/pack path (ops/lookup.py
    # join_table_tier_evict: coldness sort + outbox pack + slot clear) —
    # the device-side half of the HBM->host spill protocol
    "spill",
    # "shard" times the sharded supervisor's key-ownership splitter
    # (parallel/sharding.py ShardAssignment.split_fn — the per-batch
    # program the reshard_pack AOT pin also covers): one masked split
    # into N sub-batches, the only per-batch cost sharding adds
    "shard",
)

#: Nexmark-style benchmark queries (``windflow_tpu/nexmark/queries.py``).
#: THE name registry for the workload suite: ``bench.py::bench_nexmark``,
#: ``benchmarks/sweep.py``, the perf-gate nexmark workload pins, and
#: ``tests/test_nexmark.py``'s dense oracles all enumerate this tuple, so a
#: query added to the package without bench/test coverage fails loudly.
NEXMARK_QUERIES = (
    "q1_currency",       # currency-map: per-bid dollar -> euro projection
    "q2_selection",      # selection-filter: auctions of interest
    "q3_enrich_join",    # stream-table join: bid -> auction category
    "q4_interval_join",  # interval join: bid within an auction's open window
    "q5_session",        # session-aggregate: per-bidder activity sessions
    "q6_topn",           # top-N-by-key: highest bids per auction
    "q7_distinct",       # distinct: first bid per selected auction
)

#: implementation names a kernel may register under (WF250 checks literal
#: impl names at ``register_kernel`` call sites too)
KERNEL_IMPLS = (
    "xla",              # reference formulation — always registered
    "pallas",           # fused Pallas kernel (TPU; interpret mode on CPU)
)
