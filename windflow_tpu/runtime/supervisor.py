"""Failure detection + recovery: supervised pipeline execution.

The reference's failure model is ``exit(EXIT_FAILURE)`` (SURVEY §5 — no restarts,
no rescaling); state is lost. Here operator state is a pytree threaded through a
compiled step, which makes recovery structural:

- **Detection**: any exception surfacing from a step (device fault, lost link,
  user-function error materializing at the sink) is caught by the supervisor loop;
  a ``step_timeout`` watchdog additionally converts a *hung* step into a
  detectable :class:`~windflow_tpu.runtime.faults.WatchdogTimeout`.
- **Checkpoint alignment**: every ``checkpoint_every`` batches the supervisor
  snapshots all operator states to host memory (optionally to ``.npz`` via
  ``spill_path``, atomically, with checksums and — ``checkpoint_keep > 1`` — a
  retained lineage) together with the stream position.
- **Recovery**: on failure the states are restored, the source is re-opened and
  fast-forwarded to the checkpointed position, and processing resumes; up to
  ``max_restarts`` attempts (counted per checkpoint interval — progress resets the
  budget), with exponential backoff + decorrelated jitter between attempts
  (``backoff_base``/``backoff_cap``) so a flapping device is not hammered.
- **Poison-batch quarantine**: when a ``dead_letter`` queue is installed and
  replay fails ``poison_threshold`` consecutive times at the same position, the
  offending batch is routed to the :class:`~windflow_tpu.runtime.faults.
  DeadLetterQueue` and skipped — one malformed input degrades gracefully
  instead of exhausting the restart budget. (Effective when
  ``poison_threshold <= max_restarts + 1``, since the budget bounds
  consecutive failures within one interval.)
- **Exactly-once sink delivery**: sink outputs are buffered and released only when
  the *next* checkpoint commits (the aligned-checkpoint output-commit rule); on
  failure the uncommitted buffer is discarded and regenerated by replay, so the
  sink never sees duplicated or torn results. Committed-but-undelivered outputs
  survive a sink fault in an outbox and are re-driven without replay — a crash
  mid-delivery loses nothing and duplicates nothing.

Requires a *replayable* source (every built-in source is: ``DeviceSource`` is a
pure function of the index; ``GeneratorSource``/``RecordSource`` re-invoke their
``it_factory``).

Fault-injection sites (``runtime/faults.py``): ``source.next`` before each
batch pull, ``chain.step`` before each compiled push, ``sink.consume`` before
each committed delivery, ``checkpoint.save``/``checkpoint.load`` inside the
spill path — a seeded :class:`~windflow_tpu.runtime.faults.FaultPlan` (or
``WF_FAULT_PLAN``) exercises every branch above deterministically.
"""

from __future__ import annotations

import contextlib
import inspect
import os
import random
from typing import Optional, Sequence

import jax
import numpy as np

from ..basic import DEFAULT_BATCH_SIZE
from ..observability import device_health as _dh
from ..observability import journal as _journal
from ..observability import tracing as _tracing
from ..operators.base import Basic_Operator
from ..operators.sink import ReduceSink, Sink
from ..operators.source import SourceBase
from . import faults as _faults
from .pipeline import CompiledChain


class RestartExhausted(RuntimeError):
    """Raised when a step keeps failing past ``max_restarts`` within one
    checkpoint interval; carries the last underlying error as ``__cause__``."""


@contextlib.contextmanager
def _restore_span(**fields):
    """Journal a whole-domain ``restore`` span AND feed its measured
    duration into the cumulative ``recovery_seconds`` counter — the restore-
    span timing surface the SLO engine's ``recovery_s`` signal differences
    per Reporter tick (``observability/slo.py``).  Timing only, never
    replayed data (the ``last_recovery_s`` stance); a restore that itself
    raises counts nothing — the attempt that finally lands does."""
    import time as _t                     # wf-lint: allow[wall-clock]
    t0 = _t.perf_counter()                # wf-lint: allow[wall-clock]
    with _journal.span("restore", **fields):
        yield
    _faults.bump("recovery_seconds",
                 round(_t.perf_counter() - t0, 6))  # wf-lint: allow[wall-clock]


def _supervised_admission(cfg, base_capacity: int, driver: str):
    """Admission control under supervision must be REPLAY-DETERMINISTIC:
    shed decisions are part of the output, so a replay from a checkpoint must
    re-shed exactly the batches the failed attempt shed. That rules out the
    wall-clock :class:`~windflow_tpu.control.TokenBucket` (a restore changes
    the refill timeline) and the ``drop_oldest_ts`` holding cell (held
    batches would have to be serialized into every checkpoint); the
    supervised drivers accept only the positional bucket + ``drop_newest``,
    and snapshot/restore the bucket state with their own checkpoints."""
    if cfg is None or not cfg.admission:
        return None
    if cfg.refill_per_batch is None:
        raise ValueError(
            "supervised admission control requires the deterministic "
            "ControlConfig(refill_per_batch=...) bucket — the wall-clock "
            "rate_tps bucket makes shed decisions time-dependent, which "
            "breaks exactly-once replay (use the live drivers for "
            "wall-clock rate limiting)")
    if cfg.shed_policy != "drop_newest":
        raise ValueError(
            "supervised admission control supports shed_policy='drop_newest' "
            "only (a drop_oldest_ts holding cell would have to be serialized "
            "into every checkpoint)")
    from ..control import AdmissionController, PositionBucket
    from ..control.admission import resolve_burst
    return AdmissionController(
        PositionBucket(cfg.refill_per_batch, resolve_burst(cfg, base_capacity)),
        "drop_newest", hold_max=0, driver=driver)


def _interval_drop_ratio(prev_adm, new_adm):
    """The JUST-committed interval's shed fraction — shed/(shed+admitted)
    deltas between two committed admission snapshots.  A pure function of
    committed state, so it is the barrier-remediation ``drop_ratio`` signal
    (a cumulative ratio would dilute a new incident under hours of healthy
    traffic — the ``interval_tuples`` stance).  None for an EMPTY interval
    (the zero-length EOS commit): an unobserved signal freezes the
    violation window instead of clearing it."""
    if prev_adm is None or new_adm is None:
        return None
    shed = int(new_adm.get("shed", 0)) - int(prev_adm.get("shed", 0))
    admitted = (int(new_adm.get("admitted", 0))
                - int(prev_adm.get("admitted", 0)))
    total = shed + admitted
    return (shed / total) if total > 0 else None


def _apply_barrier_decision(d: dict, adm) -> None:
    """Actuate + journal ONE BarrierRemediation decision — shared by both
    supervised drivers (reshard decisions are arbitrated by the sharded
    coordinator before reaching here, so only admission-rate decisions
    actuate in this helper)."""
    from ..control import _state as _cstate
    if not d.get("applied"):
        _cstate.bump("remediation_skips")
        _journal.record("remediation_skip", **d)
        return
    if d.get("actuator") == "admission_rate" and adm is not None:
        d.update(adm.scale_rate(d["factor"], d["floor"]))
    _cstate.bump("remediation_actions")
    _journal.record("remediation_apply", **d)


def _supervised_tracer(trace_arg, name: str):
    """Causal tracing under supervision must mint REPLAY-STABLE ids: a
    restore replays batches from the committed position, and the replayed
    batch must carry the id the failed attempt minted — otherwise every
    exemplar and ring flow recorded before the failure dangles.  Position-
    derived ids (the default) are a pure function of stream position, exactly
    like the :func:`_supervised_admission` bucket; the live-only
    ``ids="sequence"`` counter is rejected here (and pre-run by the
    validator's WF108)."""
    from ..observability import TraceConfig, Tracer
    cfg = TraceConfig.resolve(trace_arg)
    if cfg is None:
        return None
    if cfg.ids != "position":
        raise ValueError(
            "supervised tracing requires TraceConfig(ids='position') — "
            "sequence ids are minted from a process counter, so a replay "
            "after restore would mint fresh ids and orphan every exemplar "
            "and ring-edge flow recorded before the failure (use the live "
            "drivers for sequence ids)")
    return Tracer(cfg, name).start()


def _warm_start_capacity(cfg, ops, source, batch_size: int) -> int:
    """Tuning-cache warm start for the supervised drivers: the closed-loop
    autotuner itself does not run under supervision (a mid-stream capacity
    switch would re-index the checkpoint positions), but a plan persisted by
    a live ``Pipeline`` run is safe to consume — same chain signature, same
    payload, same device kind → start at the tuned capacity."""
    if cfg is None or not cfg.autotune or not cfg.cache_path:
        return batch_size
    from ..control import (TuningCache, chain_signature, device_kind,
                           payload_signature, tuning_key)
    from ..control import _state as _cstate
    key = tuning_key(chain_signature(ops),
                     payload_signature(source.payload_spec()), device_kind())
    hit = TuningCache(cfg.cache_path).get(key)
    if not hit:
        return batch_size
    cap = int(hit["capacity"])
    _cstate.bump("tuning_cache_hits")
    _journal.record("tuning_warm_start", tuner="supervised", capacity=cap,
                    key=key)
    return cap


class SupervisedPipeline:
    """Source -> ops... -> sink with failure detection, checkpoint-aligned
    recovery, and exactly-once sink delivery."""

    def __init__(self, source: SourceBase, ops: Sequence[Basic_Operator],
                 sink: Optional[Sink] = None, *,
                 batch_size: int = DEFAULT_BATCH_SIZE,
                 checkpoint_every: int = 8, max_restarts: int = 3,
                 spill_path: Optional[str] = None, checkpoint_keep: int = 1,
                 backoff_base: float = 0.05, backoff_cap: float = 2.0,
                 dead_letter=None, poison_threshold: int = 3,
                 step_timeout: Optional[float] = None, faults=None,
                 control=None, trace=None, shards=None,
                 reshard=None, shard_key=None, shard_range=None,
                 remediation=None):
        self.source = source
        self.sink = sink
        #: shard-local supervision opt-in (shards= kwarg or WF_SHARDS env,
        #: off by default — shards resolved to 1 runs TODAY'S exact single-
        #: supervision-domain path, byte-for-byte): N>1 partitions the key
        #: space into N ShardSupervisor units coordinated by a thin
        #: ShardedSupervisor control plane — a failed shard restores and
        #: replays ONLY its key range while peers keep serving. ``reshard=``
        #: (or WF_RESHARD) registers a live re-sharding plan applied at a
        #: checkpoint barrier ("auto" derives one from per-shard load).
        from ..parallel.sharding import resolve_shards
        self._shards = resolve_shards(shards)
        self._reshard_arg = reshard
        #: ownership-key override (TupleRef -> int, the KeyBy convention):
        #: REQUIRED when the stateful operators group on a derived key (a
        #: KeyBy downstream, or an operator key_fn over a payload field
        #: that differs from the ingest key) — see sharding.make_splitter
        self._shard_key = shard_key
        #: multi-host slice: this process supervises shards [lo, hi) of the
        #: global count (parallel/multihost.py::process_shard_slice) —
        #: sub-batches of peer processes' shards are discarded here, each
        #: host runs its own recovery domains and writes its own per-shard
        #: checkpoint files. None = all shards (single-host).
        self._shard_range = (tuple(int(x) for x in shard_range)
                             if shard_range is not None else None)
        if self._shard_range is not None and self._shards <= 1:
            raise ValueError(
                "shard_range= requires shards > 1 (or WF_SHARDS=N): with "
                "sharding off this host would silently supervise the WHOLE "
                "stream and the fleet would emit every output once per "
                "host — pass the global shard count alongside the slice")
        self._sharded = None          # last run's ShardedSupervisor (report)
        #: causal tracing opt-in (position-mode ids only — see
        #: _supervised_tracer); resolved at run()
        self._trace_arg = trace
        self._tracer = None
        from ..control import ControlConfig
        self._control = ControlConfig.resolve(control)
        batch_size = _warm_start_capacity(self._control, list(ops), source,
                                          batch_size)
        self._admission = _supervised_admission(
            self._control,
            getattr(source, "out_capacity", lambda b: b)(batch_size),
            "supervised-pipeline")
        #: self-driving remediation opt-in (remediation= kwarg or
        #: WF_REMEDIATION env — this driver has no monitoring= kwarg):
        #: BARRIER mode only.  The live Reporter-tick engine acts on
        #: wall-clock verdicts, which a replay cannot re-derive; here a
        #: BarrierRemediation (control/remediation.py) evaluates committed
        #: deterministic signals at every checkpoint barrier and its
        #: decision state rides the admission snapshot, so supervised
        #: replay re-derives the identical actions at the identical
        #: barriers (with remediation off, checkpoints are byte-for-byte
        #: today's)
        from ..control import remediation as _remediation
        self._remediation_arg = remediation if remediation is not None \
            else os.environ.get("WF_REMEDIATION")
        rem_policy = _remediation.resolve_barrier_policy(
            self._remediation_arg,
            admission=self._admission is not None, shards=self._shards)
        self._remediation = None
        if rem_policy is not None:
            cool = float(os.environ.get("WF_REMEDIATION_COOLDOWN_S",
                                        _remediation.DEFAULT_COOLDOWN_S))
            self._remediation = _remediation.BarrierRemediation(
                rem_policy,
                # the documented deterministic proxy: one barrier ~ one
                # second of the live engine's wall-clock cooldown
                cooldown_barriers=max(1, int(round(cool))),
                max_actions=int(os.environ.get(
                    "WF_REMEDIATION_MAX_ACTIONS",
                    _remediation.DEFAULT_MAX_ACTIONS)))
        self.batch_size = batch_size
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.max_restarts = int(max_restarts)
        self.spill_path = spill_path
        self.checkpoint_keep = max(1, int(checkpoint_keep))
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self.dead_letter = dead_letter       # DeadLetterQueue enables quarantine
        self.poison_threshold = max(1, int(poison_threshold))
        self.step_timeout = step_timeout
        self._faults_arg = faults
        # event-time sub-toggle (env-driven — this driver has no monitoring=
        # kwarg): geometry-binding; the histograms ride the state snapshots,
        # so a restore replays them (restored-or-reset, results bit-exact)
        from ..observability import event_time_enabled
        self.chain = CompiledChain(list(ops), source.payload_spec(),
                                   batch_capacity=batch_size,
                                   event_time=event_time_enabled(None))
        if self._shards > 1 and self.chain._tier_ops:
            raise ValueError(
                "shard-local supervision (shards > 1) does not yet compose "
                "with tiered keyed state — the per-op HostStore is one "
                "process-wide cold tier, so a per-shard restore could roll "
                "back a peer shard's spilled rows; run tiered tables with "
                "shards=1 (or size the hot tables per shard and keep "
                "tiered= off)")
        self.restarts = 0                     # total recoveries (observability)

    # -- checkpointing ------------------------------------------------------------------

    def _snapshot(self):
        # host copies: device state may be lost with the device on failure.
        # Tiered state settles FIRST (in-flight spills applied + cleared),
        # so (states, cold tiers) is a consistent pair — a restore discards
        # whatever was in flight and replay re-derives it.
        self.chain.tier_settle()
        states = [jax.tree.map(lambda a: np.asarray(a), st)
                  for st in self.chain.states]
        return (states, self.chain.tier_snapshot())

    def _restore(self, snap):
        states, tiers = snap
        self.chain.states = [jax.tree.map(jax.numpy.asarray, st)
                             for st in states]
        self.chain.tier_restore(tiers)

    # -- run ----------------------------------------------------------------------------

    def run(self):
        injector = _faults.resolve(self._faults_arg)
        self._tracer = _supervised_tracer(
            self._trace_arg, self.source.getName() + "-supervised")
        with _faults.activate(injector):
            try:
                if self._shards > 1:
                    self._sharded = ShardedSupervisor(self)
                    return self._sharded.run()
                return self._run()
            finally:
                if self._tracer is not None:
                    self._tracer.finish()

    def shard_report(self) -> dict:
        """Per-shard health rows (``names.py::SHARD_GAUGES``) of the last
        sharded run — empty when ``shards`` resolved to 1."""
        return self._sharded.shard_report() if self._sharded is not None \
            else {}

    def _run(self):
        snap = self._snapshot()
        snap_pos = 0                          # batches fully committed
        snap_cursor = None                    # source resume token at commit
        adm = self._admission
        rem = self._remediation
        snap_adm = adm.state() if adm is not None else None
        if rem is not None and adm is not None:
            # barrier-remediation decision state rides the admission
            # snapshot (present ONLY when remediation is on): a restore
            # resets the engine to the committed decision point, so replay
            # re-derives the same remaining actions at the same barriers
            snap_adm = dict(snap_adm, remediation={
                "engine": rem.state(), "rate": adm.current_rate()})
        pending = []                          # outputs since last commit
        outbox = []                           # committed, not yet delivered
        budget = self.max_restarts
        # backoff jitter is TIMING only — it never touches replayed data, so
        # nondeterminism here cannot break exactly-once output
        rng = random.Random()                 # wf-lint: allow[wall-clock]
        prev_sleep = self.backoff_base
        fail_pos = None                       # poison tracking: last fail position
        fail_streak = 0                       # consecutive failures at fail_pos
        quarantine_pos = None                 # armed: skip + dead-letter this pos
        quarantine_err = None

        def deliver_outbox():
            # resumable: each output leaves the outbox only after the sink took
            # it, so a sink fault mid-commit re-delivers the REMAINDER, not the
            # whole buffer (exactly-once even across delivery failures)
            while outbox:
                _faults.fire("sink.consume", pos=snap_pos,
                             pending=len(outbox))
                self.sink.consume(outbox[0])
                outbox.pop(0)

        def push_batch(batch, pos):
            # the span opens on the driver thread BEFORE the step-watchdog
            # worker runs the push
            span = _tracing.service(batch, "chain")
            out = _faults.call_with_timeout(
                lambda: self.chain.push(batch), self.step_timeout,
                stage="chain.step",
                pre=lambda: _faults.fire("chain.step", pos=pos))
            if span is not None:
                span.done()
                _tracing.carry(batch, out)
            # materialize now: surfaces async device faults HERE, before
            # the output could ever reach the sink
            pending.append(out.to_host() if self.sink is not None else out)

        def commit(pos):
            nonlocal snap, snap_pos, snap_cursor, snap_adm, budget
            with _journal.span("checkpoint", at_batch=pos,
                               outputs_committed=len(pending)):
                # fallible work FIRST: a failure here leaves the previous
                # commit fully intact (replay regenerates `pending`)
                new_snap = self._snapshot()   # np.asarray barrier: faults surface here
                new_cursor = self.source.cursor()
                new_adm = adm.state() if adm is not None else None
                if rem is not None and adm is not None:
                    # deterministic barrier remediation: the interval's
                    # committed shed ratio in, zero or more actuations out —
                    # evaluated BEFORE the commit point so the committed
                    # snapshot embeds the post-action rate + decision state
                    # (a failure below rolls everything back and the replay
                    # re-evaluates this barrier identically)
                    ratio = _interval_drop_ratio(snap_adm, new_adm)
                    signals = {} if ratio is None else {"drop_ratio": ratio}
                    for d in rem.on_barrier(pos, signals):
                        _apply_barrier_decision(d, adm)
                    new_adm = dict(new_adm, remediation={
                        "engine": rem.state(), "rate": adm.current_rate()})
                if self.spill_path:
                    from .checkpoint import save_chain
                    save_chain(self.chain, self.spill_path,
                               meta={"batches_done": pos},
                               keep=self.checkpoint_keep)
                # -- the commit point: pure assignments, cannot fail --
                snap, snap_pos, snap_cursor = new_snap, pos, new_cursor
                snap_adm = new_adm
                budget = self.max_restarts    # progress refills the budget
                if self.sink is not None:
                    outbox.extend(pending)
                pending.clear()
                deliver_outbox()

        pos = 0
        cur_pos = None                        # batch position being processed
        while True:
            try:
                # no batch in flight yet: a failure in reopen/delivery below
                # must NOT be attributed to the previous attempt's batch (a
                # stale cur_pos would inflate the poison streak and dead-letter
                # a healthy batch)
                cur_pos = None
                it = _reopen_source(self.source, self.batch_size, pos,
                                    snap_cursor)
                deliver_outbox()              # finish any interrupted delivery
                while True:
                    if quarantine_pos is not None and pos == quarantine_pos:
                        eos = self._quarantine(it, pos, quarantine_err)
                        quarantine_pos = quarantine_err = None
                        fail_pos, fail_streak = None, 0
                        if eos:
                            break
                        pos += 1
                        cur_pos = None
                        if pos - snap_pos >= self.checkpoint_every:
                            commit(pos)
                        continue
                    cur_pos = pos
                    _faults.fire("source.next", pos=pos)
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    # trace ids are position-derived (enforced by
                    # _supervised_tracer), so a replay of this position after
                    # a restore re-mints the identical id
                    _tracing.ingest(batch, pos)
                    if adm is not None and not adm.offer(batch, pos=pos):
                        # shed (deterministic positional bucket: replay from
                        # the committed bucket state re-sheds the same batch)
                        pos += 1
                        cur_pos = None
                        if pos - snap_pos >= self.checkpoint_every:
                            commit(pos)
                        continue
                    push_batch(batch, pos)
                    pos += 1
                    cur_pos = None
                    if pos - snap_pos >= self.checkpoint_every:
                        commit(pos)
                # EOS: flush, commit everything (inside the recovery loop — a
                # fault during flush or the final commit replays like any other)
                cur_pos = None
                for out in self.chain.flush():
                    pending.append(out.to_host()
                                   if self.sink is not None else out)
                commit(pos)
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:        # noqa: BLE001 — detection point
                # poison tracking: K consecutive failures at one batch position
                # arm the quarantine for that batch. Only the ARMING failure is
                # free; every other failure — including one at the quarantined
                # read, or elsewhere while armed — still spends budget, so a
                # deterministic error can never livelock the restore loop.
                armed_now = False
                if self.dead_letter is not None and cur_pos is not None:
                    if cur_pos == fail_pos:
                        fail_streak += 1
                    else:
                        fail_pos, fail_streak = cur_pos, 1
                    if (fail_streak >= self.poison_threshold
                            and quarantine_pos is None):
                        quarantine_pos, quarantine_err = cur_pos, e
                        armed_now = True
                if not armed_now:
                    if budget <= 0:
                        _journal.record("restart_exhausted", at_batch=pos,
                                        error=type(e).__name__)
                        raise RestartExhausted(
                            f"step failed {self.max_restarts + 1}x within one "
                            f"checkpoint interval (at batch {pos})") from e
                    budget -= 1
                self.restarts += 1
                _faults.bump("restarts")
                # watchdog-abandoned worker: let a slow-but-alive step finish
                # its mutation BEFORE the restore (a hung one never returns)
                _faults.join_abandoned_worker(e, self.step_timeout)
                # then close the failed attempt's trace spans (tagged
                # aborted) so the flight recorder never exports orphan
                # begins — the replayed batch reopens spans under the SAME
                # trace id (after the join, a dead worker's segment is safe
                # to sweep)
                _tracing.abort_open("restore")
                # a step that faulted mid-compile leaves its trace note
                # parked on this thread; drop it so the next successful
                # push cannot be charged the aborted attempt's duration
                _dh.clear_pending()
                with _restore_span(at_batch=pos,
                                   replay_from=snap_pos, attempt=self.restarts,
                                   error=type(e).__name__):
                    self._restore(snap)
                    if adm is not None:
                        adm.set_state(snap_adm)
                        if rem is not None and snap_adm is not None:
                            # set_state restores tokens/counters only — the
                            # refill RATE is config the remediation engine
                            # may have scaled, so re-pin both from the
                            # committed decision point
                            rst = snap_adm.get("remediation") or {}
                            rem.set_state(rst.get("engine") or {})
                            adm.set_rate(float(
                                rst.get("rate", adm.current_rate())))
                pending.clear()               # uncommitted: regenerated by replay
                pos = snap_pos                # replay uncommitted batches
                prev_sleep = _faults.backoff_sleep(
                    rng, prev_sleep, self.backoff_base, self.backoff_cap,
                    attempt=self.restarts)
        if self.sink is not None:
            self.sink.consume(None)
        for op in [self.source, *self.chain.ops,
                   *([self.sink] if self.sink is not None else [])]:
            op.close()
        return self.chain.result()

    def _quarantine(self, it, pos: int, error) -> bool:
        return _quarantine_skip(it, pos, error, self.dead_letter, "pipeline")


def _quarantine_skip(it, pos: int, error, dead_letter, driver: str,
                     **journal_fields) -> bool:
    """THE quarantine protocol, shared by both supervised drivers: dead-letter
    the batch at ``pos`` instead of processing it; returns True at EOS.
    Quarantine skips poison *processing*: the batch is still read from the
    source (no injection fired — the position is being skipped, not retried).
    A read that genuinely raises propagates (and spends restart budget
    upstream): an unreadable position cannot be skipped without source
    cooperation, and a loud failure beats silently truncating the stream or
    skewing every later batch's position by one."""
    try:
        batch = next(it)
    except StopIteration:
        return True
    _journal.record("dead_letter", at_batch=pos,
                    error=type(error).__name__ if error else None,
                    **journal_fields)
    _faults.bump("dead_letters")
    dead_letter.put(batch, pos=pos, error=error, driver=driver)
    return False


def _reopen_source(source, batch_size: int, pos: int, cursor):
    """(Re-)open a source iterator at committed batch ``pos``. Seekable sources
    (the :meth:`SourceBase.cursor` contract) resume in O(1) from the token
    captured at the last commit; legacy/user sources whose ``batches`` has a
    pre-cursor signature (detected via ``inspect.signature``, NOT by catching
    ``TypeError`` — which would silently mask genuine ``TypeError``s raised
    *by* the source) fall back to fast-forwarding a fresh iterator by ``pos``
    batches."""
    if pos and cursor is not None and _accepts_cursor(source.batches):
        return source.batches(batch_size, cursor=cursor)
    it = source.batches(batch_size)
    for _ in range(pos):
        next(it, None)
    return it


def _accepts_cursor(fn) -> bool:
    """Does ``fn`` (a ``batches`` callable) accept a ``cursor`` kwarg?"""
    try:
        params = inspect.signature(fn).parameters
    except (TypeError, ValueError):           # C callable etc.: assume modern
        return True
    return ("cursor" in params
            or any(p.kind is inspect.Parameter.VAR_KEYWORD
                   for p in params.values()))


# ------------------------------------------------------ shard-local supervision


def _fresh_states(chain: CompiledChain) -> list:
    """Freshly-initialized operator states for one shard (each shard owns an
    independent state set; the compiled executables are shared — ``step`` is
    a pure function of (states, batch), so N shards reuse ONE jit cache)."""
    states = [op.init_state(chain.specs[i]) for i, op in enumerate(chain.ops)]
    if chain.device is not None:
        states = [jax.device_put(s, chain.device) for s in states]
    return states


class _ShardRecoveryBase:
    """Shared per-shard failure bookkeeping of BOTH sharded drivers
    (:class:`ShardSupervisor` / :class:`_GraphShard`): poison-streak
    tracking with budget-free arming, the restart budget ->
    :class:`RestartExhausted` escalation, watchdog join + trace/health
    cleanup, recovery timing, decorrelated backoff, quarantine
    dead-lettering, and the ``names.py::SHARD_GAUGES`` report row — ONE
    copy of the protocol, so the two drivers cannot drift. Subclasses
    supply ``_restore_for_replay`` (put the shard back on its last commit),
    ``_count_restart`` (the driver's restart counter), ``_replay_from``
    (the journaled replay extent), ``_committed_tag`` (the report's
    committed position), ``_exhausted_message``, and ``_journal_fields``
    (driver-specific journal coordinates)."""

    def _init_recovery(self, idx: int, co) -> None:
        self.idx = int(idx)
        self.co = co
        self.budget = co.max_restarts
        self.restarts = 0
        self.dead_letters = 0
        self.reshard_moves = 0
        self.occupancy_tuples = 0           # committed live tuples processed
        self.interval_tuples = 0            # the JUST-committed interval's
        #                                     live tuples — the governor's
        #                                     load signal (a cumulative total
        #                                     would dilute a NEW hot spot
        #                                     under hours of old traffic and
        #                                     compare post-reshard skew
        #                                     against half-zero counters)
        self.last_recovery_s = 0.0
        self.prev_sleep = co.backoff_base
        # backoff jitter is TIMING only — never replayed data (the
        # SupervisedPipeline stance)
        self._rng = random.Random()         # wf-lint: allow[wall-clock]
        self._fail_key = None               # poison streak: last failing key
        self._streak = 0
        self._quarantine_key = None         # armed: dead-letter this key
        self._quarantine_err = None
        self._rec_t0 = None

    # -- driver hooks ------------------------------------------------------

    def _restore_for_replay(self) -> None:
        raise NotImplementedError

    def _count_restart(self) -> None:
        raise NotImplementedError

    def _replay_from(self):
        raise NotImplementedError

    def _committed_tag(self):
        raise NotImplementedError

    def _exhausted_message(self, key) -> str:
        raise NotImplementedError

    def _journal_fields(self, key) -> dict:
        return {}

    # -- the shared protocol ----------------------------------------------

    def _on_failure(self, e, key) -> None:
        """Shard-local recovery bookkeeping: poison tracking (only the
        ARMING failure is budget-free), budget, watchdog join, restore,
        backoff — the SupervisedPipeline recovery path scoped to ONE key
        range."""
        armed_now = False
        if self.co.dead_letter is not None and key is not None:
            if key == self._fail_key:
                self._streak += 1
            else:
                self._fail_key, self._streak = key, 1
            if (self._streak >= self.co.poison_threshold
                    and self._quarantine_key is None):
                self._quarantine_key, self._quarantine_err = key, e
                armed_now = True
        if not armed_now:
            if self.budget <= 0:
                _journal.record("restart_exhausted", shard=self.idx,
                                error=type(e).__name__,
                                **self._journal_fields(key))
                raise RestartExhausted(self._exhausted_message(key)) from e
            self.budget -= 1
        self.restarts += 1
        self._count_restart()
        _faults.bump("restarts")
        _faults.join_abandoned_worker(e, self.co.step_timeout)
        _tracing.abort_open("restore")
        _dh.clear_pending()
        import time as _t
        self._rec_t0 = _t.perf_counter()    # wf-lint: allow[wall-clock]
        _journal.record("shard_restore", shard=self.idx,
                        replay_from=self._replay_from(),
                        attempt=self.restarts, error=type(e).__name__,
                        **self._journal_fields(key))
        self._restore_for_replay()
        self.prev_sleep = _faults.backoff_sleep(
            self._rng, self.prev_sleep, self.co.backoff_base,
            self.co.backoff_cap, attempt=self.restarts)

    def _quarantine_hit(self, pos, sub, driver: str, **fields) -> None:
        """Dead-letter the armed sub-batch + disarm: the
        :func:`_quarantine_skip` protocol minus the re-read (the sub-batch
        is already buffered — and under sharding it IS the exact failing
        unit, never a group-mate; the caller blanks its replay slot)."""
        _journal.record("dead_letter", at_batch=pos, shard=self.idx,
                        error=(type(self._quarantine_err).__name__
                               if self._quarantine_err else None), **fields)
        _faults.bump("dead_letters")
        if self.co.dead_letter is not None:
            self.co.dead_letter.put(sub, pos=pos,
                                    error=self._quarantine_err,
                                    driver=driver)
        self.dead_letters += 1
        self._quarantine_key = self._quarantine_err = None
        self._fail_key, self._streak = None, 0

    def _finish_recovery_timing(self) -> None:
        if self._rec_t0 is not None:
            import time as _t
            self.last_recovery_s = \
                _t.perf_counter() - self._rec_t0  # wf-lint: allow[wall-clock]
            self._rec_t0 = None
            # shard-local restores feed the same cumulative restore-span
            # surface as whole-domain ones (the SLO "recovery_s" signal)
            _faults.bump("recovery_seconds", round(self.last_recovery_s, 6))

    def report(self) -> dict:
        """One ``names.py::SHARD_GAUGES`` row."""
        return {"occupancy_tuples": self.occupancy_tuples,
                "restarts": self.restarts,
                "last_recovery_s": round(self.last_recovery_s, 6),
                "dead_letters": self.dead_letters,
                "reshard_moves": self.reshard_moves,
                "committed_pos": self._committed_tag()}


class ShardSupervisor(_ShardRecoveryBase):
    """One per-shard supervision unit: owns its restart budget + decorrelated
    backoff, its exactly-once outbox, its poison-batch quarantine, its
    watchdog-wrapped steps, and (under ``spill_path``) its own checkpoint
    lineage file stem — the recovery domain of ONE key range.

    The unit borrows the coordinator's shared :class:`~windflow_tpu.runtime.
    pipeline.CompiledChain` for compute (states installed per push — executors
    are pure, so shards share executables) but fails independently: a fault in
    this shard's step restores THIS shard's snapshot and replays THIS shard's
    sub-batches (buffered since its last commit) while peer shards keep
    serving — no source rewind, no global restart. The buffer is
    device-resident but cheap: a sub-batch shares the input batch's
    immutable leaves and adds only its own validity mask, so the interval
    pins ~``checkpoint_every`` batches plus N masks — bounded, and freed at
    every commit. Recovery is journaled as ``shard_restore`` with the shard
    id and replay extent."""

    def __init__(self, idx: int, chain: CompiledChain, states, co):
        self._init_recovery(idx, co)        # co = the ShardedSupervisor
        self.chain = chain
        self.states = states
        self.snap = self._snapshot_states()
        self.snap_pos = 0
        self.replay = []                    # [(pos, sub)] since last commit;
        #                                     sub=None marks a quarantined slot
        self.pending = []                   # outputs since last commit
        self.outbox = []                    # committed, not yet delivered

    # -- state plumbing ----------------------------------------------------

    def _snapshot_states(self):
        return [jax.tree.map(lambda a: np.asarray(a), st)
                for st in self.states]

    def _restore_states(self):
        self.states = [jax.tree.map(jax.numpy.asarray, st)
                       for st in self.snap]

    def _step(self, sub):
        self.chain.states = self.states
        out = self.chain.push(sub)
        self.states = self.chain.states
        return out

    # -- the supervised per-shard push ------------------------------------

    def offer(self, pos: int, sub) -> None:
        """Process this shard's sub-batch of stream position ``pos`` —
        buffered for replay, pushed under the watchdog, recovered
        shard-locally on failure."""
        self.replay.append((pos, sub))
        self._drive(len(self.replay) - 1)

    def _drive(self, start: int) -> None:
        i = start
        while i < len(self.replay):
            pos, sub = self.replay[i]
            if sub is None:                 # previously quarantined slot
                i += 1
                continue
            if (self._quarantine_key is not None
                    and pos == self._quarantine_key):
                self._quarantine_hit(
                    pos, sub, f"sharded-pipeline:shard{self.idx}")
                self.replay[i] = (pos, None)
                i += 1
                continue
            span = _tracing.service(sub, "chain")
            try:
                _faults.fire("shard.kill", shard=self.idx, pos=pos)
                out = _faults.call_with_timeout(
                    lambda sub=sub: self._step(sub), self.co.step_timeout,
                    stage=f"shard{self.idx}.step")
                if span is not None:
                    span.done()
                    _tracing.carry(sub, out)
                self.pending.append(out.to_host()
                                    if self.co.sink is not None else out)
                i += 1
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:      # noqa: BLE001 — detection point
                self._on_failure(e, pos)
                i = 0                       # replay the whole interval
        self._finish_recovery_timing()

    # -- _ShardRecoveryBase hooks -----------------------------------------

    def _restore_for_replay(self) -> None:
        self._restore_states()
        self.pending.clear()                # uncommitted: replay regenerates

    def _count_restart(self) -> None:
        self.co.p.restarts += 1

    def _replay_from(self):
        return self.snap_pos

    def _committed_tag(self):
        return self.snap_pos

    def _exhausted_message(self, key) -> str:
        return (f"shard {self.idx} failed {self.co.max_restarts + 1}x "
                f"within one checkpoint interval (at batch {key})")

    def _journal_fields(self, key) -> dict:
        return {"at_batch": key}

    # -- EOS ---------------------------------------------------------------

    def flush_supervised(self) -> None:
        """EOS flush with the same shard-local recovery: a fault during the
        drain restores + replays this shard's interval, then re-flushes."""
        while True:
            try:
                self.chain.states = self.states
                outs = _faults.call_with_timeout(
                    self.chain.flush, self.co.step_timeout,
                    stage=f"shard{self.idx}.flush")
                self.states = self.chain.states
                for out in outs:
                    self.pending.append(out.to_host()
                                        if self.co.sink is not None else out)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:      # noqa: BLE001 — detection point
                self._on_failure(e, None)
                self._drive(0)

    # -- commit / delivery -------------------------------------------------

    def commit(self, pos: int, snap) -> None:
        """The shard's commit point (pure assignments — the coordinator did
        the fallible snapshot/save work first)."""
        self.snap = snap
        self.snap_pos = pos
        n_interval = 0
        for _p, sub in self.replay:
            if sub is not None:
                # one tiny device read per buffered sub-batch, at the commit
                # barrier only (the snapshot above already synced) — the
                # deterministic load signal the reshard governor consumes
                n_interval += int(np.asarray(sub.count()))
        self.occupancy_tuples += n_interval
        self.interval_tuples = n_interval
        self.replay.clear()
        self.budget = self.co.max_restarts
        if self.co.sink is not None:
            self.outbox.extend(self.pending)
        self.pending.clear()

    def deliver(self) -> None:
        """Resumable committed delivery (the deliver_outbox discipline)."""
        while self.outbox:
            _faults.fire("sink.consume", shard=self.idx,
                         pending=len(self.outbox))
            self.co.sink.consume(self.outbox[0])
            self.outbox.pop(0)


class ShardedSupervisor:
    """Thin control plane over N :class:`ShardSupervisor` units — the
    sharded form of :class:`SupervisedPipeline` (``shards=``/``WF_SHARDS``).

    The coordinator owns only what is genuinely global: the source iterator
    and its commit cursor, the deterministic admission bucket, the aligned
    checkpoint barrier (each shard snapshots independently; spills fan out
    per shard via ``checkpoint.save_sharded``), and the re-sharding plan.
    Everything that can fail per key range lives in the shard units; a global
    fault (source read, checkpoint save, admission) falls back to the classic
    whole-domain restore — every shard back to its snapshot, source reopened
    at the committed cursor.

    Live re-sharding runs at a checkpoint barrier as a deterministic
    two-phase handoff: (1) **drain** — every retiring shard's settled state
    seals to a per-shard handoff manifest (the HostStore-manifest wire
    format: named numpy arrays under the checkpoint sha256/atomic machinery);
    (2) **re-admit** — shards whose key range is unchanged adopt their state
    as-is, changed shards rebuild deterministically by replaying the
    committed prefix through the new ownership mask (the recovery path's
    replay discipline reused as the migration primitive: re-admitted keys
    claim table slots through the SAME cumsum fresh-slot allocation every
    ingest uses, and zero per-operator migration code exists to drift). The
    barrier position makes replay re-derive the identical layout, so chaos
    byte-identity holds with sharding and re-sharding active; a failure
    mid-handoff discards the sealed manifests and re-derives the move at the
    same barrier."""

    def __init__(self, p: "SupervisedPipeline"):
        from ..parallel.sharding import (ReshardPlan, ShardAssignment,
                                         make_splitter)
        self._make_splitter = make_splitter
        self.p = p
        self.chain = p.chain
        self.source = p.source
        self.sink = p.sink
        self.dead_letter = p.dead_letter
        self.poison_threshold = p.poison_threshold
        self.step_timeout = p.step_timeout
        self.max_restarts = p.max_restarts
        self.backoff_base = p.backoff_base
        self.backoff_cap = p.backoff_cap
        self.checkpoint_every = p.checkpoint_every
        self.spill_path = p.spill_path
        self.checkpoint_keep = p.checkpoint_keep
        self.assignment = ShardAssignment(p._shards)
        plan = ReshardPlan.resolve(p._reshard_arg)
        self.auto_reshard = plan == "auto"
        self.pending_plan = None if self.auto_reshard else plan
        self._reshard_due = False
        self.reshard_count = 0
        self._auto_stopped = False
        self._auto_prev_ratio = None
        self._auto_check_pending = False
        #: barrier remediation (p._remediation, or None): evaluated in
        #: _barrier on committed signals; decision state + scaled rate are
        #: kept in snap_rem (the coordinator's half of the snapshot) and
        #: restored by the global failure handler
        self.rem = p._remediation
        self.snap_rem = None
        self._rem_drop_ratio = None         # last committed interval's shed
        lo, hi = p._shard_range if p._shard_range is not None \
            else (0, p._shards)
        if not (0 <= lo <= hi <= p._shards):
            raise ValueError(
                f"shard_range={p._shard_range} is not a sub-range of "
                f"[0, {p._shards})")
        if (lo, hi) != (0, p._shards) and (self.auto_reshard
                                           or self.pending_plan is not None):
            raise ValueError(
                "live re-sharding under a multi-host shard_range is not "
                "yet supported — each host would have to apply the plan at "
                "the same barrier over DCN; run reshard on the single-host "
                "layout (or drop shard_range)")
        # an EMPTY slice (lo == hi) is legal: a fleet larger than the shard
        # count leaves surplus hosts supervising zero shards — they idle
        # through the stream instead of crashing at construction
        self.shards = ([ShardSupervisor(lo, self.chain, self.chain.states,
                                        self)] if lo < hi else [])
        for k in range(lo + 1, hi):
            self.shards.append(
                ShardSupervisor(k, self.chain, _fresh_states(self.chain),
                                self))
        # global commit state (the coordinator's half of the aligned barrier)
        self.commit_pos = 0
        self.snap_cursor = None
        self.snap_adm = None
        self._gbudget = self.max_restarts

    # -- run ---------------------------------------------------------------

    def run(self):
        p = self.p
        adm = p._admission
        self.snap_adm = adm.state() if adm is not None else None
        if self.rem is not None:
            self.snap_rem = {"engine": self.rem.state(),
                             "rate": (adm.current_rate()
                                      if adm is not None else None)}
        rng = random.Random()               # wf-lint: allow[wall-clock]
        prev_sleep = self.backoff_base
        split = None
        while True:
            try:
                if self._reshard_due:
                    # a failure interrupted the handoff: re-derive the move
                    # at the SAME barrier (commit_pos), so the faulted run's
                    # layout history matches the fault-free run's
                    self._apply_reshard(self.pending_plan, self.commit_pos,
                                        adm)
                    self._reshard_due = False
                    self.pending_plan = None
                    if self.auto_reshard:
                        # the retry path must arm the no-improvement check
                        # exactly like _barrier — a failure-interrupted
                        # auto reshard skipping the damping could cascade
                        self._auto_check_pending = True
                    split = None
                if split is None:
                    split = self._make_splitter(self.assignment,
                                                p._shard_key)
                it = _reopen_source(self.source, p.batch_size,
                                    self.commit_pos, self.snap_cursor)
                for s in self.shards:
                    s.deliver()             # finish any interrupted delivery
                pos = self.commit_pos
                while True:
                    _faults.fire("source.next", pos=pos)
                    try:
                        batch = next(it)
                    except StopIteration:
                        break
                    _tracing.ingest(batch, pos)
                    if adm is not None and not adm.offer(batch, pos=pos):
                        pos += 1
                        if pos - self.commit_pos >= self.checkpoint_every:
                            self._commit(pos, adm)
                            split = self._barrier(pos, adm, split)
                        continue
                    if self.shards:
                        subs = split(batch)
                        for s in self.shards:
                            sub = subs[s.idx]
                            _tracing.carry(batch, sub)
                            s.offer(pos, sub)
                    pos += 1
                    if pos - self.commit_pos >= self.checkpoint_every:
                        self._commit(pos, adm)
                        split = self._barrier(pos, adm, split)
                # EOS: per-shard flush (each with shard-local recovery), one
                # final commit, committed delivery
                for s in self.shards:
                    s.flush_supervised()
                self._commit(pos, adm)
                if self.pending_plan is not None or self._reshard_due:
                    # the stream ended before the plan's barrier: say so —
                    # a silently-dropped re-layout looks like a healthy run
                    _journal.record("reshard", aborted=True, at_pos=pos,
                                    error="stream ended before the plan's "
                                          "at_pos barrier")
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except RestartExhausted:
                raise
            except BaseException as e:      # noqa: BLE001 — global detection
                # a failure OUTSIDE any shard's step (source read, admission,
                # checkpoint save, sink delivery, reshard handoff): the
                # classic whole-domain recovery — every shard restores its
                # snapshot, the source reopens at the committed cursor
                if self._gbudget <= 0:
                    _journal.record("restart_exhausted",
                                    at_batch=self.commit_pos,
                                    error=type(e).__name__)
                    raise RestartExhausted(
                        f"sharded run failed {self.max_restarts + 1}x within "
                        f"one checkpoint interval (committed "
                        f"{self.commit_pos})") from e
                self._gbudget -= 1
                p.restarts += 1
                _faults.bump("restarts")
                _faults.join_abandoned_worker(e, self.step_timeout)
                _tracing.abort_open("restore")
                _dh.clear_pending()
                with _restore_span(at_batch=self.commit_pos,
                                   replay_from=self.commit_pos,
                                   shards=len(self.shards),
                                   error=type(e).__name__):
                    for s in self.shards:
                        s._restore_states()
                        s.pending.clear()
                        s.replay.clear()
                    if adm is not None:
                        adm.set_state(self.snap_adm)
                    if self.rem is not None and self.snap_rem is not None:
                        # set_state restores tokens only — re-pin the
                        # remediation-scaled rate + decision state from
                        # the committed decision point
                        self.rem.set_state(self.snap_rem.get("engine") or {})
                        if (adm is not None
                                and self.snap_rem.get("rate") is not None):
                            adm.set_rate(float(self.snap_rem["rate"]))
                    if self.spill_path:
                        from .checkpoint import discard_handoffs
                        dropped = discard_handoffs(self.spill_path)
                        if dropped:
                            _journal.record("reshard", discarded=True,
                                            files=dropped)
                prev_sleep = _faults.backoff_sleep(
                    rng, prev_sleep, self.backoff_base, self.backoff_cap,
                    attempt=p.restarts)
        if self.sink is not None:
            self.sink.consume(None)
        for op in [self.source, *self.chain.ops,
                   *([self.sink] if self.sink is not None else [])]:
            op.close()
        return self._merged_result()

    # -- aligned barrier ---------------------------------------------------

    def _commit(self, pos: int, adm) -> None:
        with _journal.span("checkpoint", at_batch=pos,
                           shards=len(self.shards),
                           outputs_committed=sum(len(s.pending)
                                                 for s in self.shards)):
            # fallible work FIRST (the commit() rule): per-shard snapshots,
            # the source cursor, and the fanned-out sharded spill
            new_snaps = [s._snapshot_states() for s in self.shards]
            new_cursor = self.source.cursor()
            new_adm = adm.state() if adm is not None else None
            drop_ratio = _interval_drop_ratio(self.snap_adm, new_adm)
            if self.spill_path and self.shards:
                # (an empty multi-host slice has nothing to persist)
                from .checkpoint import save_sharded
                save_sharded(new_snaps, self.spill_path,
                             layout=self.assignment.to_meta(),
                             meta={"batches_done": pos},
                             keep=self.checkpoint_keep,
                             shard_ids=[s.idx for s in self.shards])
            # -- the commit point: pure assignments --
            for s, sn in zip(self.shards, new_snaps):
                s.commit(pos, sn)
            self.commit_pos = pos
            self.snap_cursor = new_cursor
            self.snap_adm = new_adm
            self._rem_drop_ratio = drop_ratio
            self._gbudget = self.max_restarts
            for s in self.shards:
                s.deliver()

    def _barrier(self, pos: int, adm, split):
        """Post-commit reshard barrier: derive (auto mode) or apply a
        pending plan. Returns the (possibly rebuilt) splitter."""
        if (self.auto_reshard and self.pending_plan is None
                and not self._auto_stopped):
            from ..control.governor import recommend_reshard
            loads = {s.idx: s.interval_tuples for s in self.shards}
            vals = list(loads.values())
            total = sum(vals)
            # hot FRACTION (max/total): scale-free across shard counts —
            # a perfect split halves it, a single unsplittable hot key
            # keeps it (a max/mean ratio would double with N even on a
            # perfect split and mis-drive the damping below)
            frac = (max(vals) / total) if total > 0 else 0.0
            if (self._auto_check_pending
                    and total >= float(len(vals))):
                # the FIRST measured barrier after an auto reshard (and
                # only that one — a stale pre-reshard number must never
                # veto a genuinely new hot spot hours later): did the
                # doubling actually move the hot fraction?
                self._auto_check_pending = False
                if frac >= 0.9 * self._auto_prev_ratio:
                    # it bought nothing — the skew sits in a single hot
                    # key slot, which key % 2N cannot split: stop
                    # auto-resharding instead of cascading to max_shards
                    self._auto_stopped = True
                    _journal.record("reshard", aborted=True, at_pos=pos,
                                    error="auto-stopped: hot fraction "
                                          f"{frac:.2f} did not improve")
                self._auto_prev_ratio = None
            elif not self._auto_check_pending:
                plan = recommend_reshard(loads, self.assignment)
                if plan is not None:
                    self.pending_plan = plan
                    self._auto_prev_ratio = frac
        if self.rem is not None:
            # AFTER the auto derivation (so arbitration sees a plan derived
            # at this very barrier) and BEFORE the due-check (so an applied
            # remediation reshard lands at this same barrier)
            self._remediate_barrier(pos, adm)
        if (self.pending_plan is not None
                and pos >= self.pending_plan.at_pos):
            self._reshard_due = True
        if self._reshard_due:
            self._apply_reshard(self.pending_plan, pos, adm)
            self._reshard_due = False
            self.pending_plan = None
            if self.auto_reshard:
                self._auto_check_pending = True
            return self._make_splitter(self.assignment, self.p._shard_key)
        return split

    # -- barrier remediation -----------------------------------------------

    def _remediate_barrier(self, pos: int, adm) -> None:
        """Deterministic remediation at the sharded barrier: committed
        signals in (the interval shed ratio from :meth:`_commit`, the
        per-shard hot fraction from the just-committed ``interval_tuples``),
        actuations out.  Runs POST-commit; the engine state + scaled rate
        fold into ``snap_rem`` immediately (pure assignments), so a later
        failure restores to the post-decision state — the ``pending_plan``
        persistence pattern, which is what keeps faulted and fault-free
        runs byte-identical."""
        vals = [s.interval_tuples for s in self.shards]
        total = sum(vals)
        signals = {}
        if total > 0:
            signals["shard_skew"] = max(vals) / total
        if adm is not None and self._rem_drop_ratio is not None:
            signals["drop_ratio"] = self._rem_drop_ratio
        for d in self.rem.on_barrier(pos, signals):
            if d.get("applied") and d.get("actuator") == "reshard":
                if (self.auto_reshard or self.pending_plan is not None
                        or self._reshard_due):
                    # one resharder wins, deterministically: an armed
                    # auto-reshard governor (or an already-pending plan)
                    # owns the barrier — the remediation request stands
                    # down instead of double-moving keys
                    _apply_barrier_decision(
                        dict(d, applied=False, reason="arbitration"), None)
                    continue
                act = next(a for a in self.rem.actions
                           if a.name == d["action"])
                from ..control.governor import recommend_reshard
                plan = recommend_reshard(
                    {s.idx: s.interval_tuples for s in self.shards},
                    self.assignment, hot_fraction=act.target)
                if plan is None:
                    # the governor's planner disagrees (shard ceiling, or
                    # load below min_load) — journaled, not silent
                    _apply_barrier_decision(
                        dict(d, applied=False, reason="no_plan"), None)
                    continue
                self.pending_plan = plan
                _apply_barrier_decision(d, None)
            else:
                _apply_barrier_decision(d, adm)
        self.snap_rem = {"engine": self.rem.state(),
                         "rate": (adm.current_rate()
                                  if adm is not None else None)}

    # -- live re-sharding --------------------------------------------------

    def _apply_reshard(self, plan, pos: int, adm) -> None:
        old = self.assignment
        try:
            new = plan.apply_to(old)
        except ValueError as e:
            _journal.record("reshard", aborted=True, at_pos=pos,
                            error=str(e))
            return
        if new == old:
            return
        with _journal.span("reshard", from_shards=old.num_shards,
                           to_shards=new.num_shards, moves=len(new.moves),
                           at_pos=pos):
            # phase 1 — drain: seal every retiring shard's committed state
            # to a handoff manifest (spill mode), or just walk the injection
            # site (in-memory mode) — either way a failure here unwinds to
            # the global handler, which discards the seal; the plan stays
            # due and re-derives at this same barrier
            if self.spill_path:
                from .checkpoint import seal_handoff
                seal_handoff([s.snap for s in self.shards], self.spill_path,
                             layout=old.to_meta(), at_pos=pos)
            else:
                for s in self.shards:
                    _faults.fire("reshard.handoff", shard=s.idx, at_pos=pos)
            # phase 2 — re-admit: unchanged shards adopt their state;
            # changed shards rebuild by masked replay of the committed
            # prefix (outputs discarded — they were committed by the old
            # layout)
            self.shards = self._readmit(old, new, pos)
            self.assignment = new
            self.reshard_count += 1
            # the barrier checkpoint: the new layout commits immediately
            # (which also resets every unit's interval_tuples — the replay
            # buffers are empty at a barrier, so the first post-reshard
            # damping check can never see stale pre-reshard numbers), and a
            # later restore re-derives ownership from the checkpoint's
            # layout epoch — then the handoff seal is obsolete
            self._commit(pos, adm)
            if self.spill_path:
                from .checkpoint import discard_handoffs
                discard_handoffs(self.spill_path)

    def _readmit(self, old, new, upto: int) -> list:
        from ..parallel.sharding import affected_shards
        affected = affected_shards(old, new)
        keep = {}
        if new.num_shards == old.num_shards:
            for s in self.shards:
                if s.idx not in affected:
                    keep[s.idx] = s
        rebuilt = [i for i in range(new.num_shards) if i not in keep]
        fresh = {i: _fresh_states(self.chain) for i in rebuilt}
        if upto > 0 and rebuilt:
            split = self._make_splitter(new, self.p._shard_key)
            radm = _supervised_admission(
                self.p._control,
                getattr(self.source, "out_capacity",
                        lambda b: b)(self.p.batch_size),
                "sharded-readmit")
            it = self.source.batches(self.p.batch_size)
            for ppos in range(upto):
                try:
                    batch = next(it)
                except StopIteration:
                    break
                if radm is not None and not radm.offer(batch, pos=ppos):
                    continue
                subs = split(batch)
                for i in rebuilt:
                    self.chain.states = fresh[i]
                    try:
                        self.chain.push(subs[i])
                        fresh[i] = self.chain.states
                    except (KeyboardInterrupt, SystemExit):
                        raise
                    except BaseException as e:  # noqa: BLE001 — a poison
                        # sub-batch the live run already dead-lettered (or
                        # fresh poison under the new mask): quarantine it
                        # inline — the rebuild must not kill a run the
                        # recovery machinery already survived (fresh[i]
                        # keeps the pre-push states; push raised before
                        # the state assignment)
                        _journal.record("dead_letter", at_batch=ppos,
                                        shard=i, during_reshard=True,
                                        error=type(e).__name__)
                        _faults.bump("dead_letters")
                        if self.dead_letter is not None:
                            self.dead_letter.put(
                                subs[i], pos=ppos, error=e,
                                driver=f"sharded-readmit:shard{i}")
        units = []
        prior = {s.idx: s for s in self.shards}
        for i in range(new.num_shards):
            if i in keep:
                units.append(keep[i])
                continue
            u = ShardSupervisor(i, self.chain, fresh[i], self)
            u.snap_pos = upto
            u.reshard_moves = (prior[i].reshard_moves + 1 if i in prior
                               else 1)
            u.occupancy_tuples = (prior[i].occupancy_tuples if i in prior
                                  else 0)
            u.restarts = prior[i].restarts if i in prior else 0
            u.dead_letters = prior[i].dead_letters if i in prior else 0
            units.append(u)
        return units

    # -- results / reporting ----------------------------------------------

    def _merged_result(self):
        """ReduceSink results combined across shards with each sink's own
        associative ``combine`` (per-key rows live in exactly one shard, so
        the combine over shard partials equals the unsharded reduction)."""
        res = {}
        for i, op in enumerate(self.chain.ops):
            if isinstance(op, ReduceSink):
                acc = None
                for s in self.shards:
                    v = op.result(s.states[i])
                    acc = v if acc is None else jax.tree.map(op.combine,
                                                             acc, v)
                if acc is None:
                    # an empty multi-host slice still honors the result
                    # contract: the identity-valued reduction, so a fleet
                    # fold over per-host results never meets a None
                    acc = op.result(op.init_state(self.chain.specs[i]))
                res[op.name] = acc
        return res

    def shard_report(self) -> dict:
        return {s.idx: s.report() for s in self.shards}


# ---------------------------------------------------------------- graph level

class _CommitBufferSink:
    """Output-commit shim installed over a pipe's Sink during supervised graph
    execution: outputs materialize to host immediately (surfacing async device
    faults before anything reaches the user callback) but are delivered only at
    checkpoint commit — the aligned-checkpoint exactly-once rule. Committed
    outputs move to an ``outbox`` that survives restore and drains resumably,
    so a sink fault mid-commit neither loses nor duplicates results."""

    def __init__(self, real):
        self.real = real
        self.pending = []                     # uncommitted (discard on restore)
        self.outbox = []                      # committed, not yet delivered
        #: sharded-driver extensions: ``shard`` routes uncommitted outputs
        #: into per-shard buckets (a shard-local restore discards ONLY its
        #: own shard's pending); ``mute`` drops outputs entirely (the
        #: re-sharding prefix replay — those outputs were already committed
        #: and delivered by the retiring layout)
        self.shard = None
        self.mute = False
        self.pending_by = {}                  # shard idx -> [batches]

    def consume(self, batch):
        if batch is None:
            return                      # EOS delivered by the supervisor at the end
        if self.mute:
            return
        host = batch.to_host()
        if self.shard is None:
            self.pending.append(host)
        else:
            self.pending_by.setdefault(self.shard, []).append(host)

    def pending_count(self):
        return len(self.pending) + sum(len(v)
                                       for v in self.pending_by.values())

    def seal(self):
        self.outbox.extend(self.pending)
        self.pending = []
        for s in sorted(self.pending_by):     # deterministic shard order
            self.outbox.extend(self.pending_by[s])
        self.pending_by = {}

    def deliver(self):
        while self.outbox:
            _faults.fire("sink.consume", pending=len(self.outbox))
            self.real.consume(self.outbox[0])
            self.outbox.pop(0)

    def commit(self):
        self.seal()
        self.deliver()

    def discard(self, shard=None):
        if shard is None:
            self.pending = []                 # the outbox survives: committed
            self.pending_by = {}
        else:
            self.pending_by.pop(shard, None)


def _graph_snapshot(graph):
    """Host copies of every pipe's chain states + Ordering_Node state + the
    exhausted set (device state may be lost with the device on failure)."""
    chains, orders, tiers = [], [], []
    for mp in graph._all_pipes():
        if mp._chain is not None:
            # tiered state settles first (the SupervisedPipeline._snapshot
            # rule): the snapshot captures a consistent (state, store) pair
            mp._chain.tier_settle()
        chains.append(None if mp._chain is None else
                      [jax.tree.map(np.asarray, st) for st in mp._chain.states])
        tiers.append(None if mp._chain is None
                     else mp._chain.tier_snapshot())
        o = mp._ordering
        if o is not None:
            o.settle()      # apply any in-flight counts/trim before copying —
            #                 the snapshot must capture the settled pool
        orders.append(None if o is None else {
            "pending": (None if o._pending is None
                        else jax.tree.map(np.asarray, o._pending)),
            "chan": (None if o._pending_chan is None
                     else np.asarray(o._pending_chan)),
            "wm": np.asarray(o._wm_dev).tolist(),
            "next_id": int(np.asarray(o._next_id))})
    return {"chains": chains, "orders": orders, "tiers": tiers,
            "exhausted": set(graph._exhausted)}


def _graph_restore(graph, snap):
    import jax.numpy as jnp
    tiers = snap.get("tiers") or [None] * len(snap["chains"])
    for mp, ch, od, tr in zip(graph._all_pipes(), snap["chains"],
                              snap["orders"], tiers):
        if mp._chain is not None:
            if ch is None:
                mp._chain.reset_states()    # chain born after the checkpoint
            else:
                mp._chain.states = [jax.tree.map(jnp.asarray, st) for st in ch]
            mp._chain.tier_restore(tr)
        if od is None:
            mp._ordering = None             # recreated lazily, fresh
        else:
            o = mp._ordering or graph._ordering_of(mp)
            o._pending = (None if od["pending"] is None
                          else jax.tree.map(jnp.asarray, od["pending"]))
            o._pending_chan = (None if od["chan"] is None
                               else jnp.asarray(od["chan"]))
            from ..batch import CTRL_DTYPE
            from ..parallel.ordering import WM_NONE
            # legacy spilled checkpoints (pre device-resident watermarks) stored
            # 'wm' as a host list that may contain None for no-watermark
            # channels — map those to the WM_NONE sentinel on restore
            wm = [int(WM_NONE) if w is None else int(w) for w in od["wm"]]
            o._wm_dev = jnp.asarray(wm, CTRL_DTYPE)
            o._next_id = jnp.asarray(od["next_id"], CTRL_DTYPE)
            # snapshots from the pre-sorted-pool design (and any external
            # writer) may hold an unsorted pool; the merge/release path
            # REQUIRES the sorted invariant — re-establish it once here
            o.resort_pending()
    graph._exhausted = set(snap["exhausted"])


def run_graph_supervised(graph, *, checkpoint_every: int = 8,
                         max_restarts: int = 3,
                         backoff_base: float = 0.05, backoff_cap: float = 2.0,
                         dead_letter=None, poison_threshold: int = 3,
                         step_timeout: Optional[float] = None, faults=None,
                         control=None, shards=None,
                         reshard=None, shard_key=None, shard_range=None):
    """Supervised push-driver execution of a whole PipeGraph DAG: aligned
    checkpoints over every pipe's state (chains, Ordering_Nodes, the exhausted
    set), replayable sources fast-forwarded to the committed positions, and
    exactly-once delivery on every sink in the DAG. The DAG analogue of
    :class:`SupervisedPipeline` (the reference exits on failure, SURVEY §5) —
    with the same hardening: backoff with decorrelated jitter between restart
    attempts, poison-batch quarantine to ``dead_letter``, and a ``step_timeout``
    watchdog over each push."""
    from ..parallel.sharding import resolve_shards
    if shard_range is not None:
        raise ValueError(
            "shard_range= (the multi-host slice) is not yet supported on "
            "the supervised graph driver — it would be silently ignored "
            "and every host would supervise ALL shards (duplicated "
            "outputs); run one SupervisedPipeline per slice, or drop "
            "shard_range")
    injector = _faults.resolve(faults)
    with _faults.activate(injector):
        n_shards = resolve_shards(shards)
        if n_shards > 1:
            return _run_graph_sharded(
                graph, shards=n_shards, reshard=reshard,
                shard_key=shard_key,
                checkpoint_every=checkpoint_every,
                max_restarts=max_restarts, backoff_base=backoff_base,
                backoff_cap=backoff_cap, dead_letter=dead_letter,
                poison_threshold=poison_threshold,
                step_timeout=step_timeout, control=control)
        return _run_graph_supervised(
            graph, checkpoint_every=checkpoint_every,
            max_restarts=max_restarts, backoff_base=backoff_base,
            backoff_cap=backoff_cap, dead_letter=dead_letter,
            poison_threshold=poison_threshold, step_timeout=step_timeout,
            control=control)


def _run_graph_supervised(graph, *, checkpoint_every, max_restarts,
                          backoff_base, backoff_cap, dead_letter,
                          poison_threshold, step_timeout, control=None):
    # reject illegal trace configs BEFORE start() builds anything: raising
    # after start() would leak the just-activated tracer and the running
    # reporter thread (their finish lives in this function's finally, which
    # is not armed yet)
    from ..observability import TraceConfig
    _tcfg = (graph._tracer.config if graph._tracer is not None
             else TraceConfig.resolve(graph._trace_arg))
    if _tcfg is not None and _tcfg.ids != "position":
        raise ValueError(
            "supervised graph tracing requires TraceConfig(ids='position') — "
            "sequence ids would not replay across a restore (see "
            "_supervised_tracer)")
    graph.start()
    # explicit control= wins; else the graph's own (constructor/WF_CONTROL)
    if control is not None:
        from ..control import ControlConfig
        cfg = ControlConfig.resolve(control)
    else:
        cfg = graph._control
    # ONE shared deterministic bucket across every source: total-ingest rate
    # limiting whose shed pattern is a pure function of the (deterministic)
    # round-robin offer order — replay-stable with the state snapshotted below
    adm = _supervised_admission(cfg, graph.batch_size, "supervised-graph")
    pipes = graph._all_pipes()
    originals = {}
    for mp in pipes:
        if mp.sink is not None:
            originals[id(mp)] = mp.sink
            mp.sink = _CommitBufferSink(mp.sink)
    shims = [mp.sink for mp in pipes if id(mp) in originals]

    committed_pos = {id(mp): 0 for mp in graph._roots}
    committed_cursor = {id(mp): None for mp in graph._roots}
    snap = _graph_snapshot(graph)
    snap_adm = adm.state() if adm is not None else None
    budget = max_restarts
    graph.supervised_restarts = 0
    # backoff jitter is timing-only, not replayed data (see SupervisedPipeline)
    rng = random.Random()                     # wf-lint: allow[wall-clock]
    prev_sleep = backoff_base
    poison_threshold = max(1, int(poison_threshold))
    fail_key = None                           # (pipe id, position) streak
    fail_streak = 0
    quarantine_key = None
    quarantine_err = None
    cur_key = None                            # batch being processed right now

    def commit(pos):
        nonlocal snap, snap_adm, committed_pos, committed_cursor, budget
        with _journal.span("checkpoint", graph=graph.name,
                           batches_committed=sum(pos.values()),
                           outputs_pending=sum(len(sh.pending)
                                               for sh in shims)):
            # fallible first: snapshot + cursors; the previous commit stays
            # intact if either fails
            new_snap = _graph_snapshot(graph)   # np.asarray barrier: faults surface here
            new_cursor = {id(mp): mp.source.cursor() for mp in graph._roots}
            new_adm = adm.state() if adm is not None else None
            # the commit point: pure assignments
            snap = new_snap
            snap_adm = new_adm
            committed_pos = dict(pos)
            committed_cursor = new_cursor
            budget = max_restarts           # progress refills the budget
            for sh in shims:
                sh.seal()
            for sh in shims:
                sh.deliver()                # resumable committed delivery

    import time as _time
    mon_cfg = graph._monitor.config if graph._monitor is not None else None
    # trace/shed stream coordinates: root index, precomputed (not a per-batch
    # list scan) — the same coordinates the push driver uses
    root_idx = {id(mp): i for i, mp in enumerate(graph._roots)}

    def ingest(mp, batch, p, sampled):
        if sampled:
            # e2e latency sample — telemetry only, never replayed data
            graph._e2e_t0 = \
                _time.perf_counter()  # wf-lint: allow[wall-clock]
        _faults.call_with_timeout(
            lambda: graph._push(mp, batch), step_timeout,
            stage="graph.step",
            pre=lambda: _faults.fire("chain.step", pos=p))
        graph._e2e_t0 = None

    try:
        while True:
            try:
                # no batch in flight: reopen/delivery failures below must not
                # inherit the previous attempt's cur_key (poison attribution)
                cur_key = None
                pos = dict(committed_pos)
                iters = {mp_id: None for mp_id in pos}
                for mp in graph._roots:
                    iters[id(mp)] = _reopen_source(
                        mp.source, graph.batch_size, pos[id(mp)],
                        committed_cursor[id(mp)])
                for sh in shims:
                    sh.deliver()            # finish any interrupted delivery
                live = [mp for mp in graph._roots
                        if id(mp) not in graph._exhausted]
                since = 0
                n_pushed = 0
                while live:
                    for mp in list(live):
                        key = (id(mp), pos[id(mp)])
                        if quarantine_key is not None and key == quarantine_key:
                            eos = _quarantine_skip(
                                iters[id(mp)], pos[id(mp)], quarantine_err,
                                dead_letter, "graph", graph=graph.name)
                            quarantine_key = quarantine_err = None
                            fail_key, fail_streak = None, 0
                            if eos:
                                live.remove(mp)
                                graph._exhaust(mp)
                                continue
                            pos[id(mp)] += 1
                            since += 1
                            # with admission on, commits must wait for the
                            # round boundary (replay restarts the round at
                            # source 0 — a mid-round snapshot would resume
                            # the shared bucket against a rotated offer order)
                            if since >= checkpoint_every and adm is None:
                                cur_key = None
                                commit(pos)
                                since = 0
                            continue
                        cur_key = key
                        _faults.fire("source.next", pos=pos[id(mp)])
                        try:
                            batch = next(iters[id(mp)])
                        except StopIteration:
                            live.remove(mp)
                            cur_key = None
                            graph._exhaust(mp)
                            continue
                        # position-derived ids (same (stream, pos) coordinates
                        # as the push driver): replay re-mints identical ids
                        _tracing.ingest(batch, pos[id(mp)],
                                        stream=root_idx[id(mp)])
                        if adm is not None and not adm.offer(
                                batch, pos=pos[id(mp)],
                                stream=root_idx[id(mp)]):
                            # shed (deterministic: replay re-sheds this batch).
                            # NO mid-round commit here: a restore replays the
                            # round from source 0, so a snapshot taken mid-
                            # round would resume the shared bucket against a
                            # ROTATED offer order and re-shed different
                            # batches — commits happen only at round
                            # boundaries, where replay order is exactly the
                            # original round-robin
                            pos[id(mp)] += 1
                            since += 1
                            cur_key = None
                            continue
                        ingest(mp, batch, pos[id(mp)],
                               mon_cfg is not None
                               and mon_cfg.should_sample_e2e(n_pushed))
                        n_pushed += 1
                        pos[id(mp)] += 1
                        since += 1
                        cur_key = None
                    if since >= checkpoint_every:
                        commit(pos)
                        since = 0
                # EOS: ordering drain + topo flush (wait_end semantics)
                cur_key = None
                for mp in graph._topo_order():
                    if mp._ordering is not None:
                        for piece in graph._chunks(mp._ordering.flush()):
                            graph._push(mp, piece)
                    graph._flush_pipe(mp)
                commit(pos)
                break
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:      # noqa: BLE001 — detection point
                # only the ARMING failure is budget-free (see the pipeline
                # driver): a deterministic error while armed must still
                # exhaust, never livelock
                armed_now = False
                if dead_letter is not None and cur_key is not None:
                    if cur_key == fail_key:
                        fail_streak += 1
                    else:
                        fail_key, fail_streak = cur_key, 1
                    if (fail_streak >= poison_threshold
                            and quarantine_key is None):
                        quarantine_key, quarantine_err = cur_key, e
                        armed_now = True
                if not armed_now:
                    if budget <= 0:
                        _journal.record("restart_exhausted", graph=graph.name,
                                        error=type(e).__name__)
                        raise RestartExhausted(
                            f"graph step failed {max_restarts + 1}x within one "
                            f"checkpoint interval") from e
                    budget -= 1
                graph.supervised_restarts += 1
                _faults.bump("restarts")
                # watchdog-abandoned worker: let a slow-but-alive step finish
                # its mutation BEFORE the restore (a hung one never returns)
                _faults.join_abandoned_worker(e, step_timeout)
                # then close the failed attempt's trace spans (aborted) — no
                # orphan begins survive a recovery; replay reopens them under
                # the same position-derived ids (after the join, a dead
                # watchdog worker's segment is safe to sweep)
                _tracing.abort_open("restore")
                # a step that faulted mid-compile leaves its health-ledger
                # trace note parked on this thread; drop it so the next
                # push cannot be charged the aborted attempt's duration
                _dh.clear_pending()
                # a sampled push that RAISED leaves its t0 armed; clear it so
                # the first post-restore sink delivery doesn't record a bogus
                # e2e sample spanning the failure + restore + replay
                graph._e2e_t0 = None
                with _restore_span(graph=graph.name,
                                   attempt=graph.supervised_restarts,
                                   error=type(e).__name__):
                    _graph_restore(graph, snap)
                    if adm is not None:
                        adm.set_state(snap_adm)
                for sh in shims:
                    sh.discard()
                prev_sleep = _faults.backoff_sleep(
                    rng, prev_sleep, backoff_base, backoff_cap,
                    attempt=graph.supervised_restarts)
        for mp in pipes:
            if id(mp) in originals:
                originals[id(mp)].consume(None)
        for mp in pipes:
            if mp._chain is not None:
                mp._chain.sync_stats()
        for op in graph._operators:
            op.close()
        graph._ended = True
        return graph._results()
    finally:
        for mp in pipes:
            if id(mp) in originals:
                mp.sink = originals[id(mp)]
        if graph._tracer is not None:
            graph._tracer.finish()
        if graph._monitor is not None:
            graph._monitor.finish(graph)


# ------------------------------------------------- sharded graph supervision


def _graph_capture(graph) -> dict:
    """Device-reference capture of the whole graph's mutable state (chains,
    Ordering_Nodes, the exhausted set) — the cheap swap unit of the sharded
    graph driver's state multiplexing: N shards time-share ONE graph object
    (and its compiled executables), each owning an independent state set.
    Unlike :func:`_graph_snapshot` nothing crosses to host — capture/install
    are reference assignments (plus an Ordering_Node ``settle`` so a deferred
    pool trim can never land on another shard's installed pool)."""
    chains, orders = [], []
    for mp in graph._all_pipes():
        chains.append(None if mp._chain is None else list(mp._chain.states))
        o = mp._ordering
        if o is not None:
            o.settle()
        orders.append(None if o is None else
                      (o._pending, o._pending_chan, o._wm_dev, o._next_id))
    return {"chains": chains, "orders": orders,
            "exhausted": set(graph._exhausted)}


def _graph_install(graph, st: dict) -> None:
    for mp, ch, od in zip(graph._all_pipes(), st["chains"], st["orders"]):
        if mp._chain is not None:
            if ch is None:
                mp._chain.reset_states()  # chain born after this capture
            else:
                mp._chain.states = list(ch)
        if od is None:
            mp._ordering = None           # recreated lazily, fresh
        else:
            o = mp._ordering
            if o is None:
                o = graph._ordering_of(mp)
            else:
                o.settle()                # flush the outgoing shard's trim
            o._pending, o._pending_chan, o._wm_dev, o._next_id = od
    graph._exhausted = set(st["exhausted"])


def _graph_blank(graph) -> dict:
    """A freshly-initialized graph state set (new shards born mid-run)."""
    for mp in graph._all_pipes():
        if mp._chain is not None:
            mp._chain.reset_states()
        mp._ordering = None
    graph._exhausted = set()
    return _graph_capture(graph)


class _GraphShard(_ShardRecoveryBase):
    """The graph-driver ShardSupervisor: one key range's supervision unit
    over the whole DAG — own restart budget/backoff, poison quarantine keyed
    by (root pipe, position), replay buffer of its sub-batches AND root-EOS
    events since its last commit, per-shard pending buckets in every sink
    shim. A failure inside this shard's pushes restores + replays only this
    shard while peers keep serving (journaled ``shard_restore``; the
    failure protocol itself lives in :class:`_ShardRecoveryBase`)."""

    def __init__(self, idx: int, co, state: dict):
        self._init_recovery(idx, co)
        self.state = state                  # device-ref capture
        self.snap = None                    # np snapshot (set at first commit)
        self.snap_tag = 0                   # committed batches at last commit
        self.replay = []                    # ("batch", mp, pos, sub) | ("eos", mp)

    def offer_batch(self, mp, pos: int, sub) -> None:
        self.replay.append(("batch", mp, pos, sub))
        self._drive(len(self.replay) - 1)

    def offer_eos(self, mp) -> None:
        self.replay.append(("eos", mp))
        self._drive(len(self.replay) - 1)

    def _drive(self, start: int) -> None:
        g = self.co.graph
        _graph_install(g, self.state)
        self.co.select_shard(self.idx)
        i = start
        while i < len(self.replay):
            entry = self.replay[i]
            key = None
            try:
                if entry[0] == "eos":
                    g._exhaust(entry[1])
                    i += 1
                    continue
                _, mp, pos, sub = entry
                if sub is None:             # previously quarantined slot
                    i += 1
                    continue
                key = (id(mp), pos)
                if self._quarantine_key == key:
                    self._quarantine_hit(
                        pos, sub, f"sharded-graph:shard{self.idx}",
                        graph=self.co.graph.name)
                    self.replay[i] = ("batch", mp, pos, None)
                    i += 1
                    continue
                _faults.fire("shard.kill", shard=self.idx, pos=pos)
                _faults.call_with_timeout(
                    lambda mp=mp, sub=sub: g._push(mp, sub),
                    self.co.step_timeout, stage=f"shard{self.idx}.step")
                i += 1
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:      # noqa: BLE001 — detection point
                self._on_failure(e, key)
                self.co.select_shard(self.idx)
                i = 0
        self.state = _graph_capture(g)
        self._finish_recovery_timing()

    # -- _ShardRecoveryBase hooks -----------------------------------------

    def _restore_for_replay(self) -> None:
        self.co.graph._e2e_t0 = None
        _graph_restore(self.co.graph, self.snap)
        # refresh the capture to the RESTORED state: a replay entered from
        # outside _drive's loop (drain_supervised) re-installs self.state
        # first — a stale pre-failure capture there would double-apply
        # every batch buffered since the last commit
        self.state = _graph_capture(self.co.graph)
        for sh in self.co.shims:
            sh.discard(shard=self.idx)

    def _count_restart(self) -> None:
        self.co.graph.supervised_restarts += 1

    def _replay_from(self):
        return self.snap_tag

    def _committed_tag(self):
        return self.snap_tag

    def _exhausted_message(self, key) -> str:
        return (f"graph shard {self.idx} failed "
                f"{self.co.max_restarts + 1}x within one checkpoint "
                f"interval")

    def _journal_fields(self, key) -> dict:
        return {"graph": self.co.graph.name}

    def drain_supervised(self) -> None:
        """Final EOS drain (ordering flush + topo-order pipe flush) with the
        shard-local recovery loop."""
        g = self.co.graph

        def drain_once():
            for mp in g._topo_order():
                if mp._ordering is not None:
                    for piece in g._chunks(mp._ordering.flush()):
                        g._push(mp, piece)
                g._flush_pipe(mp)
        while True:
            _graph_install(g, self.state)
            self.co.select_shard(self.idx)
            try:
                _faults.call_with_timeout(
                    drain_once, self.co.step_timeout,
                    stage=f"shard{self.idx}.drain")
                self.state = _graph_capture(g)
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as e:      # noqa: BLE001 — detection point
                self._on_failure(e, None)
                self._drive(0)

    def commit(self, tag: int, snap) -> None:
        self.snap = snap
        self.snap_tag = tag
        n_interval = 0
        for entry in self.replay:
            if entry[0] == "batch" and entry[3] is not None:
                n_interval += int(np.asarray(entry[3].count()))
        self.occupancy_tuples += n_interval
        self.interval_tuples = n_interval
        self.replay.clear()
        self.budget = self.co.max_restarts


class _ShardedGraphRun:
    """Coordinator of the sharded graph driver — the ShardedSupervisor
    control plane over :class:`_GraphShard` units (``run_graph_supervised``
    with ``shards > 1``). Same division of labor as the pipeline form:
    sources/cursors/admission/commit barrier/reshard plan are global, every
    per-key-range failure domain is a shard unit."""

    def __init__(self, graph, *, shards, reshard, checkpoint_every,
                 max_restarts, backoff_base, backoff_cap, dead_letter,
                 poison_threshold, step_timeout, control,
                 shard_key=None):
        from ..parallel.sharding import (ReshardPlan, ShardAssignment,
                                         make_splitter)
        self._make_splitter = make_splitter
        self._shard_key = shard_key
        self.graph = graph
        if any(getattr(op, "_tier_cfg", None) is not None
               for op in graph._operators):
            raise ValueError(
                "shard-local supervision (shards > 1) does not yet compose "
                "with tiered keyed state (shared per-op HostStore) — run "
                "tiered tables with shards=1")
        self.checkpoint_every = max(1, int(checkpoint_every))
        self.max_restarts = int(max_restarts)
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self.dead_letter = dead_letter
        self.poison_threshold = max(1, int(poison_threshold))
        self.step_timeout = step_timeout
        self._control = control
        self.assignment = ShardAssignment(int(shards))
        plan = ReshardPlan.resolve(reshard)
        if plan == "auto":
            raise ValueError(
                "reshard='auto' is a pipeline-driver feature (the graph "
                "driver takes an explicit ReshardPlan)")
        self.pending_plan = plan
        self._reshard_due = False
        self.reshard_count = 0
        self.shards = []
        self.shims = []

    def select_shard(self, idx) -> None:
        for sh in self.shims:
            sh.shard = idx

    def mute_shims(self, on: bool) -> None:
        for sh in self.shims:
            sh.mute = on

    def run(self):
        graph = self.graph
        from ..observability import TraceConfig
        _tcfg = (graph._tracer.config if graph._tracer is not None
                 else TraceConfig.resolve(graph._trace_arg))
        if _tcfg is not None and _tcfg.ids != "position":
            raise ValueError(
                "supervised graph tracing requires TraceConfig("
                "ids='position') — sequence ids would not replay across a "
                "restore (see _supervised_tracer)")
        graph.start()
        if graph._monitor is not None:
            # per-shard health rows ride the monitoring snapshots (the
            # ``shards`` section wf_health.py / wf_state.py render and
            # merge_snapshots folds host-tagged)
            graph._monitor.registry.attach_shards(self.shard_report)
        if self._control is not None:
            from ..control import ControlConfig
            cfg = ControlConfig.resolve(self._control)
        else:
            cfg = graph._control
        adm = _supervised_admission(cfg, graph.batch_size, "sharded-graph")
        pipes = graph._all_pipes()
        originals = {}
        for mp in pipes:
            if mp.sink is not None:
                originals[id(mp)] = mp.sink
                mp.sink = _CommitBufferSink(mp.sink)
        self.shims = [mp.sink for mp in pipes if id(mp) in originals]
        # shard 0 adopts the graph's fresh state; peers get blank captures
        base_state = _graph_capture(graph)
        self.shards = [_GraphShard(0, self, base_state)]
        for k in range(1, self.assignment.num_shards):
            self.shards.append(_GraphShard(k, self, _graph_blank(graph)))
        # first commit baseline (np snapshots of the blank states)
        for s in self.shards:
            _graph_install(graph, s.state)
            s.snap = _graph_snapshot(graph)
        committed_pos = {id(mp): 0 for mp in graph._roots}
        committed_cursor = {id(mp): None for mp in graph._roots}
        snap_adm = adm.state() if adm is not None else None
        gbudget = self.max_restarts
        graph.supervised_restarts = 0
        rng = random.Random()               # wf-lint: allow[wall-clock]
        prev_sleep = self.backoff_base
        root_idx = {id(mp): i for i, mp in enumerate(graph._roots)}
        split = None

        def commit(pos):
            nonlocal committed_pos, committed_cursor, snap_adm, gbudget
            with _journal.span(
                    "checkpoint", graph=graph.name,
                    shards=len(self.shards),
                    batches_committed=sum(pos.values()),
                    outputs_pending=sum(sh.pending_count()
                                        for sh in self.shims)):
                new_snaps = []
                for s in self.shards:
                    _graph_install(graph, s.state)
                    new_snaps.append(_graph_snapshot(graph))
                new_cursor = {id(mp): mp.source.cursor()
                              for mp in graph._roots}
                new_adm = adm.state() if adm is not None else None
                # the commit point: pure assignments
                tag = sum(pos.values())
                for s, sn in zip(self.shards, new_snaps):
                    s.commit(tag, sn)
                committed_pos = dict(pos)
                committed_cursor = new_cursor
                snap_adm = new_adm
                gbudget = self.max_restarts
                for sh in self.shims:
                    sh.seal()
                for sh in self.shims:
                    sh.deliver()

        def barrier(pos):
            nonlocal split
            if (self.pending_plan is not None
                    and sum(pos.values()) >= self.pending_plan.at_pos):
                self._reshard_due = True
            if self._reshard_due:
                self._apply_reshard(self.pending_plan, committed_pos,
                                    adm, commit)
                self._reshard_due = False
                self.pending_plan = None
                split = self._make_splitter(self.assignment,
                                            self._shard_key)

        try:
            while True:
                try:
                    if self._reshard_due:
                        self._apply_reshard(self.pending_plan, committed_pos,
                                            adm, commit)
                        self._reshard_due = False
                        self.pending_plan = None
                        split = None
                    if split is None:
                        split = self._make_splitter(self.assignment,
                                                    self._shard_key)
                    pos = dict(committed_pos)
                    iters = {}
                    for mp in graph._roots:
                        iters[id(mp)] = _reopen_source(
                            mp.source, graph.batch_size, pos[id(mp)],
                            committed_cursor[id(mp)])
                    for sh in self.shims:
                        sh.deliver()        # finish interrupted delivery
                    # exhausted roots are global (source EOS at a position);
                    # read the union off shard snapshots' exhausted sets
                    done_roots = set()
                    for s in self.shards:
                        done_roots |= {pid for pid in s.snap["exhausted"]
                                       if pid in committed_pos}
                    live = [mp for mp in graph._roots
                            if id(mp) not in done_roots]
                    since = 0
                    while live:
                        for mp in list(live):
                            _faults.fire("source.next", pos=pos[id(mp)])
                            try:
                                batch = next(iters[id(mp)])
                            except StopIteration:
                                live.remove(mp)
                                for s in self.shards:
                                    s.offer_eos(mp)
                                continue
                            _tracing.ingest(batch, pos[id(mp)],
                                            stream=root_idx[id(mp)])
                            if adm is not None and not adm.offer(
                                    batch, pos=pos[id(mp)],
                                    stream=root_idx[id(mp)]):
                                # shed (deterministic; commits stay on round
                                # boundaries, the unsharded-driver rule)
                                pos[id(mp)] += 1
                                since += 1
                                continue
                            subs = split(batch)
                            for s, sub in zip(self.shards, subs):
                                _tracing.carry(batch, sub)
                                s.offer_batch(mp, pos[id(mp)], sub)
                            pos[id(mp)] += 1
                            since += 1
                        if since >= self.checkpoint_every:
                            commit(pos)
                            barrier(pos)
                            since = 0
                    for s in self.shards:
                        s.drain_supervised()
                    commit(pos)
                    if self.pending_plan is not None or self._reshard_due:
                        _journal.record("reshard", aborted=True,
                                        graph=graph.name,
                                        error="stream ended before the "
                                              "plan's at_pos barrier")
                    break
                except (KeyboardInterrupt, SystemExit):
                    raise
                except RestartExhausted:
                    raise
                except BaseException as e:  # noqa: BLE001 — global detection
                    if gbudget <= 0:
                        _journal.record("restart_exhausted",
                                        graph=graph.name,
                                        error=type(e).__name__)
                        raise RestartExhausted(
                            f"sharded graph run failed "
                            f"{self.max_restarts + 1}x within one "
                            f"checkpoint interval") from e
                    gbudget -= 1
                    graph.supervised_restarts += 1
                    _faults.bump("restarts")
                    _faults.join_abandoned_worker(e, self.step_timeout)
                    _tracing.abort_open("restore")
                    _dh.clear_pending()
                    graph._e2e_t0 = None
                    with _restore_span(graph=graph.name,
                                       shards=len(self.shards),
                                       attempt=graph.supervised_restarts,
                                       error=type(e).__name__):
                        for s in self.shards:
                            _graph_restore(graph, s.snap)
                            s.state = _graph_capture(graph)
                            s.replay.clear()
                        for sh in self.shims:
                            sh.discard()
                        if adm is not None:
                            adm.set_state(snap_adm)
                    prev_sleep = _faults.backoff_sleep(
                        rng, prev_sleep, self.backoff_base,
                        self.backoff_cap,
                        attempt=graph.supervised_restarts)
            for mp in pipes:
                if id(mp) in originals:
                    originals[id(mp)].consume(None)
            for mp in pipes:
                if mp._chain is not None:
                    mp._chain.sync_stats()
            for op in graph._operators:
                op.close()
            graph._ended = True
            graph._shard_report = self.shard_report()
            return self._merged_results()
        finally:
            for mp in pipes:
                if id(mp) in originals:
                    mp.sink = originals[id(mp)]
            if graph._tracer is not None:
                graph._tracer.finish()
            if graph._monitor is not None:
                graph._monitor.finish(graph)

    # -- live re-sharding --------------------------------------------------

    def _apply_reshard(self, plan, committed_pos, adm, commit) -> None:
        from ..parallel.sharding import affected_shards
        graph = self.graph
        old = self.assignment
        try:
            new = plan.apply_to(old)
        except ValueError as e:
            _journal.record("reshard", aborted=True, graph=graph.name,
                            error=str(e))
            return
        if new == old:
            return
        tag = sum(committed_pos.values())
        with _journal.span("reshard", graph=graph.name,
                           from_shards=old.num_shards,
                           to_shards=new.num_shards, moves=len(new.moves),
                           at_pos=tag):
            # phase 1 — drain: the committed snapshots ARE the sealed
            # manifests here (the graph driver checkpoints in memory); walk
            # the injection site per retiring shard so torn-handoff chaos
            # exercises the unwind
            for s in self.shards:
                _faults.fire("reshard.handoff", shard=s.idx, at_pos=tag)
            # phase 2 — re-admit
            affected = affected_shards(old, new)
            keep = {}
            if new.num_shards == old.num_shards:
                for s in self.shards:
                    if s.idx not in affected:
                        keep[s.idx] = s
            rebuilt = [i for i in range(new.num_shards) if i not in keep]
            fresh = {i: _graph_blank(graph) for i in rebuilt}
            if rebuilt and tag > 0:
                fresh = self._replay_prefix(new, rebuilt, fresh,
                                            committed_pos, adm)
            prior = {s.idx: s for s in self.shards}
            units = []
            for i in range(new.num_shards):
                if i in keep:
                    units.append(keep[i])
                    continue
                u = _GraphShard(i, self, fresh[i])
                _graph_install(graph, u.state)
                u.snap = _graph_snapshot(graph)
                u.snap_tag = tag
                u.reshard_moves = (prior[i].reshard_moves + 1
                                   if i in prior else 1)
                if i in prior:
                    u.occupancy_tuples = prior[i].occupancy_tuples
                    u.restarts = prior[i].restarts
                    u.dead_letters = prior[i].dead_letters
                units.append(u)
            self.shards = units
            self.assignment = new
            self.reshard_count += 1
            commit(dict(committed_pos))

    def _replay_prefix(self, new, rebuilt, fresh, committed_pos, adm):
        """Deterministic re-admission: replay the committed prefix of every
        root (the original round-robin interleave, a fresh deterministic
        admission bucket re-deriving the shed pattern) through the NEW
        ownership mask into the rebuilt shards' states — sinks muted: those
        outputs were committed and delivered by the retiring layout."""
        graph = self.graph
        split = self._make_splitter(new, self._shard_key)
        radm = _supervised_admission(
            self._resolved_cfg(), graph.batch_size, "sharded-readmit")
        iters, rpos = {}, {}
        for mp in graph._roots:
            iters[id(mp)] = mp.source.batches(graph.batch_size)
            rpos[id(mp)] = 0
        exhausted_at_commit = set()
        for s in self.shards:
            exhausted_at_commit |= {pid for pid in s.snap["exhausted"]
                                    if pid in committed_pos}
        self.mute_shims(True)
        try:
            live = [mp for mp in graph._roots if committed_pos[id(mp)] > 0
                    or id(mp) in exhausted_at_commit]
            while live:
                for mp in list(live):
                    target = committed_pos[id(mp)]
                    if rpos[id(mp)] >= target:
                        if id(mp) in exhausted_at_commit:
                            for i in rebuilt:
                                _graph_install(graph, fresh[i])
                                graph._exhaust(mp)
                                fresh[i] = _graph_capture(graph)
                        live.remove(mp)
                        continue
                    try:
                        batch = next(iters[id(mp)])
                    except StopIteration:
                        live.remove(mp)
                        continue
                    p = rpos[id(mp)]
                    rpos[id(mp)] += 1
                    if radm is not None and not radm.offer(batch, pos=p):
                        continue
                    subs = split(batch)
                    for i in rebuilt:
                        _graph_install(graph, fresh[i])
                        try:
                            graph._push(mp, subs[i])
                            fresh[i] = _graph_capture(graph)
                        except (KeyboardInterrupt, SystemExit):
                            raise
                        except BaseException as e:  # noqa: BLE001 — poison
                            # under the new mask: quarantine inline (the
                            # pipeline-readmit rule); fresh[i] keeps the
                            # pre-push capture, so the partial mutation a
                            # failed _push left in the graph is discarded
                            # by the next install
                            _journal.record("dead_letter", at_batch=p,
                                            shard=i, during_reshard=True,
                                            graph=graph.name,
                                            error=type(e).__name__)
                            _faults.bump("dead_letters")
                            if self.dead_letter is not None:
                                self.dead_letter.put(
                                    subs[i], pos=p, error=e,
                                    driver=f"sharded-readmit:shard{i}")
        finally:
            self.mute_shims(False)
        return fresh

    def _resolved_cfg(self):
        if self._control is not None:
            from ..control import ControlConfig
            return ControlConfig.resolve(self._control)
        return self.graph._control

    def _merged_results(self):
        """Per-shard ReduceSink partials combined with each sink's own
        associative ``combine`` (the pipeline driver's merged-result rule)."""
        graph = self.graph
        pipes = graph._all_pipes()
        res = {}
        for j, mp in enumerate(pipes):
            if mp._chain is None:
                continue
            for i, op in enumerate(mp._chain.ops):
                if isinstance(op, ReduceSink):
                    acc = None
                    for s in self.shards:
                        st = s.state["chains"][j]
                        if st is None:      # shard never pushed this pipe
                            continue
                        v = op.result(st[i])
                        acc = v if acc is None else jax.tree.map(op.combine,
                                                                 acc, v)
                    if acc is not None:
                        res[op.name] = acc
        return res

    def shard_report(self) -> dict:
        return {s.idx: s.report() for s in self.shards}


def _run_graph_sharded(graph, *, shards, reshard, checkpoint_every,
                       max_restarts, backoff_base, backoff_cap, dead_letter,
                       poison_threshold, step_timeout, control,
                       shard_key=None):
    run = _ShardedGraphRun(
        graph, shards=shards, reshard=reshard, shard_key=shard_key,
        checkpoint_every=checkpoint_every, max_restarts=max_restarts,
        backoff_base=backoff_base, backoff_cap=backoff_cap,
        dead_letter=dead_letter, poison_threshold=poison_threshold,
        step_timeout=step_timeout, control=control)
    return run.run()
