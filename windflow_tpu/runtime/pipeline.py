"""Linear operator chain compiled to one XLA program + host run loop.

This is the execution core under MultiPipe: a chain of operators between shuffle-free
boundaries compiles into ONE jitted ``step(states, batch) -> (states, out_batch)``.
That is the TPU answer to the reference's two composition mechanisms at once:

- ``chain()`` / ``ff_comb`` fusion (``wf/pipegraph.hpp:1272-1318``): adjacent operators
  run with no queue hop — here they are *literally one program*, with XLA fusing the
  elementwise bodies (the optimization the reference can only approximate with
  ``ff_comb``).
- the GPU micro-batch overlap (``was_batch_started`` double buffering,
  ``wf/map_gpu_node.hpp:224-340``): JAX dispatch is async — the host loop builds/feeds
  batch N+1 while the device executes batch N; no explicit stream management needed.

EOS protocol: the source exhausts; then each stateful operator's ``flush`` drains
residual state (partial windows etc. — reference ``eosnotify``, ``wf/win_seq.hpp:468-529``)
and the flushed batches cascade through the *remaining* suffix of the chain. All flush
paths reuse the same compiled shapes (mask padding, never shape change).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Sequence

import jax

from ..basic import DEFAULT_BATCH_SIZE
from ..batch import Batch
from ..observability import device_health as _dh
from ..observability import journal as _journal
from ..observability import tracing as _tracing
from ..operators.base import Basic_Operator
from ..operators.sink import ReduceSink, Sink
from ..operators.source import SourceBase


def resolve_batch_hint(ops) -> Optional[int]:
    """Smallest withBatch hint among ``ops`` (each hint is a per-operator
    capacity ceiling — reference GPU ``batch_len``, wf/builders_gpu.hpp:115-122 —
    and a fused chain cannot exceed any member's ceiling); None if no op
    carries a hint."""
    hints = [op._batch_hint for op in ops
             if getattr(op, "_batch_hint", None) is not None]
    return min(hints) if hints else None


def record_source_launch(source, batch: Batch) -> None:
    """Per-batch source-side stats: one launch + the H2D bytes the framed batch
    cost (a DeviceSource generates inside the compiled program — zero
    transfer). The SINGLE place H2D bytes are counted (wf/stats_record.hpp:
    76-80); every driver loop calls this as it pulls a batch from a source.
    Byte size is static per capacity — cached on the source after the first
    batch of each shape (the tree walk is driver-loop overhead otherwise)."""
    from ..operators.source import DeviceSource
    if isinstance(source, DeviceSource):
        hd = 0
    else:
        cache = getattr(source, "_nbytes_by_cap", None)
        if cache is None:
            cache = source._nbytes_by_cap = {}
        cap = batch.capacity
        hd = cache.get(cap)
        if hd is None:
            hd = cache[cap] = _batch_nbytes(batch)
    source.get_StatsRecords()[0].record_launch(hd_bytes=hd)


def _batch_nbytes(batch: Batch) -> int:
    """Static byte size of a batch from shapes/dtypes (no device access)."""
    total = 0
    for leaf in jax.tree.leaves(batch):
        size = 1
        for d in getattr(leaf, "shape", ()):
            size *= d
        total += size * jax.numpy.dtype(getattr(leaf, "dtype", "float32")).itemsize
    return total


def _health_sig(tree) -> str:
    """Shape/dtype/weak-type signature of a (possibly abstract) pytree —
    the compile-ledger cache key component.  Safe at trace time: tracers
    expose shape/dtype/weak_type without concretization."""
    parts = []
    for leaf in jax.tree.leaves(tree):
        parts.append(f"{getattr(leaf, 'shape', ())}/"
                     f"{getattr(leaf, 'dtype', '?')}"
                     + ("w" if getattr(leaf, "weak_type", False) else ""))
    return ";".join(parts)


# a chain instance is driven by exactly ONE thread — the pipeline driver,
# a segment thread (ThreadedPipeline), or a pipe body (threaded PipeGraph);
# states/_steps/counters are plain unlocked fields on that basis.  The
# reporter thread only READS (snapshot-time state readbacks tolerate
# observing the previous push's list reference — each element is an
# immutable pytree).  Recorded for the WF260 concurrency lint.
class CompiledChain:  # wf-lint: single-writer[driver, stage]
    """Compile ``ops`` (no source/sink) into suffix-runnable jitted programs.

    ``step_from(i)`` runs ops[i:] — used both for the main path (i=0) and for EOS
    flush cascades starting after operator i."""

    #: every Nth push is timed dispatch->completion (block_until_ready) and the
    #: sample recorded as the entry op's service time (wf/stats_record.hpp:76-80
    #: tracks per-svc service time; sampling keeps the async overlap intact on
    #: the other N-1 pushes)
    SERVICE_SAMPLE_EVERY = 16

    def __init__(self, ops: Sequence[Basic_Operator], in_spec: Any,
                 batch_capacity: int = None, event_time: bool = None):
        self.ops = list(ops)
        # event-time observability toggle (MonitoringConfig.event_time) —
        # GEOMETRY-BINDING: stateful operators add lateness histograms to
        # their state pytrees, so it must be known before init_state below.
        # None consults WF_MONITORING/WF_MONITORING_EVENT_TIME; the drivers
        # pass their own monitoring= resolution.  Off (the default) leaves
        # state and compiled programs byte-for-byte unchanged.
        if event_time is None:
            from ..observability import event_time_enabled
            event_time = event_time_enabled(None)
        self.event_time = bool(event_time)
        for op in self.ops:
            # set unconditionally: operator instances reused across chains
            # must not keep a previous chain's toggle (sticky True would
            # compile histograms into an off chain's state)
            op._event_time = self.event_time
        self._drop_synced = {}      # id(op) -> {kind: last journaled value}
        self.specs = [in_spec]          # specs[i] = input payload spec of ops[i]
        if batch_capacity is None:
            batch_capacity = resolve_batch_hint(self.ops)
        # withDevice placement (reference withGPU device selection,
        # wf/builders_gpu.hpp:123-130): the chain is ONE fused program, so one
        # device per chain — conflicting per-op hints are a build error.
        devs = {id(op._device): op._device for op in self.ops
                if getattr(op, "_device", None) is not None}
        if len(devs) > 1:
            names = ", ".join(f"{op.getName()}->{op._device}" for op in self.ops
                              if getattr(op, "_device", None) is not None)
            raise ValueError(
                f"conflicting withDevice hints inside one fused chain ({names}); "
                f"a CompiledChain executes as one XLA program on one device — "
                f"split the graph at the device boundary")
        self.device = next(iter(devs.values())) if devs else None
        cap = batch_capacity
        for op in self.ops:
            if cap is not None:
                op.bind_geometry(cap)
                cap = op.out_capacity(cap)
            self.specs.append(op.out_spec(self.specs[-1]))
        self.states = [op.init_state(self.specs[i]) for i, op in enumerate(self.ops)]
        if self.device is not None:
            self.states = [jax.device_put(s, self.device) for s in self.states]
        #: operators with tiered keyed state (state/tiered.py): their
        #: controllers' maintain runs after every push — the async
        #: HBM->host spill settle point. Empty (one falsy check per push)
        #: unless some operator was built with tiered= on.
        self._tier_ops = [j for j, op in enumerate(self.ops)
                          if op.tier_controllers()]
        self._steps = {}
        self._push_count = 0
        self._nbytes_cache = {}     # (from_op, in capacity) -> (in, out bytes)
        self._flush_warmed = set()  # input capacities _warm_flush has seen
        #: stage label for the health ledger's compile + device-time
        #: attribution (the flight-recorder stage convention): drivers
        #: overwrite it with their real stage name — ThreadedPipeline
        #: ``seg<i>``, PipeGraph ``pipe<i>``, Pipeline/supervised ``chain``
        self.label = "chain"

    def warm(self, capacity: int) -> None:
        """Trace + compile the full-chain step for ``capacity`` WITHOUT
        touching operator state: a functional dry-run on an all-invalid batch
        whose outputs are discarded (``step`` is pure, so the real states are
        untouched). jax.jit caches one executable per input shape, so after
        warming every rung of a capacity ladder the autotuner's switches pick
        cached executables — the hot path never pays a trace/compile."""
        b = Batch.empty(capacity, self.specs[0])
        if self.device is not None:
            b = jax.device_put(b, self.device)
        hl, t0c = self._health_begin("warm")
        self._step_fn(0)(tuple(self.states), b)
        self._health_end(hl, t0c, 0, b)

    def _warm_flush(self, capacity: int) -> None:
        """Trace + compile the steps the EOS flush pushes an operator's open
        windows through (``ops[i + 1:]`` behind every operator that knows
        its flush's capacity, ``flush_capacity``), on all-invalid batches
        whose outputs are discarded, as :meth:`warm` does: at a stream's
        first batch of ``capacity``, so that its end compiles nothing."""
        c = capacity
        for i, op in enumerate(self.ops[:-1]):
            cap = op.flush_capacity(c)
            c = op.out_capacity(c)
            if cap is None:
                continue
            b = Batch.empty(cap, self.specs[i + 1])
            if self.device is not None:
                b = jax.device_put(b, self.device)
            hl, t0c = self._health_begin("warm")
            self._step_fn(i + 1)(tuple(self.states), b)
            self._health_end(hl, t0c, i + 1, b)

    def reset_states(self) -> None:
        """Re-initialize every operator's state (supervised replay of a chain
        that did not exist at the last checkpoint)."""
        self.states = [op.init_state(self.specs[i])
                       for i, op in enumerate(self.ops)]
        if self.device is not None:
            self.states = [jax.device_put(s, self.device) for s in self.states]

    @property
    def out_spec(self):
        return self.specs[-1]

    def _step_fn(self, i: int):
        """The jitted step over ops[i:].  Each operator's ``apply`` runs
        under its ``jax.named_scope`` (``Class:name``): HLO metadata only, so
        the profiler's device operations name their operator while
        executables and cost pins stay as they were."""
        if i not in self._steps:
            def step(states, batch):
                # compile-ledger hook: this line runs at TRACE time only
                # (host side effect, zero equations in the program — the
                # compiled executable and the perf-gate pins are byte-for-
                # byte identical with the ledger on or off); one module-
                # attribute load + None check per trace when health is off
                hl = _dh.get_active()
                if hl is not None:
                    hl.note_trace(self.label, i, "step", _health_sig(batch),
                                  capacity=jax.tree.leaves(batch)[0].shape[0]
                                  if jax.tree.leaves(batch) else None)
                states = list(states)
                for j in range(i, len(self.ops)):
                    with jax.named_scope(self.ops[j].scope_name()):
                        states[j], batch = self.ops[j].apply(states[j], batch)
                return tuple(states), batch
            self._steps[i] = jax.jit(step)
        return self._steps[i]

    # -- runtime-health ledger (MonitoringConfig.health) --------------------

    def _health_begin(self, cause: str):
        """(ledger, t0) when the health ledger is active: arm the cause and
        the trace-count mark so :meth:`_health_end` can journal any compile
        this invocation triggers with its measured duration.  (None, 0.0)
        when health is off — the only off-path cost is this None check."""
        hl = _dh.get_active()
        if hl is None:
            return None, 0.0
        hl.set_cause(cause)
        return hl, time.perf_counter()

    def _health_end(self, hl, t0c: float, from_op: int, example) -> None:
        """Commit any trace notes the invocation parked: duration = the
        whole first call (trace + XLA compile + first execution — the
        honest number a user waits for), cost = AOT cost/memory analysis of
        the just-compiled program (suppressed re-lowering, so it cannot
        count as another compile)."""
        if hl is None:
            return
        pending = hl.take_pending()
        if not pending:
            return
        cost = self._health_cost(hl, from_op, example)
        hl.commit_pending(time.perf_counter() - t0c, cost,
                          op=self.ops[from_op].getName() if self.ops else "",
                          notes=pending)

    def _health_cost(self, hl, from_op: int, example) -> dict:
        """AOT cost-analysis flops/bytes + executable memory footprint of
        the program just compiled for (from_op, example's shapes).
        One extra lowering on the health path only (``hl.cost_analysis``
        gates it); every failure degrades to an empty dict — the compile
        event then simply carries no cost columns."""
        if not hl.cost_analysis:
            return {}
        hl._suppress(True)
        try:
            fn = self._steps[from_op]
            compiled = fn.lower(tuple(self.states), example).compile()
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            out = {}
            if ca.get("flops") is not None:
                out["flops"] = int(ca["flops"])
            if ca.get("bytes accessed") is not None:
                out["bytes_accessed"] = int(ca["bytes accessed"])
            ma = compiled.memory_analysis()
            if ma is not None:
                out["argument_bytes"] = int(ma.argument_size_in_bytes)
                out["output_bytes"] = int(ma.output_size_in_bytes)
                out["temp_bytes"] = int(ma.temp_size_in_bytes)
                out["code_bytes"] = int(ma.generated_code_size_in_bytes)
            return out
        except Exception:   # noqa: BLE001 — cost columns are best-effort,
            return {}       # backend-dependent telemetry; the compile event
            #                 itself (cause/key/duration) always lands
        finally:
            hl._suppress(False)

    # -- tiered keyed state (state/tiered.py) -------------------------------

    def _tier_maintain(self) -> None:
        """Per-push maintenance of every tiered operator: advance the async
        spill pipeline (start/consume ``copy_to_host_async`` copies, apply
        settled prefixes to the host stores, one cached clear executable
        when a prefix settled) + the compaction cadence. Called by
        ``push`` right after the state update — the cadence
        is therefore a pure function of stream position, so supervised
        replay re-walks it exactly."""
        for j in self._tier_ops:
            st = self.states[j]
            for t in self.ops[j].tier_controllers():
                st = t.maintain(st)
            self.states[j] = st

    def tier_settle(self) -> None:
        """Synchronously drain every tiered operator's spill outbox into
        its host store and drop in-flight copies — the pre-snapshot
        barrier (supervised snapshots settle first, so a checkpoint always
        captures a consistent (state, store) pair)."""
        for j in self._tier_ops:
            st = self.states[j]
            for t in self.ops[j].tier_controllers():
                st = t.settle(st)
            self.states[j] = st

    def tier_snapshot(self):
        """Host-memory copies of every tiered operator's cold tier (after
        :meth:`tier_settle` — callers settle first); None when no operator
        is tiered."""
        if not self._tier_ops:
            return None
        return {j: [t.manifest() for t in self.ops[j].tier_controllers()]
                for j in self._tier_ops}

    def tier_restore(self, snap) -> None:
        """Restore the cold tiers from a :meth:`tier_snapshot`; in-flight
        spill copies of the failed attempt are discarded (the restored
        device states still hold those rows in their outboxes — replay
        re-derives the spill)."""
        for j in self._tier_ops:
            ctls = self.ops[j].tier_controllers()
            mans = (snap or {}).get(j)
            for i, t in enumerate(ctls):
                if mans is not None and i < len(mans):
                    t.restore(mans[i])
                else:
                    t.discard_inflight()

    def tier_manifests(self) -> dict:
        """Flat ``{"tier<op>_<ctl>_<name>": np.ndarray}`` map of every cold
        tier — the checkpoint-file representation (``runtime/checkpoint.py``
        stores these beside the ``op<i>_leaf<j>`` state arrays, covered by
        the same per-array sha256)."""
        out = {}
        for j in self._tier_ops:
            for i, t in enumerate(self.ops[j].tier_controllers()):
                for k, v in t.manifest().items():
                    out[f"tier{j}_{i}_{k}"] = v
        return out

    def tier_restore_manifests(self, arrays: dict) -> None:
        """Restore cold tiers from checkpoint arrays (the
        :meth:`tier_manifests` layout). A checkpoint written before an
        operator was tiered simply has no ``tier*`` keys — the fresh empty
        store stands (the legacy grown-field stance of ``load_chain``)."""
        for j in self._tier_ops:
            for i, t in enumerate(self.ops[j].tier_controllers()):
                prefix = f"tier{j}_{i}_"
                man = {k[len(prefix):]: v for k, v in arrays.items()
                       if k.startswith(prefix)}
                if man:
                    t.restore(man)
                else:
                    t.discard_inflight()

    def state_footprints(self) -> dict:
        """Per-operator state-pytree footprint in bytes, from static
        shape/dtype metadata (the specs bound at construction — no device
        access, no sync).  THE memory-ledger row tiered state (ROADMAP 3)
        sizes its promotion/eviction against."""
        out: dict = {}
        for op, st in zip(self.ops, self.states):
            n = 0
            for leaf in jax.tree.leaves(st):
                size = 1
                for d in getattr(leaf, "shape", ()):
                    size *= d
                n += size * jax.numpy.dtype(
                    getattr(leaf, "dtype", "float32")).itemsize
            name = op.getName()
            out[name] = out.get(name, 0) + n
        return out

    def push(self, batch: Batch, from_op: int = 0) -> Batch:
        """Run one batch through ops[from_op:]; updates states; returns the out batch."""
        self._push_count += 1
        sampled = self._sampled(self._push_count)
        pos = _tracing.pos_of(batch)
        with _tracing.span("wf.chain.push", pos=pos, sampled=int(sampled)):
            return self._push(batch, from_op, sampled, pos)

    def _sampled(self, c: int) -> bool:
        """Is launch number ``c`` timed to completion?  Never #1 — it would
        time JIT trace + XLA compile, not service. Early launches sample at
        powers of two (2, 4, 8) so SHORT runs still carry service-time
        percentiles (the monitoring snapshot's p50/p95/p99 needs samples);
        steady state samples every SERVICE_SAMPLE_EVERY to keep the async
        pipeline overlapped."""
        return ((c % self.SERVICE_SAMPLE_EVERY) == 0
                or (1 < c < self.SERVICE_SAMPLE_EVERY and (c & (c - 1)) == 0))

    def _push(self, batch: Batch, from_op: int, sampled: bool,
              pos: Optional[int]) -> Batch:
        if self.device is not None:
            batch = jax.device_put(batch, self.device)
        if from_op == 0 and batch.capacity not in self._flush_warmed:
            self._flush_warmed.add(batch.capacity)
            self._warm_flush(batch.capacity)
        hl, t0c = self._health_begin("push")
        t0 = time.perf_counter() if sampled else 0.0
        # the jit call alone (argument flattening, PjRt Execute): its start is
        # the program's lower anchor for the step's start on the device
        with _tracing.span("wf.chain.dispatch", pos=pos):
            states, out = self._step_fn(from_op)(tuple(self.states), batch)
        if sampled:
            # device-time attribution (health): dispatch returned async, so
            # t_disp - t0 is host-dispatch overhead and t_done - t_disp the
            # device completion wait — riding the block_until_ready this
            # sampled push already pays
            t_disp = time.perf_counter()
            with _tracing.span("wf.chain.sync", pos=pos):
                jax.block_until_ready(out)
            t_done = time.perf_counter()
            service_s = t_done - t0
            # never attribute a launch that COMPILED (pending trace notes):
            # its "dispatch" span is trace+XLA time, and the sums never
            # decay — one such sample would mis-flag the stage forever
            if (hl is not None and not hl.has_pending()
                    and hl.service_sample()):
                hl.note_service(self.label, dispatch_s=t_disp - t0,
                                device_s=t_done - t_disp)
            # sampled compiled-program launch -> the event journal (no-op —
            # one None check — unless monitoring activated a journal)
            if _journal.get_active() is not None:
                _journal.record(
                    "launch", op=self.ops[from_op].getName() if self.ops else "",
                    from_op=from_op, push=self._push_count,
                    service_s=round(service_s, 6))
        else:
            service_s = None
        if hl is not None:
            # after the timed window, so the cost-analysis lowering of a
            # compile event can never inflate the service sample
            self._health_end(hl, t0c, from_op, batch)
        self.states = list(states)
        if self._tier_ops:
            self._tier_maintain()
        if sampled:
            # the sampled push already paid the block_until_ready: fold the
            # event-time drop readback (lateness_drop journal events carrying
            # this batch's trace coordinates) into the same sync
            self._journal_drops(batch)
        # batch counters are per-op; ops[from_op:] execute as ONE fused compiled
        # program, so num_kernels counts ONE launch, attributed to the entry op
        # (reference GPU Stats_Record fields, wf/stats_record.hpp:76-80).
        # Byte counts come from static shapes (capacity x itemsize — the
        # reference counts sizeof(tuple_t) per tuple), no device sync; static
        # per capacity, so cached after the first push of each shape.
        ck = (from_op, batch.capacity)
        if ck in self._nbytes_cache:
            in_bytes, out_bytes = self._nbytes_cache[ck]
        else:
            in_bytes, out_bytes = _batch_nbytes(batch), _batch_nbytes(out)
            self._nbytes_cache[ck] = (in_bytes, out_bytes)
        for j in range(from_op, len(self.ops)):
            rec = self.ops[j].get_StatsRecords()[0]
            rec.batches_received += 1
            rec.batches_sent += 1
            rec.bytes_received += in_bytes
            rec.bytes_sent += out_bytes
        if self.ops:
            # H2D bytes are counted ONCE, at the source that framed the batch
            # (Pipeline.run / pipegraph source loops) — counting the possible
            # device_put above too would double-count the same transfer.
            # Sampled launches carry the batch's trace id (if any) as the
            # service-histogram exemplar — the p99 service bucket then names
            # a concrete batch in the flight recorder.
            self.ops[from_op].get_StatsRecords()[0].record_launch(
                service_s,
                exemplar=(None if service_s is None
                          else _tracing.tid_of(batch)))
        return out

    def flush(self) -> List[Batch]:
        """EOS: drain every operator in order, cascading flushed batches through the
        remaining suffix. Returns the list of final out-batches produced."""
        outs: List[Batch] = []
        with _tracing.span("wf.chain.flush"):
            for i, op in enumerate(self.ops):
                while True:
                    self.states[i], fb = op.flush(self.states[i])
                    if fb is None:
                        break
                    if i + 1 < len(self.ops):
                        outs.append(self.push(fb, from_op=i + 1))
                    else:
                        outs.append(fb)
        return outs

    def sync_stats(self) -> None:
        """Pull device-resident stats counters (e.g. window OLD-drop counts)
        into every operator's host Stats_Record — called at EOS and by the
        metrics registry at snapshot time."""
        if self._tier_ops:
            # EOS barrier: in-flight spills settle so the final counters /
            # tier sections (and any following checkpoint) are consistent
            self.tier_settle()
        for op, st in zip(self.ops, self.states):
            op.collect_stats(st)
        self._journal_drops(None)

    def _journal_drops(self, batch) -> None:
        """Event-time drop forensics: journal ``lateness_drop`` events for
        every operator drop counter that advanced since the last readback,
        carrying the PR 5 trace coordinates of ``batch`` (the sampled batch
        whose existing block_until_ready this read rides — zero extra
        syncs; EOS passes None).  ``wf_trace.py``/``wf_state.py`` join the
        events to traced batches on (tid, pos).  No-op unless event_time
        monitoring is on AND a journal is active."""
        if not self.event_time or _journal.get_active() is None:
            return
        tid = _tracing.tid_of(batch) if batch is not None else None
        for op, st in zip(self.ops, self.states):
            try:
                counters = op.drop_counters(st)
            except Exception:   # noqa: BLE001 — telemetry must not kill a run
                continue
            if not counters:
                continue
            prev = self._drop_synced.setdefault(id(op), {})
            for kind, val in counters.items():
                delta = int(val) - prev.get(kind, 0)
                if delta <= 0:
                    continue
                prev[kind] = int(val)
                fields = {"op": op.getName(), "kind": kind, "n": delta,
                          "total": int(val)}
                if tid is not None:
                    fields["tid"] = int(tid)
                    fields["pos"] = _tracing.trace_pos(tid)
                _journal.record("lateness_drop", **fields)

    def result(self):
        """Results of any ReduceSink-style terminal ops (device accumulators)."""
        res = {}
        for i, op in enumerate(self.ops):
            if isinstance(op, ReduceSink):
                res[op.name] = op.result(self.states[i])
        return res


class Pipeline:
    """Source -> ops... -> sink, run batch-at-a-time. The minimum end-to-end slice
    (SURVEY §7 step 3); MultiPipe builds on this per-segment."""

    def __init__(self, source: SourceBase, ops: Sequence[Basic_Operator],
                 sink: Optional[Sink] = None, *,
                 batch_size: Optional[int] = None, prefetch: int = 0,
                 monitoring=None, control=None, trace=None):
        self.source = source
        self.sink = sink
        if batch_size is None:
            # withBatch hints are capacity ceilings; explicit batch_size wins
            batch_size = resolve_batch_hint(ops) or DEFAULT_BATCH_SIZE
        self.batch_size = batch_size
        self.prefetch = int(prefetch)   # >0: overlapped host framing + H2D transfers
        #: prefetch pause hook: the backpressure governor (or any external
        #: controller) sets this Event to suspend the prefetch worker
        import threading as _threading
        self.prefetch_pause = _threading.Event()
        chain_ops = list(ops)
        cap = getattr(source, "out_capacity", lambda b: b)(batch_size)
        #: adaptive control plane (None = off, the default — today's exact
        #: code path, no controller state). Resolved HERE (not lazily like
        #: monitoring) because the capacity ladder governs chain geometry:
        #: autotuning binds the operators at the ladder's top rung so every
        #: smaller rung runs inside the same (oversized-is-safe) rings.
        from ..control import ControlConfig
        self._control = ControlConfig.resolve(control)
        self._ladder = None
        chain_cap = cap
        if self._control is not None and self._control.autotune:
            from ..control import build_ladder
            self._ladder = build_ladder(cap, up=self._control.ladder_up,
                                        down=self._control.ladder_down)
            chain_cap = self._ladder[-1]
        # event-time sub-toggle resolved at CONSTRUCTION (geometry-binding,
        # the control= convention): the histograms live in operator state
        from ..observability import event_time_enabled
        self.chain = CompiledChain(chain_ops, source.payload_spec(),
                                   batch_capacity=chain_cap,
                                   event_time=event_time_enabled(monitoring))
        #: None = consult WF_MONITORING; True/str/MonitoringConfig = enable
        #: (see observability.MonitoringConfig.resolve); resolved lazily so an
        #: env change between construction and run() is honored
        self._monitoring_arg = monitoring
        self._monitor = None
        #: per-batch causal tracing (None = consult WF_TRACE; see
        #: observability.tracing.TraceConfig.resolve) — same lazy resolution
        self._trace_arg = trace
        self._tracer = None

    def _make_controller(self):
        """Assemble the run-scoped control pieces from the resolved config:
        (autotuner, rebatcher, admission) — any of them None when that
        sub-system is off."""
        cfg = self._control
        if cfg is None:
            return None, None, None
        from ..control import (CapacityAutotuner, Rebatcher, TuningCache,
                               admission_from_config, chain_signature,
                               device_kind, payload_signature, tuning_key)
        base = getattr(self.source, "out_capacity",
                       lambda b: b)(self.batch_size)
        tuner = rebatcher = None
        if cfg.autotune and self._ladder and len(self._ladder) > 1:
            cache = key = None
            if cfg.cache_path:
                cache = TuningCache(cfg.cache_path)
                key = tuning_key(chain_signature(self.chain.ops),
                                 payload_signature(self.chain.specs[0]),
                                 device_kind())
            tuner = CapacityAutotuner(
                self._ladder, start_capacity=base,
                decide_every=cfg.decide_every,
                settle_batches=cfg.settle_batches,
                improve_threshold=cfg.improve_threshold,
                cache=cache, cache_key=key,
                name=self.source.getName() + "-pipeline")
            rebatcher = Rebatcher(base)
            if tuner.capacity != base:        # cache warm start: actuate now
                rebatcher.set_target(tuner.capacity)
            if cfg.prewarm:
                # a converged warm start only ever runs the cached rung plus
                # the base shape (rebatcher drain/passthrough) — compiling
                # the rest of the ladder would spend seconds on executables
                # that cannot execute
                warm_caps = ({tuner.capacity, base} if tuner.converged
                             else self._ladder)
                with _dh.cause("autotune_prewarm"):
                    for c in sorted(warm_caps):
                        self.chain.warm(c)
        admission = admission_from_config(cfg, base, driver="pipeline")
        return tuner, rebatcher, admission

    def run(self):
        import time as _time
        from ..observability import Monitor, MonitoringConfig, TraceConfig, \
            Tracer
        cfg = MonitoringConfig.resolve(self._monitoring_arg)
        if cfg is not None and self._monitor is None:
            self._monitor = Monitor(cfg, self.source.getName() + "-pipeline")
            self._monitor.registry.register_pipeline(self)
            self._monitor.start()
        mon = self._monitor
        tcfg = TraceConfig.resolve(self._trace_arg)
        if tcfg is not None and self._tracer is None:
            self._tracer = Tracer(tcfg,
                                  self.source.getName() + "-pipeline").start()
        tuner, rebatcher, admission = self._make_controller()
        if mon is not None and tuner is not None:
            mon.registry.attach_gauge("control_chosen_capacity",
                                      lambda: tuner.capacity)
        if mon is not None and mon.remediation is not None:
            # bind the actuators THIS run owns (control/remediation.py):
            # unbound actuators skip loudly.  scale_rate is lock-guarded;
            # the re-climb request is an Event the drive loop consumes at
            # its next on_batch boundary — both safe from the Reporter tick
            if admission is not None:
                mon.remediation.bind(
                    "admission_rate",
                    lambda a, _adm=admission: _adm.scale_rate(a.factor,
                                                              a.floor))
            if tuner is not None:
                def _reclimb(_a, _t=tuner):
                    _t.request_reclimb()
                    return {"tuners": [_t.name]}
                mon.remediation.bind("autotune_reclimb", _reclimb)
        try:
            batches = (self.source.batches_prefetched(
                           self.batch_size, self.prefetch,
                           pause_event=self.prefetch_pause)
                       if self.prefetch else self.source.batches(self.batch_size))
            n = 0

            def drive(b):
                # push one chain-capacity batch + sink delivery + sampling;
                # with control off this runs exactly once per source batch —
                # today's code path
                nonlocal n
                # e2e sampling needs a host sink (its consume blocks on the
                # materialized result — the "receipt"); in-graph ReduceSinks
                # have no host receipt to time
                sampled = (mon is not None and self.sink is not None
                           and mon.config.should_sample_e2e(n))
                t0 = _time.perf_counter() if sampled else 0.0
                with _tracing.span("chain", b):
                    out = self.chain.push(b)
                _tracing.carry(b, out)
                if self.sink is not None:
                    with _tracing.span("sink", out):
                        self.sink.consume(out)
                if sampled:
                    # Sink.consume materialized the batch on the host (or the
                    # sink is in-graph) — this is a true source-framing ->
                    # host-receipt sample through device compute + transfer
                    mon.registry.record_e2e(_time.perf_counter() - t0,
                                            exemplar=_tracing.tid_of(b))
                n += 1
                if tuner is not None:
                    newcap = tuner.on_batch(b.capacity)
                    if newcap is not None:
                        rebatcher.set_target(newcap)

            n_offered = 0
            for batch in batches:
                record_source_launch(self.source, batch)
                _tracing.ingest(batch, n_offered)
                # shed journal coordinate = the offered position trace ids
                # are minted from (n counts DRIVEN batches, which drifts past
                # a shed — the report joins on offered positions)
                admitted = (batch,) if admission is None \
                    else admission.offer(batch, pos=n_offered)
                n_offered += 1
                for ab in admitted:
                    for rb in (rebatcher.feed(ab) if rebatcher is not None
                               else (ab,)):
                        drive(rb)
            _journal.record("eos", pipeline=self.source.getName())
            if admission is not None:
                for ab in admission.drain():      # bounded held tail
                    for rb in (rebatcher.feed(ab) if rebatcher is not None
                               else (ab,)):
                        drive(rb)
            if rebatcher is not None:
                for rb in rebatcher.drain():      # partial up-rung buffer
                    drive(rb)
            for out in self.chain.flush():
                if self.sink is not None:
                    self.sink.consume(out)
            if self.sink is not None:
                self.sink.consume(None)  # empty-optional EOS signal (wf/sink.hpp)
            self.chain.sync_stats()
            for op in [self.source, *self.chain.ops,
                       *([self.sink] if self.sink is not None else [])]:
                op.close()            # closing_func per replica (svc_end parity)
            return self.chain.result()
        finally:
            if self._tracer is not None:
                self._tracer.finish()
            if mon is not None:
                mon.finish(self)
