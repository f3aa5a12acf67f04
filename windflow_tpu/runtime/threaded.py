"""Threaded pipeline-parallel scheduler over the native SPSC runtime.

The reference runs ONE OS THREAD PER NODE connected by FastFlow lock-free queues
(``ff_pipeline::run()``, ``wf/pipegraph.hpp:1522-1533``); on TPU the per-*operator*
thread model would serialize on the single device queue, so the threaded scheduler
parallelizes at the *segment* level: each pipeline segment (a compiled chain) gets a
host thread that pops micro-batch handles from its input SPSC ring, dispatches its
device program (async — the device pipelines across segments), and pushes the output
handle downstream. The source thread generates/uploads batches; the sink thread
consumes results. Host threads overlap Python dispatch of stage i+1 with device
execution of stage i — the ``was_batch_started`` double-buffering of the reference GPU
nodes (``wf/map_gpu_node.hpp:224-340``) generalized to the whole pipeline.

Thread pinning mirrors the reference default mapping (one core per stage,
disable like NO_DEFAULT_MAPPING with ``pin=False``).

Failure hardening (chaos-harness findings):

- a failing stage **drains its input ring to EOS** before exiting, so an
  upstream producer can never block forever on a full ring behind a dead
  consumer (the deadlock the seed code had);
- ``run()`` closes source/ops/sink even when a stage failed, then re-raises
  the first stage error;
- ``heartbeat_timeout`` starts a watchdog thread over per-stage heartbeats: a
  stage that stops beating (hung device step, stalled queue) is journaled as
  ``watchdog_stale`` and counted — a hang becomes a detectable fault instead
  of a silent wedge. Detection only: the threaded driver has no replay
  machinery, supervision lives in ``SupervisedPipeline``. Attribution caveat:
  a stage blocked *pushing* into a full ring behind the stalled stage also
  stops beating, so ``watchdog_stale`` lists the whole blocked chain — the
  root cause is the furthest-downstream stale stage.

Fault-injection sites (``runtime/faults.py``): ``source.next`` per source
batch, ``queue.stall`` per popped item (stall kind = the latency fault the
watchdog must notice), ``chain.step`` per segment push, ``sink.consume`` per
sink delivery.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import List, Optional, Sequence

from ..basic import DEFAULT_BATCH_SIZE
from ..native import SPSCQueue, pin_thread
from ..observability import journal as _journal
from ..observability import tracing as _tracing
from ..operators.sink import Sink
from ..operators.source import SourceBase
from . import faults as _faults
from .pipeline import CompiledChain

_EOS = object()

#: how long a failed stage keeps draining its input waiting for the upstream
#: EOS marker before giving up (the upstream's ``finally`` always sends one,
#: so this only bounds pathological cases like a killed producer thread)
_DRAIN_TIMEOUT_S = 30.0


def _resolve_edge_capacity(spec, name: str, index: int, default: int = 8) -> int:
    """Per-edge SPSC ring capacity: ``spec`` is one int for every edge (the
    historical behavior), a dict keyed by edge name or index (missing edges
    fall back to the default), or a callable ``(name, index) -> int``."""
    if callable(spec):
        cap = spec(name, index)
    elif isinstance(spec, dict):
        cap = spec.get(name, spec.get(index, default))
    else:
        cap = spec
    cap = int(cap)
    if cap < 1:
        raise ValueError(f"edge {name!r}: queue capacity must be >= 1, got {cap}")
    return cap


class ThreadedPipeline:
    """Source -> [segment chains...] -> sink, one host thread per stage."""

    def __init__(self, source: SourceBase, segments: Sequence[Sequence],
                 sink: Optional[Sink] = None, *,
                 batch_size: int = DEFAULT_BATCH_SIZE,
                 queue_capacity=8, pin: bool = True,
                 heartbeat_timeout: Optional[float] = None, faults=None,
                 prefetch: int = 0, control=None, trace=None,
                 monitoring=None):
        self.source = source
        self.sink = sink
        #: telemetry opt-in (monitoring= kwarg or WF_MONITORING env — the
        #: Pipeline/PipeGraph convention, previously missing on this
        #: driver): segment chains + SPSC ring-depth gauges registered, e2e
        #: latency sampled source-framing -> sink-receipt across the stage
        #: threads, and the SLO engine riding the Reporter tick
        self._monitoring_arg = monitoring
        # created in run() BEFORE the stage threads start (happens-before
        # via Thread.start); stage bodies only read the reference
        self._monitor = None                # wf-lint: single-writer[driver]
        # (enqueue seq, perf_counter) stamps of SAMPLED source batches: the
        # source stage appends, the sink stage pops its matching receipt —
        # SPSC rings preserve order, so receipt m pairs with enqueue m;
        # deque append/popleft are GIL-atomic, and the two writers never
        # touch the same end
        self._e2e_stamps = collections.deque()  # wf-lint: single-writer[driver, stage]
        #: per-batch causal tracing opt-in (trace= kwarg or WF_TRACE env)
        self._trace_arg = trace
        self._tracer = None
        self.batch_size = batch_size
        self.pin = pin
        self.heartbeat_timeout = heartbeat_timeout
        self._faults_arg = faults
        self.prefetch = int(prefetch)   # >0: prefetched (overlapped H2D) ingest
        spec = source.payload_spec()
        self.chains: List[CompiledChain] = []
        cap = getattr(source, "out_capacity", lambda b: b)(batch_size)
        # event-time sub-toggle (WF_MONITORING/WF_MONITORING_EVENT_TIME —
        # this driver has no monitoring= kwarg): geometry-binding, resolved
        # once before the segment chains build their operator states
        from ..observability import event_time_enabled
        et = event_time_enabled(None)
        for seg in segments:
            chain = CompiledChain(list(seg), spec, batch_capacity=cap,
                                  event_time=et)
            # health-ledger stage label (compile journal + device-time
            # attribution): the same per-segment name the flight recorder
            # and ring edges use, so dispatch-bound rows line up with traces
            chain.label = f"seg{len(self.chains)}"
            spec = chain.out_spec
            for op in chain.ops:
                cap = op.out_capacity(cap)
            self.chains.append(chain)
        # queue i feeds chain i; last queue feeds the sink thread. Edges are
        # named so hot edges can be sized independently: ``queue_capacity``
        # is one int (every edge, the historical default), a dict keyed by
        # edge name or index, or a callable ``(name, index) -> int``.
        n = len(self.chains)
        self.edge_names = [("src->seg0" if n else "src->sink")] + \
            [f"seg{i}->" + (f"seg{i + 1}" if i + 1 < n else "sink")
             for i in range(n)]
        self.edge_capacities = {
            name: _resolve_edge_capacity(queue_capacity, name, i)
            for i, name in enumerate(self.edge_names)}
        self.queues = [SPSCQueue(self.edge_capacities[name])
                       for name in self.edge_names]
        #: adaptive control plane (off by default): backpressure governor over
        #: the rings + admission control at the source. Autotuning does not
        #: apply here — each segment chain's capacity is its queue contract.
        from ..control import ControlConfig
        self._control = ControlConfig.resolve(control)
        # governor/_admission are built in run() BEFORE the stage threads
        # start; stage bodies only read the references
        self.governor = None                # wf-lint: single-writer[driver]
        self._admission = None              # wf-lint: single-writer[driver]
        # stage threads append, the driver reads AFTER join() — the join is
        # the memory barrier, list appends are GIL-atomic
        self._errors: List[BaseException] = []  # wf-lint: single-writer[stage]
        # per-stage slot, each written by its own stage thread only; the
        # watchdog reads and tolerates a stale beat (it re-polls)
        self._beats = {}                    # wf-lint: single-writer[stage]
        # set.add per exiting stage; watchdog membership checks are
        # GIL-atomic and a late observation only delays the stale flag
        self._done = set()                  # wf-lint: single-writer[stage]
        self.watchdog_stale: List[str] = [] # stages the watchdog flagged

    def queue_depths(self) -> dict:
        """Live ring depth per edge name (the backpressure signal)."""
        return {name: q.size()
                for name, q in zip(self.edge_names, self.queues)}

    # -- failure path -----------------------------------------------------------------

    def _drain_to_eos(self, q) -> bool:
        """A failed consumer keeps popping its input until the upstream's EOS
        marker arrives — the upstream producer is blocked on a full ring
        otherwise (SPSC ``push`` spins until space) and would never reach its
        own EOS/exit. Returns False only on drain timeout."""
        return _faults.drain_queue_to_sentinel(q, _EOS,
                                               timeout_s=_DRAIN_TIMEOUT_S)

    # -- stage bodies -----------------------------------------------------------------

    def _source_body(self, core: int):
        if self.pin:
            pin_thread(core)
        from .pipeline import record_source_launch
        stage = "source"
        self._beats[stage] = time.monotonic()
        gov, adm = self.governor, self._admission
        try:
            if self.prefetch:
                batches = self.source.batches_prefetched(
                    self.batch_size, self.prefetch,
                    pause_event=gov.pause_event if gov is not None else None)
            else:
                batches = self.source.batches(self.batch_size)
            mon = self._monitor
            n = 0
            n_enq = 0
            for batch in batches:
                self._beats[stage] = time.monotonic()
                _faults.fire("source.next", stage=stage, pos=n)
                record_source_launch(self.source, batch)
                _tracing.ingest(batch, n)
                admitted = (batch,) if adm is None else adm.offer(batch, pos=n)
                for ab in admitted:
                    if gov is not None:
                        # a throttle wait beats the heartbeat: backpressure is
                        # intentional, not a hang the watchdog should flag
                        gov.throttle(heartbeat=lambda: self._beats.__setitem__(
                            stage, time.monotonic()))
                        self._beats[stage] = time.monotonic()
                    if (mon is not None and self.sink is not None
                            and mon.config.should_sample_e2e(n_enq)):
                        # e2e sample: stamp the ENQUEUE index (post-
                        # admission), matched by receipt order at the sink
                        self._e2e_stamps.append((n_enq, time.perf_counter()))
                    _tracing.event(ab, self.edge_names[0], "enq")
                    self.queues[0].push(ab)
                    n_enq += 1
                n += 1
            if adm is not None:
                for ab in adm.drain():      # bounded held tail (drop_oldest)
                    if gov is not None:
                        gov.throttle(heartbeat=lambda: self._beats.__setitem__(
                            stage, time.monotonic()))
                        self._beats[stage] = time.monotonic()
                    self.queues[0].push(ab)
        except BaseException as e:          # noqa: BLE001 — propagated to join
            self._errors.append(e)
        finally:
            self._done.add(stage)
            self.queues[0].push(_EOS)

    def _segment_body(self, i: int, core: int):
        if self.pin:
            pin_thread(core)
        chain, q_in, q_out = self.chains[i], self.queues[i], self.queues[i + 1]
        edge_in, edge_out = self.edge_names[i], self.edge_names[i + 1]
        stage = f"seg{i}"
        self._beats[stage] = time.monotonic()
        eos_seen = False
        try:
            n = 0
            while True:
                self._beats[stage] = time.monotonic()
                ok, item = q_in.pop(spin=256, max_yields=1024)
                if not ok:
                    continue
                if item is _EOS:
                    eos_seen = True
                    for out in chain.flush():
                        q_out.push(out)
                    break
                _faults.fire("queue.stall", stage=stage, pos=n)
                _faults.fire("chain.step", stage=stage, pos=n)
                _tracing.event(item, edge_in, "deq")
                span = _tracing.service(item, stage)
                out = chain.push(item)
                if span is not None:
                    span.done()
                    _tracing.carry(item, out)
                _tracing.event(out, edge_out, "enq")   # no-op untraced
                q_out.push(out)
                n += 1
        except BaseException as e:          # noqa: BLE001
            self._errors.append(e)
            if self.governor is not None:
                self.governor.stop()        # a throttled source must not wait
                                            # on a ring this stage will drain
            if not eos_seen:
                self._drain_to_eos(q_in)    # unwedge the upstream producer
        finally:
            self._done.add(stage)
            q_out.push(_EOS)

    def _sink_body(self, core: int):
        if self.pin:
            pin_thread(core)
        q = self.queues[-1]
        stage = "sink"
        self._beats[stage] = time.monotonic()
        eos_seen = False
        try:
            n = 0
            while True:
                self._beats[stage] = time.monotonic()
                ok, item = q.pop(spin=256, max_yields=1024)
                if not ok:
                    continue
                if item is _EOS:
                    eos_seen = True
                    break
                _faults.fire("sink.consume", stage=stage, pos=n)
                _tracing.event(item, self.edge_names[-1], "deq")
                span = _tracing.service(item, stage)
                if self.sink is not None:
                    self.sink.consume(item)
                if span is not None:
                    span.done()
                stamps = self._e2e_stamps
                if stamps and stamps[0][0] == n:
                    # the stamped enqueue reached its receipt: a true
                    # source-framing -> host-receipt sample through every
                    # ring + segment (consume materialized the batch)
                    _seq, t0 = stamps.popleft()
                    self._monitor.registry.record_e2e(
                        time.perf_counter() - t0,
                        exemplar=_tracing.tid_of(item))
                n += 1
            if self.sink is not None:
                self.sink.consume(None)
        except BaseException as e:          # noqa: BLE001
            self._errors.append(e)
            if self.governor is not None:
                self.governor.stop()
            if not eos_seen:
                self._drain_to_eos(q)       # unwedge the upstream producer
        finally:
            self._done.add(stage)

    # -- watchdog ---------------------------------------------------------------------

    def _watchdog_body(self, stop: threading.Event):
        t = self.heartbeat_timeout
        while not stop.wait(min(t / 4.0, 0.05)):
            now = time.monotonic()
            for stage, last in list(self._beats.items()):
                if stage in self._done or stage in self.watchdog_stale:
                    continue
                if now - last > t:
                    self.watchdog_stale.append(stage)
                    _faults.bump("watchdog_timeouts")
                    _journal.record("watchdog_stale", stage=stage,
                                    stalled_s=round(now - last, 3),
                                    timeout_s=t)

    # -- run --------------------------------------------------------------------------

    def run(self):
        injector = _faults.resolve(self._faults_arg)
        from ..observability import Monitor, MonitoringConfig, TraceConfig, \
            Tracer
        mcfg = MonitoringConfig.resolve(self._monitoring_arg)
        self._e2e_stamps.clear()            # receipt indices restart at 0
        if mcfg is not None and self._monitor is None:
            self._monitor = Monitor(mcfg,
                                    self.source.getName() + "-threaded")
            reg = self._monitor.registry
            reg.register_operator(self.source)
            for chain in self.chains:
                reg.register_chain(chain.label, chain)
            if self.sink is not None:
                reg.register_operator(self.sink)
            for name, q in zip(self.edge_names, self.queues):
                reg.attach_queue_gauge(name, q.size,
                                       capacity=self.edge_capacities[name])
            self._monitor.start()
        tcfg = TraceConfig.resolve(self._trace_arg)
        if tcfg is not None and self._tracer is None:
            self._tracer = Tracer(tcfg,
                                  self.source.getName() + "-threaded").start()
        cfg = self._control
        if cfg is not None:
            from ..control import admission_from_config, governor_from_config
            self.governor = governor_from_config(cfg)
            if self.governor is not None:
                for name, q in zip(self.edge_names, self.queues):
                    self.governor.watch(name, q.size,
                                        self.edge_capacities[name])
            self._admission = admission_from_config(
                cfg, getattr(self.source, "out_capacity",
                             lambda b: b)(self.batch_size),
                driver="threaded")
        if (self._monitor is not None
                and self._monitor.remediation is not None
                and self._admission is not None):
            # bind the actuators THIS run owns — remediation actions whose
            # actuator stays unbound skip loudly (remediation_skip
            # reason=unbound) instead of guessing.  scale_rate takes the
            # bucket lock, so the Reporter-thread actuation is atomic
            # w.r.t. the source thread's offer()
            adm = self._admission
            self._monitor.remediation.bind(
                "admission_rate",
                lambda a: adm.scale_rate(a.factor, a.floor))
        with _faults.activate(injector):
            try:
                return self._run()
            finally:
                if self._monitor is not None:
                    # final snapshot + journal close; no topology target —
                    # the export models Pipeline/PipeGraph shapes
                    self._monitor.finish()
                if self._tracer is not None:
                    self._tracer.finish()
                if self.governor is not None:
                    # never leave a source wedged in a throttle wait past
                    # teardown (the object stays readable for post-run stats)
                    self.governor.stop()

    def _run(self):
        threads = [threading.Thread(  # wf-lint: thread-role[stage]
            target=self._source_body, args=(0,), name="wf-source")]
        for i in range(len(self.chains)):
            threads.append(threading.Thread(  # wf-lint: thread-role[stage]
                target=self._segment_body, args=(i, i + 1),
                name=f"wf-seg{i}"))
        threads.append(threading.Thread(  # wf-lint: thread-role[stage]
            target=self._sink_body, args=(len(self.chains) + 1,),
            name="wf-sink"))
        stop_watchdog = threading.Event()
        watchdog = None
        if self.heartbeat_timeout:
            watchdog = threading.Thread(  # wf-lint: thread-role[watchdog]
                target=self._watchdog_body,
                args=(stop_watchdog,), daemon=True,
                name="wf-watchdog")
            watchdog.start()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if watchdog is not None:
            stop_watchdog.set()
            watchdog.join()
        err = self._errors[0] if self._errors else None
        # close EVERYTHING before re-raising (closing_func / svc_end parity
        # must run on the failure path too — the seed skipped close entirely
        # when a stage had failed); a close error surfaces only on clean runs
        for c in self.chains:
            for op in c.ops:
                try:
                    op.close()
                except Exception as ce:     # noqa: BLE001
                    err = err or ce
        try:
            self.source.close()
        except Exception as ce:             # noqa: BLE001
            err = err or ce
        if self.sink is not None:
            try:
                self.sink.close()
            except Exception as ce:         # noqa: BLE001
                err = err or ce
        if err is not None:
            raise err
        res = {}
        for c in self.chains:
            res.update(c.result())
        return res
