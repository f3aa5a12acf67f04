"""PipeGraph + MultiPipe — the composition layer (reference L4).

Counterpart of ``wf/pipegraph.hpp`` (PipeGraph ``:104-244``, MultiPipe ``:255-571``,
split ``:3030-3062``, select ``:3065-3081``, merge ``:2992-3026``, Application Tree
``AppNode`` ``:64-75``). The reference compiles the logical operator graph into nested
FastFlow farms/pipelines with one thread per node; here each MultiPipe's operator chain
compiles into ONE jitted device program (``CompiledChain``), and the DAG between
MultiPipes (split/merge edges) is executed by a host push-driver:

- ``add(op)`` / ``chain(op)``: both append to the compiled chain. The reference
  distinguishes shuffle (new matrioska + emitter clone, ``:1231-1266``) from chaining
  (``ff_comb`` fusion ``:1272-1318``); on TPU keyed routing happens *inside* the
  program via segment ops, so every add is as cheap as a chain — ``chain`` is kept for
  API parity and asserts the op is chainable (FORWARD routing), mirroring the
  reference's conditions.
- ``split(fn, n)``: installs a splitting function (``Splitting_Emitter``,
  ``wf/splitting_emitter.hpp:41-152``) evaluated per tuple under ``vmap``; branch i
  receives the batch masked to tuples routed to i (multicast when the function
  returns a mask vector).
- ``select(i)``: the i-th split branch as a new MultiPipe (``:3065-3081``).
- ``merge(*others)``: N output streams into one (``:2992-3026``); type compatibility
  is checked on payload specs (the typeid check ``:1573-1578``). In DETERMINISTIC
  mode merged batches are buffered per round and stably sorted by (ts, id) — the
  batch-level Ordering_Node (``wf/ordering_node.hpp``).
- EOS: sources exhaust, then every chain flushes in topological order, cascading
  through downstream chains (reference eosnotify propagation).

Graph introspection: ``listOperators`` and a graphviz ``dump_DOTGraph``
(``wf/pipegraph.hpp:226-237``, GRAPHVIZ_WINDFLOW).
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..basic import Mode, DEFAULT_BATCH_SIZE
from ..batch import Batch, concat_batches, tuple_refs
from ..observability import tracing as _tracing
from ..operators.base import Basic_Operator
from ..operators.sink import ReduceSink, Sink
from ..operators.source import SourceBase
from .pipeline import CompiledChain


class AppNode:
    """Node of the Application Tree (``wf/pipegraph.hpp:64-75``).

    A merge removes the absorbed pipes' nodes from the forest (the reference
    deletes them, ``wf/pipegraph.hpp:846-858``): ``absorbed`` is set, ``parent``
    cleared, and split-parent children lists are re-pointed at the merged node —
    so the live forest is exactly the nodes with ``absorbed == False``."""

    def __init__(self, mp: "MultiPipe", parent: Optional["AppNode"] = None):
        self.mp = mp
        self.parent = parent
        self.children: List[AppNode] = []
        self.absorbed = False

    def absorb(self) -> None:
        """Detach this node (and its subtree) from the live forest."""
        self.absorbed = True
        self.parent = None
        for c in self.children:
            c.absorb()


class MultiPipe:
    """A growing chain of operators with optional split/merge structure."""

    def __init__(self, graph: "PipeGraph", source: Optional[SourceBase] = None):
        self.graph = graph
        self.source = source
        # appended during graph BUILD (driver), before any driver runs;
        # pipe threads only iterate
        self.ops: List[Basic_Operator] = []  # wf-lint: single-writer[driver]
        self.sink: Optional[Sink] = None
        self.has_sink = False
        # split structure
        self.split_fn: Optional[Callable] = None
        self.split_branches: List[MultiPipe] = []
        # merge structure: upstream pipes feeding this one
        self.merge_inputs: List[MultiPipe] = []
        self._dataflow_parent: Optional[MultiPipe] = None   # split-branch feeder
        # compiled lazily by whichever thread first pushes through this pipe
        # — the push driver (driver) or the pipe's OWN body thread (stage);
        # a pipe is never driven from two threads at once
        self._chain: Optional[CompiledChain] = None  # wf-lint: single-writer[driver, stage]
        self._outputs_to: List[MultiPipe] = []
        self._ordering = None     # lazily-built Ordering_Node (DETERMINISTIC merges)
        # application-tree position of a PARTIAL merge result: the reference
        # re-parents the merged AppNode under the split parent, replacing the
        # absorbed sibling branches (wf/pipegraph.hpp:944-952) — that is what
        # legalizes graph_8/graph_9-style follow-up merges with the remaining
        # siblings.
        self._merge_parent: Optional[MultiPipe] = None
        self._covers_idx: tuple = ()

    # -- construction (reference add/chain overloads, wf/pipegraph.hpp:1565-2950) -----

    def add(self, op: Basic_Operator) -> "MultiPipe":
        self._check_open()
        if isinstance(op, Sink):
            raise TypeError(
                f"add({op.name}): host Sinks terminate a MultiPipe — use "
                f"add_sink()/chain_sink() (in-graph reductions stay addable via "
                f"ReduceSink)")
        op._mark_used()
        op._chained = False
        self.graph._register(op)
        self.ops.append(op)
        return self

    def chain(self, op: Basic_Operator) -> "MultiPipe":
        """Queue-free fusion when the operator is FORWARD; silent fallback to
        ``add()`` otherwise — exactly the reference's behavior
        (``wf/pipegraph.hpp:1602-1640``: KEYBY or unchainable ops fall through
        to add). The outcome is recorded on the operator (``_chained``) and
        rendered distinctly by ``dump_DOTGraph``, mirroring the reference's
        ``gv_chain_vertex`` vs add-vertex distinction."""
        from ..basic import routing_modes_t
        self.add(op)
        op._chained = op.getRoutingMode() in (routing_modes_t.FORWARD,
                                              routing_modes_t.NONE)
        return self

    def add_sink(self, sink: Sink) -> "MultiPipe":
        self._check_open()
        sink._mark_used()
        self.graph._register(sink)
        self.sink = sink
        self.has_sink = True
        return self

    chain_sink = add_sink

    # -- split / select / merge -------------------------------------------------------

    def split(self, fn: Callable, n_branches: int) -> "MultiPipe":
        """``fn(t) -> int branch`` or ``fn(t) -> bool[n]`` multicast mask."""
        self._check_open()
        if self.has_sink:
            raise RuntimeError("cannot split a MultiPipe with a sink")
        self.split_fn = fn
        self.split_branches = []
        node = self.graph._node_of(self)
        for _ in range(n_branches):
            child = MultiPipe(self.graph)
            child._dataflow_parent = self
            self.split_branches.append(child)
            cn = AppNode(child, node)
            node.children.append(cn)
            self.graph._nodes[id(child)] = cn
        return self

    def select(self, i: int) -> "MultiPipe":
        if self.split_fn is None:
            raise RuntimeError("select() on a non-split MultiPipe (wf/pipegraph.hpp:3065)")
        if not (0 <= i < len(self.split_branches)):
            raise IndexError(f"branch {i} of {len(self.split_branches)}")
        return self.split_branches[i]

    def merge(self, *others: "MultiPipe") -> "MultiPipe":
        """Merge this pipe's output with ``others`` into a new MultiPipe.

        Legality mirrors the reference (``wf/pipegraph.hpp:2992-3026`` entry
        checks; structural cases merge-ind / merge-full / merge-partial with the
        contiguity rule, ``:813-965``): at least two distinct member pipes, none
        already merged or split or sunk, and the set must be independent roots,
        a whole split subtree, or contiguous sibling branches."""
        pipes = [self, *others]
        merge_parent, covers_idx = self.graph._check_merge_legality(pipes)
        specs = [p._out_payload_spec() for p in pipes]
        s0 = jax.tree.structure(specs[0])
        for s in specs[1:]:
            if jax.tree.structure(s) != s0 or any(
                    a.shape != b.shape or a.dtype != b.dtype
                    for a, b in zip(jax.tree.leaves(specs[0]), jax.tree.leaves(s))):
                raise TypeError("merge(): incompatible tuple types "
                                "(wf/pipegraph.hpp:1573-1578 typeid check)")
        merged = MultiPipe(self.graph)
        merged.merge_inputs = pipes
        merged._merge_parent = merge_parent
        merged._covers_idx = covers_idx
        # Application-Tree surgery, as the reference does it: the merged node is
        # a LEAF that replaces the absorbed subtrees — under the split parent
        # for merge-partial / nested merge-full (wf/pipegraph.hpp:846-858,
        # 944-957), as a root for merge-ind / root-level merge-full.
        node = AppNode(merged)
        if merge_parent is not None:
            parent_node = self.graph._node_of(merge_parent)
            node.parent = parent_node
            # a direct child is absorbed iff the branch indexes it covers are
            # within this merge's cover (children are split branches, or the
            # results of earlier partial merges which are NOT in
            # split_branches — identify both by index cover)
            def _child_idxs(c):
                if c.mp._merge_parent is merge_parent:
                    return set(c.mp._covers_idx)
                return {i for i, b in enumerate(merge_parent.split_branches)
                        if b is c.mp}
            target = set(covers_idx)
            new_children, replaced = [], False
            for c in parent_node.children:
                ci = _child_idxs(c)
                if ci and ci <= target:
                    c.absorb()
                    if not replaced:
                        new_children.append(node)
                        replaced = True
                else:
                    new_children.append(c)
            parent_node.children = new_children
        else:
            # root-level merge (merge-ind / merge-full of whole roots): the
            # absorbed roots leave the forest, like the partial case above
            for p in pipes:
                self.graph._node_of(p).absorb()
        for p in pipes:
            p._outputs_to.append(merged)
        self.graph._nodes[id(merged)] = node
        self.graph._merged_roots = [r for r in self.graph._merged_roots
                                    if r not in pipes]
        self.graph._merged_roots.append(merged)
        return merged

    def join_with(self, other: "MultiPipe", join_op) -> "MultiPipe":
        """Two-input join wiring over merge semantics: merge this pipe with
        ``other`` (the ``wf/pipegraph.hpp:1573-1578`` typeid check applies —
        both sides must already carry the unified/tagged payload schema) and
        add ``join_op`` (a :class:`~windflow_tpu.operators.join.
        StreamTableJoin` / :class:`~windflow_tpu.operators.join.
        IntervalJoin`, whose ``side_fn`` separates the sides again). Under
        ``Mode.DETERMINISTIC`` the merge's Ordering_Node fixes the
        interleave, making the join byte-identical across drivers."""
        from ..operators.join import IntervalJoin, StreamTableJoin
        if not isinstance(join_op, (StreamTableJoin, IntervalJoin)):
            raise TypeError(
                f"join_with expects a StreamTableJoin/IntervalJoin operator, "
                f"got {type(join_op).__name__}")
        merged = self.merge(other)
        merged.add(join_op)
        return merged

    # -- internals --------------------------------------------------------------------

    def _check_open(self):
        if self.split_fn is not None:
            raise RuntimeError("MultiPipe already split; use select()")
        if self.has_sink:
            raise RuntimeError("MultiPipe already has a sink")

    def _in_payload_spec(self):
        if self.source is not None:
            return self.source.payload_spec()
        if self.merge_inputs:
            return self.merge_inputs[0]._out_payload_spec()
        # split branch: the splitting pipe's output spec
        return self._dataflow_parent._out_payload_spec()

    def _out_payload_spec(self):
        spec = self._in_payload_spec()
        for op in self.ops:
            spec = op.out_spec(spec)
        return spec

    def _compile(self, batch_capacity: int):
        if self._chain is None:
            # event-time sub-toggle: geometry-binding (lateness histograms
            # live in operator state), resolved from the graph's monitoring=
            from ..observability import event_time_enabled
            self._chain = CompiledChain(
                self.ops, self._in_payload_spec(),
                batch_capacity=batch_capacity,
                event_time=event_time_enabled(self.graph._monitoring_arg))
            # health-ledger stage label = the flight-recorder pipe label, so
            # the dispatch-bound classifier names the same edges wf_trace
            # renders (the fusion candidates of ROADMAP item 2)
            self._chain.label = self.graph._trace_label(self)
        return self._chain


class PipeGraph:
    """The streaming environment (``wf/pipegraph.hpp:104-244``)."""

    def __init__(self, name: str = "pipegraph", mode: Mode = Mode.DEFAULT,
                 batch_size: int = None, monitoring=None, control=None,
                 queue_capacity=8, trace=None):
        self.name = name
        self.mode = mode
        #: None = resolve at start(): min withBatch hint over registered
        #: operators (capacity ceilings, wf/builders_gpu.hpp:115-122), else
        #: DEFAULT_BATCH_SIZE; an explicit value always wins.  Written by
        #: start() on the driver BEFORE the threaded bodies spawn.
        self.batch_size = batch_size      # wf-lint: single-writer[driver]
        #: telemetry opt-in (the reference's MONITORING mode): None = consult
        #: WF_MONITORING; True / out-dir string / observability.MonitoringConfig
        #: enable the metrics registry + periodic reporter + event journal +
        #: topology dump for this graph's run. Off by default (zero hot-path
        #: cost beyond a None check).
        self._monitoring_arg = monitoring
        self._monitor = None
        #: per-batch causal tracing opt-in (mirrors monitoring=): None =
        #: consult WF_TRACE; resolved at start(). Trace ids are minted per
        #: (root stream, offered position) — deterministic, so the supervised
        #: driver replays identical ids after a restore.
        self._trace_arg = trace
        self._tracer = None
        # id(pipe) -> "pipe<i>", built lazily by whichever thread first
        # needs a label; concurrent rebuilds produce the IDENTICAL dict
        # (pure function of the pipe list), so last-writer-wins is benign
        self._trace_labels = None     # wf-lint: single-writer[driver, stage]
        #: control-plane opt-in (mirrors monitoring=/faults=): None = consult
        #: WF_CONTROL; resolved at start(). Admission control gates every
        #: source loop; the backpressure governor throttles the threaded
        #: driver's sources on SPSC ring watermarks.
        self._control_arg = control
        self._control = None
        #: SPSC ring capacity for the threaded driver's dataflow edges: one
        #: int for all, a dict keyed by edge label ("src->2", "0->1", by
        #: consumer pipe index), or a callable (label, index) -> int.
        self.queue_capacity = queue_capacity
        self._e2e_t0 = None           # in-flight e2e latency sample start
        # graph build is driver-only; bodies and the reporter only iterate
        self._roots: List[MultiPipe] = []  # wf-lint: single-writer[driver]
        self._merged_roots: List[MultiPipe] = []
        self._nodes = {}
        self._operators: List[Basic_Operator] = []
        self._started = False
        self._ended = False
        self._exhausted = set()       # pipe ids whose inputs are known complete

    # -- reference surface ------------------------------------------------------------

    def add_source(self, source: SourceBase) -> MultiPipe:
        if self._started:
            raise RuntimeError("graph already running")
        source._mark_used()
        self._register(source)
        mp = MultiPipe(self, source)
        self._roots.append(mp)
        node = AppNode(mp)
        self._nodes[id(mp)] = node
        return mp

    def run(self, threaded: bool = False):
        """Drive the graph to completion. ``threaded=True`` gives each MultiPipe its
        own host thread connected by native SPSC rings — true pipeline parallelism
        across segments (the reference's thread-per-node model at segment
        granularity, ``wf/pipegraph.hpp:1522-1533``)."""
        self.start()
        if threaded:
            return self._run_threaded()
        return self.wait_end()

    def start(self):
        if self.batch_size is None:
            from .pipeline import resolve_batch_hint
            self.batch_size = (resolve_batch_hint(self._operators)
                               or DEFAULT_BATCH_SIZE)
        self._started = True
        if self._monitor is None:
            from ..observability import Monitor, MonitoringConfig
            cfg = MonitoringConfig.resolve(self._monitoring_arg)
            if cfg is not None:
                self._monitor = Monitor(cfg, self.name)
                self._monitor.registry.register_graph(self)
                self._monitor.start()
        if self._control is None:
            from ..control import ControlConfig
            self._control = ControlConfig.resolve(self._control_arg)
        if self._tracer is None:
            from ..observability import TraceConfig, Tracer
            tcfg = TraceConfig.resolve(self._trace_arg)
            if tcfg is not None:
                self._tracer = Tracer(tcfg, self.name).start()

    def _trace_label(self, mp) -> str:
        """Flight-recorder stage label of one pipe (stable pipe index)."""
        if self._trace_labels is None or id(mp) not in self._trace_labels:
            self._trace_labels = {id(p): f"pipe{i}"
                                  for i, p in enumerate(self._all_pipes())}
        return self._trace_labels.get(id(mp), "pipe?")

    def _make_admissions(self, driver: str):
        """Per-source admission controllers over ONE shared token bucket
        (total-ingest rate limit, per-source holding cells), keyed by root
        pipe id. Every value is None when admission is off."""
        from ..control import admission_group
        group = admission_group(self._control, self.batch_size,
                                len(self._roots), driver=driver)
        return {id(mp): adm for mp, adm in zip(self._roots, group)}

    def run_supervised(self, *, checkpoint_every: int = 8,
                       max_restarts: int = 3, **hardening):
        """Supervised execution of the whole DAG: aligned checkpoints, replay
        from the committed positions on failure, exactly-once delivery on every
        sink (``runtime/supervisor.py::run_graph_supervised``; the reference's
        failure model is exit(EXIT_FAILURE), SURVEY §5). ``hardening`` forwards
        the recovery knobs: ``backoff_base``/``backoff_cap`` (decorrelated-
        jitter restart backoff), ``dead_letter``/``poison_threshold``
        (poison-batch quarantine), ``step_timeout`` (hung-step watchdog),
        ``faults`` (a FaultPlan/FaultInjector for chaos testing)."""
        from .supervisor import run_graph_supervised
        return run_graph_supervised(self, checkpoint_every=checkpoint_every,
                                    max_restarts=max_restarts, **hardening)

    # -- threaded driver --------------------------------------------------------------

    def _iter_edges(self):
        """Dataflow edges of the threaded driver, in ring-creation order:
        yields ``(producer, consumer, label, index)`` — ``producer`` None for
        source-ingest edges. THE single enumeration, consumed by
        ``_run_threaded`` (ring creation) and ``analysis.validate`` (pre-run
        capacity/watermark checks) — edge labels are minted nowhere else, so
        the validator can never check rings the driver does not build."""
        pipes = self._all_pipes()
        pipe_idx = {id(p): i for i, p in enumerate(pipes)}
        n = 0
        for p in pipes:
            if p.source is not None:
                yield None, p, f"src->{pipe_idx[id(p)]}", n
                n += 1
            for b in p.split_branches:
                yield p, b, f"{pipe_idx[id(p)]}->{pipe_idx[id(b)]}", n
                n += 1
            for m in p._outputs_to:
                yield p, m, f"{pipe_idx[id(p)]}->{pipe_idx[id(m)]}", n
                n += 1

    def _run_threaded(self):
        import threading
        from ..native import SPSCQueue

        pipes = self._all_pipes()
        pipe_idx = {id(p): i for i, p in enumerate(pipes)}
        EOS = object()
        # one SPSC ring per dataflow EDGE (single producer, single consumer); a
        # consumer with several inputs (merge) polls its rings round-robin
        in_queues = {id(p): [] for p in pipes}
        out_edges = {}                           # (producer id, consumer id) -> queue
        channel_of = {}                          # queue id -> merge channel index
        edge_label = {}                          # queue id -> edge label (tracing)
        from .threaded import _resolve_edge_capacity
        from ..control import governor_from_config
        governor = governor_from_config(self._control)
        admissions = self._make_admissions("graph-threaded")

        for prod, dst, label, index in self._iter_edges():
            cap = _resolve_edge_capacity(self.queue_capacity, label, index)
            q = SPSCQueue(cap)
            in_queues[id(dst)].append(q)
            out_edges[("src" if prod is None else id(prod), id(dst))] = q
            edge_label[id(q)] = label
            if self._monitor is not None:
                # live ring-depth gauge per dataflow edge: depth near capacity
                # = backpressure, the consumer pipe is the bottleneck
                self._monitor.registry.attach_queue_gauge(label, q.size,
                                                          capacity=cap)
            if governor is not None:
                governor.watch(label, q.size, cap)
            if prod is not None and dst.merge_inputs:
                channel_of[id(q)] = dst.merge_inputs.index(prod)
        errors = []

        def deliver(mp, out):
            if mp.sink is not None:
                mp.sink.consume(out)
            if mp.split_fn is not None:
                sel = jax.vmap(mp.split_fn)(tuple_refs(out))
                for i, branch in enumerate(mp.split_branches):
                    if getattr(sel, "ndim", 1) == 2:
                        keep = sel[:, i].astype(jnp.bool_)
                    else:
                        keep = jnp.asarray(sel, jnp.int32) == i
                    q = out_edges[(id(mp), id(branch))]
                    masked = out.mask(keep)
                    _tracing.carry(out, masked)
                    _tracing.event(masked, edge_label[id(q)], "enq")
                    q.push(masked)
            for merged in mp._outputs_to:
                q = out_edges[(id(mp), id(merged))]
                _tracing.event(out, edge_label[id(q)], "enq")
                q.push(out)

        def propagate_eos(mp):
            from ..observability import journal as _journal
            _journal.record("eos_propagate", graph=self.name,
                            pipe=pipe_idx[id(mp)])
            for branch in mp.split_branches:
                out_edges[(id(mp), id(branch))].push(EOS)
            for merged in mp._outputs_to:
                out_edges[(id(mp), id(merged))].push(EOS)

        def pipe_body(mp):
            # DETERMINISTIC merges go through the SAME Ordering_Node as the push
            # driver — cross-channel low-watermark holdback, not per-batch sorting
            onode = (self._ordering_of(mp)
                     if self.mode == Mode.DETERMINISTIC and mp.merge_inputs
                     else None)

            def run_batch(item):
                deliver(mp, self._compute(mp, item))

            live = list(in_queues[id(mp)])
            try:
                while live:
                    for q in list(live):
                        ok, item = q.pop(spin=64, max_yields=0)
                        if not ok:
                            continue
                        if item is EOS:
                            live.remove(q)
                            if onode is not None and id(q) in channel_of:
                                rel = onode.close_channel(channel_of[id(q)])
                                for piece in self._chunks(
                                        rel, onode.last_release_count):
                                    run_batch(piece)
                            continue
                        if onode is not None and id(q) in channel_of:
                            _tracing.event(item, edge_label[id(q)], "deq")
                            rel = onode.push(channel_of[id(q)], item)
                            for piece in self._chunks(
                                    rel, onode.last_release_count):
                                run_batch(piece)
                        else:
                            _tracing.event(item, edge_label[id(q)], "deq")
                            run_batch(item)
                if onode is not None:
                    for piece in self._chunks(onode.flush(),
                                              onode.last_release_count):
                        run_batch(piece)
                if mp._chain is not None:
                    for out in mp._chain.flush():
                        deliver(mp, out)
                if mp.sink is not None:
                    mp.sink.consume(None)
            except BaseException as e:          # noqa: BLE001 — re-raised at join
                errors.append(e)
                if governor is not None:
                    governor.stop()     # a throttled source must not wait on
                                        # a ring this dead pipe will drain
                # drain the remaining input rings to EOS so upstream producers
                # blocked on a full ring behind this dead pipe can finish and
                # send their own EOS (otherwise the join above deadlocks)
                from . import faults as _faults
                for q in list(live):
                    if _faults.drain_queue_to_sentinel(q, EOS):
                        live.remove(q)
            finally:
                propagate_eos(mp)

        def source_body(mp):
            from .pipeline import record_source_launch
            q = out_edges[("src", id(mp))]
            adm = admissions.get(id(mp))
            stream = self._roots.index(mp)
            try:
                n = 0
                for batch in mp.source.batches(self.batch_size):
                    record_source_launch(mp.source, batch)
                    _tracing.ingest(batch, n, stream=stream)
                    admitted = (batch,) if adm is None else adm.offer(
                        batch, pos=n, stream=stream)
                    for ab in admitted:
                        if governor is not None:
                            governor.throttle()
                        _tracing.event(ab, edge_label[id(q)], "enq")
                        q.push(ab)
                    n += 1
                if adm is not None:
                    for ab in adm.drain():
                        if governor is not None:
                            governor.throttle()
                        q.push(ab)
            except BaseException as e:          # noqa: BLE001
                errors.append(e)
            finally:
                q.push(EOS)

        try:
            threads = []
            for p in pipes:
                threads.append(threading.Thread(  # wf-lint: thread-role[stage]
                    target=pipe_body, args=(p,),
                    name=f"wf-pipe-{id(p) % 1000}"))
            for p in self._roots:
                threads.append(threading.Thread(  # wf-lint: thread-role[stage]
                    target=source_body, args=(p,), name="wf-src"))
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise errors[0]
            for p in pipes:
                if p._chain is not None:
                    p._chain.sync_stats()
            for op in self._operators:
                op.close()            # closing_func per replica (svc_end parity)
            self._ended = True
            return self._results()
        finally:
            if governor is not None:
                governor.stop()
            if self._tracer is not None:
                self._tracer.finish()
            if self._monitor is not None:
                self._monitor.finish(self)

    def wait_end(self):
        """Drive the whole DAG to completion (the reference joins threads here,
        ``wf/pipegraph.hpp:1058-1105``; our driver is a host push loop)."""
        if self._ended:
            return self._results()
        if not self._started:
            self.start()              # resolves batch_size from withBatch hints
        import time as _time
        from .pipeline import record_source_launch
        from ..observability import journal as _journal
        try:
            admissions = self._make_admissions("graph")
            sources = [(mp, mp.source.batches(self.batch_size))
                       for mp in self._roots]
            live = list(sources)
            round_robin_pos = 0
            n_pushed = 0
            # trace ids are minted per (root stream, per-root offered
            # position) — the same coordinates the supervised driver replays
            root_idx = {id(mp): i for i, mp in enumerate(self._roots)}
            offered = {id(mp): 0 for mp in self._roots}

            def ingest(mp, ab, sampled):
                if sampled:
                    # e2e latency sample: source framing -> first sink's
                    # host receipt (recorded in _deliver after consume)
                    self._e2e_t0 = _time.perf_counter()
                self._push(mp, ab)
                self._e2e_t0 = None

            while live:
                mp, it = live[round_robin_pos % len(live)]
                try:
                    batch = next(it)
                except StopIteration:
                    live.remove((mp, it))
                    adm = admissions.get(id(mp))
                    if adm is not None:
                        for ab in adm.drain():  # bounded held tail
                            ingest(mp, ab, False)
                    self._exhaust(mp)
                    continue
                record_source_launch(mp.source, batch)
                opos = offered[id(mp)]
                _tracing.ingest(batch, opos, stream=root_idx[id(mp)])
                offered[id(mp)] += 1
                adm = admissions.get(id(mp))
                # shed journal coordinates = (stream, per-root offered pos),
                # the same coordinates trace ids are minted from — wf_trace's
                # report joins shed events to traced batches on them
                admitted = (batch,) if adm is None else adm.offer(
                    batch, pos=opos, stream=root_idx[id(mp)])
                round_robin_pos += 1
                for ab in admitted:
                    sampled = (self._monitor is not None
                               and self._monitor.config.should_sample_e2e(
                                   n_pushed))
                    ingest(mp, ab, sampled)
                    n_pushed += 1
            # EOS: flush every pipe in topological order; a merged pipe first
            # drains its Ordering_Node (tuples held back by the low-watermark)
            pipe_idx = {id(p): i for i, p in enumerate(self._all_pipes())}
            for mp in self._topo_order():
                _journal.record("eos_flush", graph=self.name,
                                pipe=pipe_idx.get(id(mp)))
                if mp._ordering is not None:
                    for piece in self._chunks(mp._ordering.flush(),
                                              mp._ordering.last_release_count):
                        self._push(mp, piece)
                self._flush_pipe(mp)
            for mp in self._all_pipes():
                if mp.sink is not None:
                    mp.sink.consume(None)
            for mp in self._all_pipes():
                if mp._chain is not None:
                    mp._chain.sync_stats()
            for op in self._operators:
                op.close()            # closing_func per replica (svc_end parity)
            self._ended = True
            return self._results()
        finally:
            if self._tracer is not None:
                self._tracer.finish()
            if self._monitor is not None:
                self._monitor.finish(self)

    def getNumThreads(self) -> int:
        """API parity: total replicas across operators (the reference counts OS
        threads; ours are logical shards, wf/pipegraph.hpp:1025-1053 banner)."""
        return sum(op.getParallelism() for op in self._operators)

    def listOperators(self) -> List[Basic_Operator]:
        return list(self._operators)

    def dump_stats(self, log_dir: str = "log"):
        """Dump every operator's Stats_Record to ``log/`` (TRACE_WINDFLOW analogue,
        ``wf/stats_record.hpp:109-155``). Returns the written paths."""
        paths = []
        for op in self._operators:
            for rec in op.get_StatsRecords():
                paths.append(rec.dump_to_file(log_dir))
        return paths

    def dump_DOTGraph(self, path: str = None) -> str:
        """Graphviz dump (GRAPHVIZ_WINDFLOW, wf/pipegraph.hpp:226-237,1450-1518)."""
        lines = ["digraph PipeGraph {", "  rankdir=LR;"]
        def op_label(o):
            # chained (queue-free fused) ops render bare; routed adds carry
            # their routing mode — the reference's gv_chain_vertex vs
            # add-vertex distinction (wf/pipegraph.hpp:1450-1518)
            if o._chained:
                return f"{o.getName()} (chained)"
            mode = o.getRoutingMode().name.lower()
            return (o.getName() if mode in ("forward", "none")
                    else f"{o.getName()} ({mode})")
        def label(mp, idx):
            ops = " | ".join(op_label(o) for o in mp.ops) or "(empty)"
            src = f"{mp.source.getName()} -> " if mp.source else ""
            snk = f" -> {mp.sink.getName()}" if mp.sink else ""
            return f'  mp{idx} [shape=record, label="{src}{ops}{snk}"];'
        pipes = self._all_pipes()
        index = {id(p): i for i, p in enumerate(pipes)}
        for i, p in enumerate(pipes):
            lines.append(label(p, i))
        for p in pipes:
            for b in p.split_branches:
                lines.append(f"  mp{index[id(p)]} -> mp{index[id(b)]} [label=split];")
            for m in p._outputs_to:
                lines.append(f"  mp{index[id(p)]} -> mp{index[id(m)]} [label=merge];")
        lines.append("}")
        dot = "\n".join(lines)
        if path:
            with open(path, "w") as f:
                f.write(dot)
        return dot

    # -- driver internals -------------------------------------------------------------

    def _register(self, op):
        self._operators.append(op)

    def _node_of(self, mp) -> AppNode:
        return self._nodes[id(mp)]

    def _all_pipes(self) -> List[MultiPipe]:
        out, seen = [], set()
        def visit(mp):
            if id(mp) in seen:
                return
            seen.add(id(mp))
            out.append(mp)
            for b in mp.split_branches:
                visit(b)
            for m in mp._outputs_to:
                visit(m)
        for r in self._roots:
            visit(r)
        return out

    def _topo_order(self) -> List[MultiPipe]:
        """Upstream-before-downstream order for EOS flushing."""
        order, seen = [], set()
        def visit(mp):
            if id(mp) in seen:
                return
            seen.add(id(mp))
            for up in mp.merge_inputs:
                visit(up)
            if mp._dataflow_parent is not None:
                visit(mp._dataflow_parent)
            order.append(mp)
        for p in self._all_pipes():
            visit(p)
        return order

    def _compute(self, mp: MultiPipe, batch: Batch) -> Batch:
        """One batch through mp's chain, under the pipe's trace span."""
        chain = mp._compile(batch.capacity)
        tr = _tracing.get_active()
        span = tr.service(batch, self._trace_label(mp)) if tr is not None \
            else None
        out = chain.push(batch)
        if span is not None:
            span.done()
            _tracing.carry(batch, out)
        return out

    def _push(self, mp: MultiPipe, batch: Batch):
        """Push one batch through mp's chain and onward through split/merge edges."""
        self._deliver(mp, self._compute(mp, batch))

    def _ordering_of(self, merged: MultiPipe):
        """Per-merge Ordering_Node (DETERMINISTIC mode): holds tuples back to the
        low-watermark over the merge's input channels — the reference inserts the
        node before each replica the same way (wf/pipegraph.hpp:1197-1248).
        Count-based windows downstream of the merge get TS_RENUMBERING (the
        reference's broadcast+renumbering case, wf/pipegraph.hpp:1954-1957,
        wf/ordering_node.hpp:218,257) so released tuples carry progressive ids."""
        if merged._ordering is None:
            from ..basic import ordering_mode_t
            from ..parallel.ordering import Ordering_Node
            cb_downstream = any(
                getattr(getattr(op, "spec", None), "is_cb", False)
                for op in merged.ops)
            mode = (ordering_mode_t.TS_RENUMBERING if cb_downstream
                    else ordering_mode_t.TS)
            merged._ordering = Ordering_Node(len(merged.merge_inputs), mode)
        return merged._ordering

    def _chunks(self, batch: Optional[Batch], n: Optional[int] = None,
                compact: bool = False):
        """Re-slice a released (variable-capacity) batch into batch_size-capacity
        pieces so downstream chains keep ONE compiled shape. ``n`` (the
        valid-lane count) can be passed by callers that already fetched it —
        Ordering_Node releases carry ``last_release_count`` — to avoid a second
        device sync. Ordering_Node releases are prefix-compacted by
        construction (the sorted-pool release is a physical prefix), so the
        default skips the compaction sort; pass ``compact=True`` for batches
        whose live lanes may be scattered."""
        import numpy as np
        if batch is None:
            return
        b = batch.compact() if compact else batch
        if n is None:
            n = int(np.asarray(jnp.sum(b.valid)))
        cap = self.batch_size
        for s in range(0, n, cap):
            def cut(a):
                seg = a[s:s + cap]
                pad = cap - seg.shape[0]
                if pad:
                    seg = jnp.pad(seg, [(0, pad)] + [(0, 0)] * (seg.ndim - 1))
                return seg
            yield Batch(key=cut(b.key), id=cut(b.id), ts=cut(b.ts),
                        payload=jax.tree.map(cut, b.payload), valid=cut(b.valid))

    def _deliver(self, mp: MultiPipe, out: Batch):
        if mp.sink is not None:
            mp.sink.consume(out)
            if self._e2e_t0 is not None and self._monitor is not None:
                import time as _time
                self._monitor.registry.record_e2e(
                    _time.perf_counter() - self._e2e_t0,
                    exemplar=_tracing.tid_of(out))
                self._e2e_t0 = None    # one sample per sampled source batch
        if mp.split_fn is not None:
            self._push_split(mp, out)
        for merged in mp._outputs_to:
            if self.mode == Mode.DETERMINISTIC:
                onode = self._ordering_of(merged)
                rel = onode.push(merged.merge_inputs.index(mp), out)
                for piece in self._chunks(rel, onode.last_release_count):
                    self._push(merged, piece)
            else:
                self._push(merged, out)

    def _push_split(self, mp: MultiPipe, out: Batch):
        n = len(mp.split_branches)
        fn = mp.split_fn
        sel = jax.vmap(fn)(tuple_refs(out))
        for i, branch in enumerate(mp.split_branches):
            if getattr(sel, "ndim", 1) == 2:           # multicast mask [C, n]
                keep = sel[:, i].astype(jnp.bool_)
            else:
                keep = jnp.asarray(sel, jnp.int32) == i
            masked = out.mask(keep)
            _tracing.carry(out, masked)     # mask() builds a new Batch — the
            #                                 trace sidecar must follow it
            self._push(branch, masked)

    def _check_merge_legality(self, pipes):
        """The reference's merge rules (``wf/pipegraph.hpp:813-965,2992-3026``).

        Entry checks: >=2 distinct pipes, all members of this graph, none already
        merged into another pipe, split, or terminated by a sink. Structural
        cases: merge-ind (independent roots), merge-full (a whole split subtree,
        collapsed bottom-up like ``get_MergedNodes1``), merge-partial (siblings
        under one split parent, CONTIGUOUS branch indexes —
        ``get_MergedNodes2`` + the adjacency check at ``:903-910``)."""
        if len(pipes) < 2:
            raise RuntimeError(
                "merge must be applied to at least two MultiPipe instances "
                "(wf/pipegraph.hpp:2996-2999)")
        if len({id(p) for p in pipes}) != len(pipes):
            raise RuntimeError("a MultiPipe cannot be merged with itself "
                               "(wf/pipegraph.hpp:3003-3008)")
        for p in pipes:
            if id(p) not in self._nodes:
                raise RuntimeError("MultiPipe to be merged does not belong to "
                                   "this PipeGraph (wf/pipegraph.hpp:673-676)")
            if p._outputs_to:
                raise RuntimeError("MultiPipe has already been merged "
                                   "(application-tree leaf check, "
                                   "wf/pipegraph.hpp:678)")
            if p.split_fn is not None:
                raise RuntimeError("a split MultiPipe cannot be merged — merge "
                                   "its branches (wf/pipegraph.hpp:678)")
            if p.has_sink:
                raise RuntimeError("a MultiPipe with a sink has no output to "
                                   "merge")
        # Structural classification over the APPLICATION tree (not the dataflow
        # graph): each work item covers a set of branch indexes under its
        # app-tree parent — a split branch covers its own index; a partial-merge
        # result covers the indexes of the branches it absorbed (the reference
        # re-parents the merged AppNode under the split parent,
        # wf/pipegraph.hpp:944-952). Collapse bottom-up: whenever items under
        # one parent cover ALL its branches, they become that parent
        # (get_MergedNodes1's subtree-covering walk).
        def cover_of(p):
            """(app-tree parent, covered branch-index set) — (None, None) = root."""
            if p._merge_parent is not None:
                return p._merge_parent, set(p._covers_idx)
            par = p._dataflow_parent
            if par is None:
                return None, None
            return par, {next(i for i, b in enumerate(par.split_branches)
                              if b is p)}

        work = list(pipes)
        changed = True
        while changed:
            changed = False
            by_parent: dict = {}
            for p in work:
                par, idxs = cover_of(p)
                if par is not None:
                    key = id(par)
                    by_parent.setdefault(key, (par, []))[1].append((p, idxs))
            for par, items in by_parent.values():
                covered = set().union(*(i for _, i in items))
                if covered == set(range(len(par.split_branches))):
                    drop = {id(p) for p, _ in items}
                    work = [w for w in work if id(w) not in drop] + [par]
                    changed = True
                    break
        covers = [cover_of(w) for w in work]
        if all(par is None for par, _ in covers):
            # merge-ind (len>1) or merge-full (collapsed to one root)
            return None, ()
        if any(par is None for par, _ in covers):
            raise RuntimeError("the requested merge operation is not supported: "
                               "mixed roots and split branches "
                               "(wf/pipegraph.hpp:963-965)")
        if len({id(par) for par, _ in covers}) != 1:
            raise RuntimeError("the requested merge operation is not supported: "
                               "branches of different split parents "
                               "(wf/pipegraph.hpp:963-965)")
        par = covers[0][0]
        idxs = sorted(set().union(*(i for _, i in covers)))
        if idxs != list(range(idxs[0], idxs[0] + len(idxs))):
            raise RuntimeError("sibling MultiPipes to be merged must be "
                               "contiguous branches of the same MultiPipe "
                               "(wf/pipegraph.hpp:903-910)")
        # merge-partial: the result pipe takes this position in the app tree
        return par, tuple(idxs)

    def _exhaust(self, mp: MultiPipe):
        """A pipe's inputs are complete: flush its chain now, close its channels
        into DETERMINISTIC merge Ordering_Nodes (a frozen watermark must not gate —
        or hoard — the surviving channels, cf. close_channel), and cascade to
        consumers whose every input is now exhausted. Keeps Ordering_Node memory
        bounded when merge inputs are unbalanced."""
        if id(mp) in self._exhausted:
            return
        self._exhausted.add(id(mp))
        self._flush_pipe(mp)
        for branch in mp.split_branches:
            self._exhaust(branch)
        for merged in mp._outputs_to:
            if self.mode == Mode.DETERMINISTIC:
                onode = self._ordering_of(merged)
                rel = onode.close_channel(merged.merge_inputs.index(mp))
                for piece in self._chunks(rel, onode.last_release_count):
                    self._push(merged, piece)
            if all(id(p) in self._exhausted for p in merged.merge_inputs):
                self._exhaust(merged)

    def _flush_pipe(self, mp: MultiPipe):
        if mp._chain is None:
            return
        for out in mp._chain.flush():
            self._deliver(mp, out)

    def _results(self):
        res = {}
        for mp in self._all_pipes():
            if mp._chain is not None:
                res.update(mp._chain.result())
        return res
