"""Operator base class — uniform introspection over all operators.

Counterpart of ``Basic_Operator`` (``wf/basic_operator.hpp:47-79``): ``getName``,
``getParallelism``, ``getRoutingMode``, ``isUsed``, ``get_StatsRecords``. Here an
operator is additionally a *pure batch transform*: ``apply(state, batch) -> (state,
out_batch)`` traced into the enclosing compiled program. Chained operators therefore
fuse into one XLA program — the always-on analogue of the reference's ``ff_comb``
chaining (``wf/pipegraph.hpp:1272-1318``).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

from ..basic import routing_modes_t
from ..batch import Batch
from ..stats import Stats_Record


class Basic_Operator:
    """Base of all operators.

    Lifecycle of the device-side state (the replacement for per-replica C++ member
    state): ``init_state(payload_spec)`` builds the state pytree; ``apply`` threads it
    through each micro-batch; ``flush`` drains residual state at EOS (the reference's
    ``eosnotify`` paths, e.g. ``wf/win_seq.hpp:468-529``)."""

    #: set by subclasses
    routing: routing_modes_t = routing_modes_t.FORWARD

    #: builder hints (withBatch / withDevice, the reference GPU builders'
    #: batch_len / gpu_id, ``wf/builders_gpu.hpp:115-130``): micro-batch
    #: capacity ceiling honored by CompiledChain/Pipeline, and the jax.Device
    #: the operator's state (and therefore its fused chain) is placed on.
    _batch_hint: int = None
    _device = None
    #: outcome of MultiPipe.chain() vs add(): True when the operator was fused
    #: queue-free (FORWARD, reference ``chain_operator`` success,
    #: ``wf/pipegraph.hpp:1272-1318``), False when it fell back to routed add;
    #: None before graph placement. Rendered by dump_DOTGraph.
    _chained = None
    #: event-time observability toggle (``MonitoringConfig.event_time``), set
    #: by CompiledChain BEFORE ``bind_geometry``/``init_state`` when the
    #: enclosing driver resolved the sub-toggle on.  Geometry-binding: when
    #: True, stateful event-time operators add an on-device lateness
    #: histogram to their state pytree and fold one masked reduction per
    #: batch into it (``observability/event_time.py``); when False (the
    #: default) state and compiled programs are byte-for-byte unchanged.
    _event_time = False

    def __init__(self, name: str, parallelism: int = 1):
        self._name = name
        self._parallelism = max(1, int(parallelism))
        self._used = False
        self._stats = [Stats_Record(name, i) for i in range(self._parallelism)]
        #: host callback run once per replica at teardown with that replica's
        #: RuntimeContext (reference closing_func at svc_end; withClosingFunction,
        #: wf/builders.hpp common methods)
        self.closing_func = None

    def close(self) -> None:
        """Invoke the closing function (if any) once per replica — the reference
        runs ``closing_func(RuntimeContext&)`` in every replica's ``svc_end``."""
        if self.closing_func is None:
            return
        from ..context import RuntimeContext
        own = getattr(self, "context", None)
        for i in range(self._parallelism):
            ctx = (own if own is not None and own.getReplicaIndex() == i
                   else RuntimeContext(self._parallelism, i))
            self.closing_func(ctx)

    # -- Basic_Operator surface (wf/basic_operator.hpp:47-79) -------------------------

    def getName(self) -> str:
        return self._name

    def getParallelism(self) -> int:
        return self._parallelism

    def scope_name(self) -> str:
        """``Class:name`` — the ``jax.named_scope`` a compiled chain traces
        this operator's ``apply`` under, so the profiler's device operations
        carry their operator (HLO metadata only: no equation changes)."""
        return f"{type(self).__name__}:{self._name}".replace("/", "_")

    def getRoutingMode(self) -> routing_modes_t:
        return self.routing

    def isUsed(self) -> bool:
        return self._used

    def get_StatsRecords(self):
        return list(self._stats)

    def collect_stats(self, state: Any = None) -> None:
        """Sync device-resident counters carried in ``state`` into the host
        ``Stats_Record`` (e.g. Win_SeqFFAT's OLD-drop counter). Called by the
        metrics registry at snapshot time and by the drivers at EOS — a tiny
        D2H read off the hot path; no-op by default."""

    def _publish_stage_counters(self, counters: dict) -> None:
        """Stash per-operator counters/gauges for the snapshot's
        ``row["counters"]`` and the ``windflow_stage_*`` Prometheus surface.
        Names must be registered in ``observability/names.py`` — the
        WF240/241 one-source-of-truth discipline applied to the per-stage
        namespace (a typo'd name raises here instead of silently forking the
        exposition)."""
        from ..observability.names import STAGE_COUNTERS, STAGE_GAUGES
        for k in counters:
            if k not in STAGE_COUNTERS and k not in STAGE_GAUGES:
                raise ValueError(
                    f"{self._name}: stage counter {k!r} is not registered in "
                    f"observability/names.py::STAGE_COUNTERS/STAGE_GAUGES — "
                    f"register it there (the emission registries the linter "
                    f"gates)")
        self._stage_counters = dict(counters)

    def stage_counters(self) -> dict:
        """Most recently published per-operator counters (empty until the
        first ``collect_stats`` of an operator that publishes any)."""
        return dict(getattr(self, "_stage_counters", ()) or {})

    def event_time_stats(self, state: Any = None) -> Optional[dict]:
        """Event-time section of the monitoring snapshot's operator row
        (watermark frontier, state occupancy/pressure, lateness histograms)
        — None for operators without an event-time surface.  Called at
        snapshot time only (reporter thread / EOS): implementations may do
        small D2H reads of carried state, exactly like ``collect_stats``."""
        return None

    def drop_counters(self, state: Any = None) -> dict:
        """Host ints of the operator's device-resident drop counters, keyed
        by the ``names.py::STAGE_COUNTERS`` drop names — read by the chain's
        sampled-push readback (event_time monitoring only) to journal
        ``lateness_drop`` events with trace coordinates.  Empty by
        default."""
        return {}

    def tier_controllers(self) -> tuple:
        """The operator's tiered-state controllers (``state/tiered.py``
        ``TieredTable``, one per tiered table) — empty unless the operator
        was built with ``tiered=`` on.  ``CompiledChain`` runs their
        ``maintain`` after every push (the async spill settle point) and
        snapshots/restores their host stores with the operator states."""
        return ()

    # pythonic aliases
    name = property(getName)
    parallelism = property(getParallelism)

    # -- batch-transform surface ------------------------------------------------------

    def bind_geometry(self, batch_capacity: int) -> None:
        """Called once by the compiler with the incoming micro-batch capacity, before
        ``init_state`` — lets stateful operators size rings/budgets relative to the
        batch (the reference sizes GPU batches similarly from batch_len/slide gcds,
        ``wf/win_seq_gpu.hpp`` tuples_per_batch)."""

    def out_capacity(self, in_capacity: int) -> int:
        """Capacity of the outgoing batch (FlatMap expands by max_fanout; windowed
        operators emit max_wins rows)."""
        return in_capacity

    def init_state(self, payload_spec: Any) -> Any:
        """Device state pytree for this operator (None if stateless)."""
        return None

    def out_spec(self, payload_spec: Any) -> Any:
        """Output payload spec given the input payload spec (type propagation — the
        analogue of the reference's typeid check at add/chain time,
        ``wf/pipegraph.hpp:1573-1578``)."""
        return payload_spec

    def apply(self, state: Any, batch: Batch) -> Tuple[Any, Batch]:
        raise NotImplementedError

    def flush(self, state: Any) -> Tuple[Any, Optional[Batch]]:
        """Drain residual state at EOS. Returns (state, out_batch or None)."""
        return state, None

    def flush_capacity(self, in_capacity: int) -> Optional[int]:
        """Capacity of the batches ``flush`` emits behind input batches of
        ``in_capacity``, where it is known ahead (None: unknown, or none)."""
        return None

    def _mark_used(self):
        self._used = True

    def __repr__(self):
        return f"{type(self).__name__}({self._name!r}, parallelism={self._parallelism})"
