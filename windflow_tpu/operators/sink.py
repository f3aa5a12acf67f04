"""Sink — stream absorption.

Counterpart of ``wf/sink.hpp`` (class at ``:67``, signature slots ``:70-77``): the
reference calls ``void(optional<tuple>&)`` per tuple (empty optional at EOS). Two
TPU-native flavours:

- ``Sink``: host callback invoked once per *batch* with the live tuples as numpy
  arrays (``f(batch_view)`` / rich) — the general egress path. Called with ``None`` at
  EOS, mirroring the empty-optional convention.
- ``ReduceSink``: an in-graph reduction (e.g. global sum / count / collect-last) that
  stays on device and is fetched once at the end — this is what the reference test
  suites do with their ``atomic<long> global_sum`` oracle
  (``src/graph_test/graph_common.hpp:32``), and avoids D2H per batch entirely.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..basic import routing_modes_t
from ..batch import Batch, tuple_refs
from ..context import RuntimeContext
from ..meta import classify_sink
from ..observability import tracing as _tracing
from .base import Basic_Operator


class Sink(Basic_Operator):
    """Host-callback sink. The callback receives a dict with numpy ``key/id/ts``,
    payload leaves restricted to live lanes.

    A result batch crosses to the host in one round trip: ``consume`` starts the
    copy of every leaf before it reads any (``Batch.to_host``), so the leaves
    travel together instead of one blocking fetch after another, and delivers
    before it returns.

    ``async_depth > 0`` routes batches through an
    :class:`~windflow_tpu.runtime.async_sink.AsyncResultShipper` instead: the
    callback fires once the copy of a batch ``async_depth`` ships old has
    landed, so a batch's delivery is deferred past later pushes and result
    transfer overlaps device compute (the reference GPU D2H overlap,
    ``wf/win_seq_gpu.hpp:243-260,524``). Callback order stays FIFO; EOS
    (``None``) drains everything first."""

    def __init__(self, fn: Callable, *, name: str = "sink", parallelism: int = 1,
                 keyed: bool = False, async_depth: int = 0,
                 context: Optional[RuntimeContext] = None):
        super().__init__(name, parallelism)
        self.fn = fn
        self.is_rich = classify_sink(fn)
        self.routing = routing_modes_t.KEYBY if keyed else routing_modes_t.FORWARD
        self.async_depth = int(async_depth)
        self._shipper = None
        self._nbytes_by_cap = {}    # capacity -> bytes of one result batch
        self.context = context or RuntimeContext(parallelism, 0)

    def _deliver(self, view):
        if self.is_rich:
            self.fn(view, self.context)
        else:
            self.fn(view)

    def _deliver_host(self, host: Batch, pos: Optional[int] = None):
        v = host.valid
        # the whole batch crossed device->host to get here: count the transfer
        # (wf/stats_record.hpp:78-80 bytes_copied_dh) + live-tuple ingress
        rec = self._stats[0]
        rec.bytes_copied_dh += sum(
            a.nbytes for a in jax.tree.leaves(host) if hasattr(a, "nbytes"))
        n_live = int(v.sum())
        rec.record_input(n_live)
        if not n_live:
            return
        with _tracing.span("wf.sink.deliver", pos=pos, n_live=n_live):
            self._deliver({
                "key": host.key[v], "id": host.id[v], "ts": host.ts[v],
                "payload": jax.tree.map(lambda a: a[v], host.payload),
            })

    def consume(self, batch: Optional[Batch]):
        """Host-side: deliver one batch (or None at EOS) to the user callback."""
        with _tracing.span("wf.sink.consume", pos=_tracing.pos_of(batch)):
            self._consume(batch)

    def _consume(self, batch: Optional[Batch]):
        if self.async_depth:
            if self._shipper is None:
                from ..runtime.async_sink import AsyncResultShipper
                self._shipper = AsyncResultShipper(depth=self.async_depth)
            if batch is None:
                for rec in self._shipper.drain():
                    self._deliver_host(rec.value)
                self._deliver(None)
                return
            self._shipper.ship(batch)
            for rec in self._shipper.harvest():
                self._deliver_host(rec.value)
            return
        if batch is None:
            self._deliver(None)
            return
        pos = _tracing.pos_of(batch)
        nbytes = self._nbytes_by_cap.get(batch.capacity)
        if nbytes is None:
            nbytes = self._nbytes_by_cap[batch.capacity] = sum(
                a.nbytes for a in jax.tree.leaves(batch))
        # waits for the device to finish the batch and for its leaves' copies,
        # all started before the first is read: one round trip, not one a leaf
        with _tracing.span("wf.sink.d2h", pos=pos, bytes=nbytes):
            host = batch.to_host()
        self._deliver_host(host, pos)


class ReduceSink(Basic_Operator):
    """In-graph reduction sink: ``value_fn(t) -> pytree`` per tuple, associative
    ``combine`` across all tuples of the stream (device-resident accumulator)."""

    def __init__(self, value_fn: Callable, *, combine: Callable = None, identity=0,
                 name: str = "reduce_sink", parallelism: int = 1):
        super().__init__(name, parallelism)
        self.value_fn = value_fn
        self.combine = combine or jnp.add
        self.identity = identity

    def init_state(self, payload_spec: Any):
        from .accumulator import _ref_spec
        val = jax.eval_shape(self.value_fn, _ref_spec(payload_spec))
        return jax.tree.map(
            lambda s: jnp.broadcast_to(jnp.asarray(self.identity, s.dtype),
                                       s.shape).copy(), val)

    def apply(self, state, batch: Batch):
        vals = jax.vmap(self.value_fn)(tuple_refs(batch))
        def red(acc, v):
            m = batch.valid.reshape(batch.valid.shape + (1,) * (v.ndim - 1))
            v = jnp.where(m, v, jnp.asarray(self.identity, v.dtype))
            if self.combine is jnp.add:
                return acc + jnp.sum(v, axis=0)
            return self.combine(acc, jax.lax.reduce(
                v, jnp.asarray(self.identity, v.dtype), self.combine, (0,)))
        state = jax.tree.map(red, state, vals)
        return state, batch

    def result(self, state):
        return state
