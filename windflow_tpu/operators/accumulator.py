"""Accumulator — per-key rolling reduce.

Counterpart of ``wf/accumulator.hpp`` (class at ``:61``, per-key state map
``:103-104``): ``void(const tuple&, result&)`` folds each tuple into its key's
accumulator (seeded with ``init_value``) and emits the updated value per input tuple;
routing is always KEYBY (``wf/pipegraph.hpp:1817-1820``).

TPU formulation: the per-key accumulator table lives in HBM (``[K, ...]``); each batch
runs a *segmented inclusive prefix scan* in stream order carrying the table in
(associative combines — sort-by-key + ``associative_scan`` + unsort, see
``ops/segment.py``), then scatters each key's last value back. For non-associative
fold functions the general per-rank round loop of ``KeyedMap`` applies; the common
streaming aggregations (sum/count/min/max — YSB counts campaigns) are associative.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from ..basic import routing_modes_t, DEFAULT_MAX_KEYS
from ..batch import Batch, tuple_refs
from ..ops.segment import leafwise, segment_prefix_scan, segment_reduce
from .base import Basic_Operator


class Accumulator(Basic_Operator):
    """Associative rolling reduce.

    ``value_fn(t) -> pytree`` extracts the value to fold from each tuple;
    ``combine(a, b) -> pytree`` is the associative fold (default add);
    ``init_value`` seeds every key (reference init_value ctor arg).
    Emits per input tuple the post-fold accumulator (payload = accumulator pytree)."""

    routing = routing_modes_t.KEYBY

    def __init__(self, value_fn: Callable, *, init_value: Any = 0.0,
                 combine: Callable = None, identity: Any = 0,
                 num_keys: int = DEFAULT_MAX_KEYS, name: str = "accumulator",
                 parallelism: int = 1):
        super().__init__(name, parallelism)
        self.value_fn = value_fn
        self.combine = combine or jnp.add
        self.identity = identity
        self.init_value = init_value
        self.num_keys = int(num_keys)

    def init_state(self, payload_spec: Any):
        val = jax.eval_shape(self.value_fn, _ref_spec(payload_spec))
        return jax.tree.map(
            lambda s: jnp.broadcast_to(jnp.asarray(self.init_value, s.dtype),
                                       (self.num_keys,) + s.shape).copy(), val)

    def out_spec(self, payload_spec: Any) -> Any:
        return jax.eval_shape(self.value_fn, _ref_spec(payload_spec))

    def apply(self, state, batch: Batch):
        vals = jax.vmap(self.value_fn)(tuple_refs(batch))
        # inclusive per-key prefix in stream order, seeded by the HBM table
        # ``combine`` acts leaf by leaf here; the segment ops take structs
        fold = leafwise(self.combine)
        prefix = segment_prefix_scan(vals, batch.key, batch.valid, fold,
                                     self.identity, carry_in=state)
        # update the table with each key's total fold for this batch
        batch_red = segment_reduce(vals, batch.key, batch.valid, self.num_keys,
                                   combine=None if self.combine is jnp.add else fold,
                                   identity=self.identity)
        if self.combine is jnp.add:
            state = jax.tree.map(jnp.add, state, batch_red)
        else:
            touched = segment_reduce(
                jnp.ones_like(batch.key), batch.key, batch.valid, self.num_keys) > 0
            state = jax.tree.map(
                lambda t, r: jnp.where(
                    touched.reshape(touched.shape + (1,) * (r.ndim - 1)),
                    self.combine(t, r), t),
                state, batch_red)
        return state, batch.with_payload(prefix)


def _ref_spec(payload_spec):
    from ..batch import TupleRef
    return TupleRef(key=jax.ShapeDtypeStruct((), jnp.int32),
                    id=jax.ShapeDtypeStruct((), jnp.int32),
                    ts=jax.ShapeDtypeStruct((), jnp.int32), data=payload_spec)
