"""The micro-batch: the unit of data flow in the whole framework.

The reference moves ONE heap-allocated tuple at a time between operator threads
(``new tuple_t()`` per emitted tuple, ``wf/source.hpp:184``, ``wf/shipper.hpp:87``) and
only its GPU operators batch (``wf/win_seq_gpu.hpp:352-560``). On TPU the only winning
model is micro-batch-at-a-time with structure-of-arrays buffers, so the *stream itself*
is a sequence of fixed-capacity :class:`Batch` values:

- ``key``/``id``/``ts`` are the reference's tuple control-field contract
  ``getControlFields() -> (key, id, ts)`` (``wf/window.hpp:132``,
  ``src/graph_test/graph_common.hpp:69-80``) lifted to arrays.
- ``payload`` is an arbitrary pytree of ``[C, ...]`` arrays — the user tuple fields.
- ``valid`` is the occupancy mask: fixed capacity + mask is how every dynamic-shape
  problem (filtering, flatmap fan-out, partial flush at EOS) is made XLA-static.

A :class:`Batch` is a JAX pytree, so it flows through ``jit``/``vmap``/``shard_map``
unchanged; sharding the leading (capacity) axis over a mesh is the data-parallel
replication of the reference (every operator's ``parallelism`` replicas,
``wf/source.hpp:284-296``).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

#: dtype used for the (key, id, ts) control fields. int32: TPU-native word size; per-key
#: ids and relative-usecs timestamps fit comfortably for streaming benchmarks.
CTRL_DTYPE = jnp.int32

#: Host-side sidecar metadata convention (the causal-tracing trace id,
#: ``observability/tracing.py``): metadata rides on the *Python* Batch object
#: under this attribute — set via ``object.__setattr__`` on the frozen
#: dataclass, NEVER as a pytree field, so compiled programs, cached
#: executables, and checkpoints are byte-identical with tracing on or off.
#: The sidecar does not survive jit/``jax.tree.map``/``dataclasses.replace``
#: (those build new objects); driver loops re-attach it across operator hops
#: with ``observability.tracing.carry`` (tracing.py mirrors this attribute
#: name as a literal — it must stay importable without JAX, so it cannot
#: import this module).  Rebatching (``split_batch``/``concat_batches``)
#: intentionally drops it: a merged or split batch is no longer the ingested
#: unit the id names.
TRACE_META_ATTR = "_wf_trace"


def trace_meta(batch):
    """The batch's host-side trace metadata (trace id), or None — the
    user-facing reader (e.g. inside a Sink callback over a host batch);
    runtime attach/propagate lives in ``observability.tracing``."""
    return getattr(batch, TRACE_META_ATTR, None)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Batch:
    """Fixed-capacity SoA micro-batch of tuples.

    All leaves share the leading capacity axis ``C``. Lanes where ``valid`` is False
    are padding: operators must ignore them and must produce masked-out garbage only
    in invalid lanes.
    """

    key: jax.Array       # i32[C] — key slot in [0, max_keys)
    id: jax.Array        # i32[C] — per-key progressive id (control field "id")
    ts: jax.Array        # i32[C] — timestamp (control field "ts")
    payload: Any         # pytree of [C, ...] arrays
    valid: jax.Array     # bool[C]

    # -- introspection ----------------------------------------------------------------

    @property
    def capacity(self) -> int:
        return self.key.shape[0]

    def count(self) -> jax.Array:
        """Number of live tuples (traced scalar)."""
        return jnp.sum(self.valid.astype(jnp.int32))

    # -- construction -----------------------------------------------------------------

    @staticmethod
    def empty(capacity: int, payload_spec: Any) -> "Batch":
        """An all-invalid batch. ``payload_spec`` is a pytree of
        ``jax.ShapeDtypeStruct`` (without the capacity axis) or example arrays."""
        def mk(leaf):
            shape = getattr(leaf, "shape", ())
            dtype = getattr(leaf, "dtype", jnp.float32)
            return jnp.zeros((capacity,) + tuple(shape), dtype)
        return Batch(
            key=jnp.zeros((capacity,), CTRL_DTYPE),
            id=jnp.zeros((capacity,), CTRL_DTYPE),
            ts=jnp.zeros((capacity,), CTRL_DTYPE),
            payload=jax.tree.map(mk, payload_spec),
            valid=jnp.zeros((capacity,), jnp.bool_),
        )

    @staticmethod
    def of(payload: Any, key=None, id=None, ts=None, valid=None) -> "Batch":
        """Build a batch from payload arrays (host or device)."""
        leaves = jax.tree.leaves(payload)
        if not leaves:
            raise ValueError("payload must contain at least one array")
        c = np.shape(leaves[0])[0]
        z = jnp.zeros((c,), CTRL_DTYPE)
        return Batch(
            key=z if key is None else jnp.asarray(key, CTRL_DTYPE),
            id=z if id is None else jnp.asarray(id, CTRL_DTYPE),
            ts=z if ts is None else jnp.asarray(ts, CTRL_DTYPE),
            payload=jax.tree.map(jnp.asarray, payload),
            valid=jnp.ones((c,), jnp.bool_) if valid is None else jnp.asarray(valid, jnp.bool_),
        )

    # -- transforms -------------------------------------------------------------------

    def replace(self, **kw) -> "Batch":
        return dataclasses.replace(self, **kw)

    def with_payload(self, payload: Any) -> "Batch":
        return dataclasses.replace(self, payload=payload)

    def mask(self, keep: jax.Array) -> "Batch":
        """Intersect the validity mask with ``keep`` (the Filter primitive)."""
        return dataclasses.replace(self, valid=self.valid & keep)

    def compact(self) -> "Batch":
        """Pack live tuples to the front (stable). Counterpart of the reference GPU
        emitter's prescan + ``create_sub_batch`` compaction
        (``wf/standard_nodes_gpu.hpp:52-238``, scan suite ``wf/gpu_utils.hpp:330-417``).

        Invalid lanes are moved to the tail and zero-masked. Shape is unchanged."""
        c = self.capacity
        # stable partition: sort by (!valid, position)
        order = jnp.argsort(jnp.where(self.valid, 0, 1), stable=True)
        take = lambda a: jnp.take(a, order, axis=0)
        return Batch(
            key=take(self.key), id=take(self.id), ts=take(self.ts),
            payload=jax.tree.map(take, self.payload),
            valid=take(self.valid),
        )

    def select(self, idx: jax.Array, valid: jax.Array) -> "Batch":
        """Gather lanes ``idx`` with a new validity mask (size may differ)."""
        take = lambda a: jnp.take(a, idx, axis=0)
        return Batch(
            key=take(self.key), id=take(self.id), ts=take(self.ts),
            payload=jax.tree.map(take, self.payload),
            valid=valid & take(self.valid),
        )

    def sorted_by(self, *, by: str = "ts") -> "Batch":
        """Stable sort live tuples by ``ts`` or ``id`` (invalid lanes to the tail).
        The batch-level counterpart of the reference ``Ordering_Node``
        (``wf/ordering_node.hpp:124-280``): DETERMINISTIC-mode order restoration."""
        k = self.ts if by == "ts" else self.id
        big = jnp.iinfo(CTRL_DTYPE).max
        order = jnp.argsort(jnp.where(self.valid, k, big), stable=True)
        take = lambda a: jnp.take(a, order, axis=0)
        return Batch(
            key=take(self.key), id=take(self.id), ts=take(self.ts),
            payload=jax.tree.map(take, self.payload),
            valid=take(self.valid),
        )

    # -- host side --------------------------------------------------------------------

    def to_host(self) -> "Batch":
        """Every leaf as numpy, in one device->host round trip: the copy of
        each device leaf is started (``jax.Array.copy_to_host_async``) before
        the first is read, so the leaves travel together instead of one
        blocking fetch after another. numpy leaves pass through."""
        for leaf in jax.tree.leaves(self):
            copy_async = getattr(leaf, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        return jax.tree.map(np.asarray, self)

    def live_payload(self) -> Any:
        """Host-side: payload restricted to live lanes (numpy)."""
        v = np.asarray(self.valid)
        return jax.tree.map(lambda a: np.asarray(a)[v], self.payload)


def hash_key_to_slot(key, num_slots: int):
    """Map arbitrary user keys (strings, large ints, numpy arrays of ints) to key
    slots in ``[0, num_slots)`` — the reference's ``hash(key) % n`` routing contract
    (``wf/standard_emitter.hpp:88-99``) applied at ingest time. Deterministic across
    runs (unlike Python's salted ``hash``)."""
    if isinstance(key, (str, bytes)):
        return _fnv1a(key) % num_slots
    if isinstance(key, (int, np.integer)):
        # same arithmetic as the array branch: Knuth multiply in uint64 wraparound
        k = int(key) & 0xFFFFFFFFFFFFFFFF
        return int((k * 2654435761) % (1 << 64) % num_slots)
    arr = np.asarray(key)
    if arr.dtype.kind in "USiu" and arr.ndim > 0:
        # array path: one native C pass when the library is built (bit-identical
        # FNV-1a / Knuth arithmetic, windflow_tpu/native/ingest.cpp)
        from .native import hash_keys_native
        slots = hash_keys_native(arr, num_slots)
        if slots is not None:
            return slots
    if arr.dtype.kind in "USO":                        # strings / bytes / objects
        # hash each distinct key once (batches typically repeat few keys)
        uniq, inv = np.unique(arr.ravel(), return_inverse=True)
        slots = np.asarray([hash_key_to_slot(u, num_slots) for u in uniq.tolist()],
                           np.int32)
        return slots[inv].reshape(arr.shape)
    if arr.dtype.kind not in "iu":
        raise TypeError(
            f"hash_key_to_slot: keys must be ints, strings, or bytes, got dtype "
            f"{arr.dtype} (float keys would silently truncate and merge)")
    return ((arr.astype(np.uint64) * np.uint64(2654435761)) % np.uint64(num_slots)
            ).astype(np.int32)


def _fnv1a(s) -> int:
    if isinstance(s, str):
        data = s.encode()
    elif isinstance(s, bytes):
        data = s
    else:
        raise TypeError(f"hash_key_to_slot: unhashable key {s!r} "
                        f"(expected str/bytes, got {type(s).__name__})")
    h = 2166136261
    for ch in data:
        h = ((h ^ ch) * 16777619) & 0xFFFFFFFF         # FNV-1a
    return h


def concat_batches(a: Batch, b: Batch) -> Batch:
    """Concatenate two batches along the capacity axis (merge primitive)."""
    cat = lambda x, y: jnp.concatenate([x, y], axis=0)
    return Batch(
        key=cat(a.key, b.key), id=cat(a.id, b.id), ts=cat(a.ts, b.ts),
        payload=jax.tree.map(cat, a.payload, b.payload),
        valid=cat(a.valid, b.valid),
    )


def split_batch(batch: Batch, capacity: int) -> list:
    """Slice a batch into ``capacity``-sized pieces along the capacity axis —
    the inverse of :func:`concat_batches` and the counterpart of the reference
    GPU emitter's ``create_sub_batch`` (``wf/standard_nodes_gpu.hpp``). Lane
    content (including the validity mask) is preserved verbatim, so results
    are invariant to the split. Requires exact divisibility: the control
    plane's capacity ladder is built so every down-rung divides the base."""
    c = batch.capacity
    capacity = int(capacity)
    if capacity < 1 or c % capacity:
        raise ValueError(f"split_batch: capacity {capacity} does not divide "
                         f"the batch capacity {c}")
    if capacity == c:
        return [batch]
    cut = lambda a, s: a[s:s + capacity]
    return [Batch(key=cut(batch.key, s), id=cut(batch.id, s),
                  ts=cut(batch.ts, s),
                  payload=jax.tree.map(lambda a: cut(a, s), batch.payload),
                  valid=cut(batch.valid, s))
            for s in range(0, c, capacity)]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TupleRef:
    """Per-tuple view handed to user functions under ``vmap`` — the counterpart of the
    reference passing ``tuple_t&`` into the user lambda. ``key``/``id``/``ts`` are the
    control fields; payload fields are reachable as attributes (dict payloads) or via
    ``.data`` (any pytree)."""

    key: jax.Array
    id: jax.Array
    ts: jax.Array
    data: Any

    def __getattr__(self, name):
        data = object.__getattribute__(self, "data")
        if isinstance(data, dict) and name in data:
            return data[name]
        raise AttributeError(name)


def tuple_refs(batch: Batch) -> TupleRef:
    """Batched TupleRef (each field keeps its capacity axis; vmap strips it)."""
    return TupleRef(key=batch.key, id=batch.id, ts=batch.ts, data=batch.payload)


class MutableTupleRef:
    """Mutable per-tuple view backing the reference's *in-place* signatures
    (``void(tuple_t&)`` Map, ``wf/map.hpp:64-74``): payload attribute writes are
    recorded during tracing and become the output payload. Control fields stay
    read-only (the reference mutates them only via ``setControlFields``, which
    routing owns here). Requires a dict payload (named fields)."""

    __slots__ = ("_ctrl", "_data")

    def __init__(self, ref: TupleRef):
        object.__setattr__(self, "_ctrl",
                           {"key": ref.key, "id": ref.id, "ts": ref.ts})
        data = ref.data
        if not isinstance(data, dict):
            raise TypeError(
                "in-place map functions need a dict payload (named fields); "
                "return a new payload instead for pytree payloads")
        object.__setattr__(self, "_data", dict(data))

    def __getattr__(self, name):
        ctrl = object.__getattribute__(self, "_ctrl")
        if name in ctrl:
            return ctrl[name]
        data = object.__getattribute__(self, "_data")
        if name == "data":
            return data
        if name in data:
            return data[name]
        raise AttributeError(name)

    def __setattr__(self, name, value):
        if name in ("key", "id", "ts"):
            raise TypeError(
                f"control field '{name}' is read-only in user functions (the "
                f"reference owns setControlFields in its routing layer)")
        object.__getattribute__(self, "_data")[name] = value

    def _payload(self):
        return dict(object.__getattribute__(self, "_data"))
