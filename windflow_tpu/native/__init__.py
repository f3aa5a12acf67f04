"""ctypes binding for the native host runtime (SPSC queues, thread pinning,
record framing).

``libwfnative.so`` is built from ``spsc_queue.cpp`` + ``ingest.cpp`` on first
use and REBUILT whenever it is older than a source or the Makefile — the
library is gitignored and survives checkouts, so a binary that merely exists
proves nothing about the code beside it. A build or load failure raises
:class:`NativeBuildError` with the compiler's output: the served path
(``RecordSource`` framing, the threaded drivers' rings) never drops to a slower
implementation on its own. The pure-Python shims (deque ring, numpy framing)
run only when ``WF_PYTHON_SHIM=1`` asks for them — a host without a C++
toolchain."""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from collections import deque

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libwfnative.so")
#: what the library is built from; any of these newer than the .so = stale
_SOURCES = ("spsc_queue.cpp", "ingest.cpp", "Makefile")

_lib = None
_load_lock = threading.Lock()


class NativeBuildError(RuntimeError):
    """``libwfnative.so`` could not be built or loaded."""


def _stale() -> bool:
    try:
        built = os.path.getmtime(_SO)
    except OSError:
        return True
    return any(os.path.getmtime(os.path.join(_DIR, f)) > built
               for f in _SOURCES)


def _build() -> None:
    """Compile to a per-process temp name and rename over the target only on
    success (concurrent first imports — bench children, test subprocesses —
    each build their own file; the last rename wins, all are identical)."""
    tmp = f"{os.path.basename(_SO)}.{os.getpid()}.tmp"
    try:
        proc = subprocess.run(["make", "-C", _DIR, f"TARGET={tmp}"],
                              capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            raise NativeBuildError(
                f"building {_SO} failed (rc={proc.returncode}):\n"
                f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
        os.replace(os.path.join(_DIR, tmp), _SO)
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeBuildError(f"building {_SO} failed: {e}") from e
    finally:
        try:
            os.remove(os.path.join(_DIR, tmp))
        except OSError:
            pass


def _load():
    """The bound library; None only under ``WF_PYTHON_SHIM=1``."""
    global _lib
    if _lib is not None:
        return _lib
    if os.environ.get("WF_PYTHON_SHIM", "") not in ("", "0"):
        return None
    with _load_lock:
        if _lib is not None:
            return _lib
        if _stale():
            _build()
        try:
            lib = ctypes.CDLL(_SO)
        except OSError as e:
            raise NativeBuildError(f"loading {_SO} failed: {e}") from e
        missing = _bind(lib)
        if missing:
            raise NativeBuildError(
                f"{_SO} lacks symbol {missing!r} although it is newer than "
                f"its sources — _SYMBOLS and the C++ sources disagree")
        _lib = lib
        return lib


_P = ctypes.POINTER
#: every exported symbol with its signature (None restype = ctypes default c_int)
_SYMBOLS = [
    ("wf_queue_create", ctypes.c_void_p, [ctypes.c_uint64]),
    ("wf_queue_destroy", None, [ctypes.c_void_p]),
    ("wf_queue_push", ctypes.c_int, [ctypes.c_void_p, ctypes.c_uint64]),
    ("wf_queue_pop", ctypes.c_int, [ctypes.c_void_p, _P(ctypes.c_uint64)]),
    ("wf_queue_push_spin", ctypes.c_int,
     [ctypes.c_void_p, ctypes.c_uint64, ctypes.c_uint64]),
    ("wf_queue_pop_spin", ctypes.c_int,
     [ctypes.c_void_p, _P(ctypes.c_uint64), ctypes.c_uint64, ctypes.c_uint64]),
    ("wf_queue_size", ctypes.c_uint64, [ctypes.c_void_p]),
    ("wf_pin_thread", ctypes.c_int, [ctypes.c_int]),
    ("wf_hardware_concurrency", ctypes.c_int, []),
    ("wf_queue_selfbench", ctypes.c_double, [ctypes.c_uint64, ctypes.c_uint64]),
    ("wf_unpack_records", None,
     [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32,
      _P(ctypes.c_uint64), _P(ctypes.c_uint64), _P(ctypes.c_char_p)]),
    ("wf_pack_records", None,
     [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint32,
      _P(ctypes.c_uint64), _P(ctypes.c_uint64), _P(ctypes.c_char_p)]),
    ("wf_hash_str_keys", None,
     [ctypes.c_char_p, _P(ctypes.c_int64), ctypes.c_uint64, ctypes.c_uint32,
      _P(ctypes.c_int32)]),
    ("wf_hash_fixed_str_keys", None,
     [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_uint64, ctypes.c_uint64,
      ctypes.c_uint32, _P(ctypes.c_int32)]),
    ("wf_hash_int_keys", None,
     [_P(ctypes.c_int64), ctypes.c_uint64, ctypes.c_uint32, _P(ctypes.c_int32)]),
]


def _bind(lib):
    """Bind every symbol in ``_SYMBOLS``; returns the first missing name."""
    for name, restype, argtypes in _SYMBOLS:
        if not hasattr(lib, name):
            return name
        fn = getattr(lib, name)
        if restype is not None:
            fn.restype = restype
        fn.argtypes = argtypes
    return None


class SPSCQueue:
    """Bounded SPSC queue of Python objects backed by the native ring: the ring moves
    opaque uint64 tokens; a side table maps tokens to objects (batch handles). The
    token table is written only by the producer and cleared only by the consumer —
    the SPSC discipline keeps it race-free without locks."""

    def __init__(self, capacity: int = 1024):
        lib = _load()
        self._lib = lib
        self._objs = {}
        self._seq = 0
        if lib is not None:
            self._q = lib.wf_queue_create(capacity)
        else:                               # WF_PYTHON_SHIM=1
            self._q = None
            self._dq = deque()
            self._cap = capacity
            self._cv = threading.Condition()

    def push(self, obj, spin: int = 1024) -> None:
        if self._q is not None:
            self._seq += 1
            tok = self._seq
            self._objs[tok] = obj
            self._lib.wf_queue_push_spin(self._q, tok, spin)
        else:
            with self._cv:
                while len(self._dq) >= self._cap:
                    self._cv.wait(0.001)
                self._dq.append(obj)
                self._cv.notify_all()

    def pop(self, spin: int = 1024, max_yields: int = 1 << 20):
        """Returns (ok, obj)."""
        if self._q is not None:
            tok = ctypes.c_uint64()
            ok = self._lib.wf_queue_pop_spin(self._q, ctypes.byref(tok),
                                             spin, max_yields)
            if not ok:
                return False, None
            return True, self._objs.pop(tok.value)
        with self._cv:
            while not self._dq:
                if not self._cv.wait(1.0):
                    return False, None
            obj = self._dq.popleft()
            self._cv.notify_all()
            return True, obj

    def size(self) -> int:
        if self._q is not None:
            return int(self._lib.wf_queue_size(self._q))
        return len(self._dq)

    def __del__(self):
        if getattr(self, "_q", None) is not None and self._lib is not None:
            self._lib.wf_queue_destroy(self._q)
            self._q = None


def unpack_records(records, fields=None):
    """AoS -> SoA in one native pass: ``records`` is a numpy structured array
    (the framing of network/disk ingest); returns ``{field: contiguous column}``.
    The native counterpart of the reference's per-tuple Source/Shipper copy path
    (``wf/source.hpp:184``, ``wf/shipper.hpp:87``). numpy per-field copies under
    ``WF_PYTHON_SHIM=1`` or for non-contiguous input."""
    import numpy as np
    lib = _load()
    dt = records.dtype
    names = list(fields if fields is not None else dt.names)
    if lib is None or not records.flags["C_CONTIGUOUS"]:
        return {f: np.ascontiguousarray(records[f]) for f in names}
    n = records.shape[0]
    outs, dsts, offs, szs = {}, [], [], []
    for f in names:
        fdt, off = dt.fields[f][0], dt.fields[f][1]
        col = np.empty(n, fdt)
        outs[f] = col
        dsts.append(col.ctypes.data_as(ctypes.c_char_p))
        offs.append(off)
        szs.append(fdt.itemsize)
    nf = len(names)
    lib.wf_unpack_records(
        records.ctypes.data_as(ctypes.c_char_p), n, dt.itemsize, nf,
        (ctypes.c_uint64 * nf)(*offs), (ctypes.c_uint64 * nf)(*szs),
        (ctypes.c_char_p * nf)(*dsts))
    # structured subdtypes (e.g. ('f4', (3,))) come back flat; reshape
    for f in names:
        sub = dt.fields[f][0]
        if sub.subdtype is not None:
            outs[f] = outs[f].view(sub.subdtype[0]).reshape((n,) + sub.subdtype[1])
    return outs


def parallel_unpack(records, workers: int = None, fields=None):
    """Sharded AoS -> SoA framing: the record buffer is split into ``workers``
    contiguous row slices, each transposed by :func:`unpack_records`'s native
    pass in its own thread, writing DIRECTLY into the shared preallocated
    columns at its row offset (no per-slice allocation, no concat, order
    trivially preserved). ctypes releases the GIL around each native call, so
    slices unpack truly concurrently — the counterpart of the reference
    sweeping 1-14 source threads (``src/GPU_Tests/new_tests/run_tests.py:20-28``,
    replica splitting ``wf/source.hpp:284-296``) applied to host framing.

    ``workers=None`` uses ``hardware_concurrency()``; 1 (or a single-core host,
    or no native library) degrades to the plain single-pass path."""
    import numpy as np
    lib = _load()
    if workers is None:
        workers = hardware_concurrency()
    n = records.shape[0]
    workers = max(1, min(int(workers), n or 1))
    if (lib is None or workers == 1 or not records.flags["C_CONTIGUOUS"]):
        return unpack_records(records, fields)
    import threading
    dt = records.dtype
    names = list(fields if fields is not None else dt.names)
    outs = {f: np.empty(n, dt.fields[f][0]) for f in names}
    bounds = [round(w * n / workers) for w in range(workers + 1)]
    rec_base = records.ctypes.data
    nf = len(names)
    offs = (ctypes.c_uint64 * nf)(*[dt.fields[f][1] for f in names])
    szs = (ctypes.c_uint64 * nf)(*[dt.fields[f][0].itemsize for f in names])

    as_cp = lambda addr: ctypes.cast(ctypes.c_void_p(addr), ctypes.c_char_p)

    def one(lo, hi):
        m = hi - lo
        if m <= 0:
            return
        dsts = (ctypes.c_char_p * nf)(*[
            # per-ROW stride is the FIELD dtype's itemsize (12 for ('f4',(3,));
            # the allocated array's base dtype would say 4)
            as_cp(outs[f].ctypes.data + lo * dt.fields[f][0].itemsize)
            for f in names])
        lib.wf_unpack_records(
            as_cp(rec_base + lo * dt.itemsize), m, dt.itemsize, nf,
            offs, szs, dsts)

    threads = [threading.Thread(target=one,  # wf-lint: thread-role[native]
                                args=(bounds[w], bounds[w + 1]))
               for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for f in names:                       # structured subdtypes come back flat
        sub = dt.fields[f][0]
        if sub.subdtype is not None:
            outs[f] = outs[f].view(sub.subdtype[0]).reshape((n,) + sub.subdtype[1])
    return outs


def pack_records(columns: dict, dtype):
    """SoA -> AoS egress (sinks emitting framed records): inverse of
    :func:`unpack_records`."""
    import numpy as np
    lib = _load()
    names = list(dtype.names)
    n = len(np.asarray(columns[names[0]]))
    out = np.empty(n, dtype)
    # validate every column against its field BEFORE any copy, native or not —
    # same error either way, and no native out-of-bounds read
    cols = []
    for f in names:
        fdt = dtype.fields[f][0]
        col = np.ascontiguousarray(np.asarray(columns[f]),
                                   fdt.base if fdt.subdtype else fdt)
        if col.nbytes != n * fdt.itemsize:
            raise ValueError(
                f"pack_records: column '{f}' has {col.shape} {col.dtype} "
                f"({col.nbytes} bytes) but field needs {n} x {fdt.itemsize} bytes")
        cols.append(col)                         # also keeps ctypes pointers alive
    if lib is None:
        for f, col in zip(names, cols):
            sub = dtype.fields[f][0].subdtype
            out[f] = col.reshape((n,) + sub[1]) if sub else col
        return out
    srcs, offs, szs = [], [], []
    for f, col in zip(names, cols):
        fdt, off = dtype.fields[f][0], dtype.fields[f][1]
        srcs.append(col.ctypes.data_as(ctypes.c_char_p))
        offs.append(off)
        szs.append(fdt.itemsize)
    nf = len(names)
    lib.wf_pack_records(
        out.ctypes.data_as(ctypes.c_char_p), n, dtype.itemsize, nf,
        (ctypes.c_uint64 * nf)(*offs), (ctypes.c_uint64 * nf)(*szs),
        (ctypes.c_char_p * nf)(*srcs))
    return out


def hash_keys_native(keys, num_slots: int):
    """Native key->slot hashing, bit-identical to
    ``windflow_tpu.batch.hash_key_to_slot``: 32-bit FNV-1a for string/bytes arrays,
    Knuth uint64 multiply for integer arrays. Returns int32 slots, or None under
    ``WF_PYTHON_SHIM=1`` (caller takes the Python path)."""
    import numpy as np
    lib = _load()
    if lib is None:
        return None
    arr = np.asarray(keys)
    out = np.empty(arr.size, np.int32)
    if arr.dtype.kind in "iu":
        k = np.ascontiguousarray(arr.ravel().astype(np.int64))
        lib.wf_hash_int_keys(k.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
                             arr.size, num_slots,
                             out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out.reshape(arr.shape)
    if arr.dtype.kind == "S":
        a = np.ascontiguousarray(arr.ravel())
        lib.wf_hash_fixed_str_keys(
            a.ctypes.data_as(ctypes.c_char_p), a.size, a.dtype.itemsize,
            a.dtype.itemsize, num_slots,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out.reshape(arr.shape)
    if arr.dtype.kind == "U":
        # dedup first (batches typically repeat few keys), hash uniques natively,
        # scatter back through the inverse index
        uniq, inv = np.unique(arr.ravel(), return_inverse=True)
        enc = [s.encode() for s in uniq.tolist()]
        buf = b"".join(enc)
        offsets = np.zeros(len(enc) + 1, np.int64)
        np.cumsum([len(e) for e in enc], out=offsets[1:])
        uout = np.empty(len(enc), np.int32)
        lib.wf_hash_str_keys(
            buf, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            len(enc), num_slots,
            uout.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return uout[inv].reshape(arr.shape)
    return None


def pin_thread(core: int) -> bool:
    lib = _load()
    return lib is not None and lib.wf_pin_thread(core) == 0


def hardware_concurrency() -> int:
    lib = _load()
    return lib.wf_hardware_concurrency() if lib is not None else (os.cpu_count() or 1)


def native_available() -> bool:
    return _load() is not None


def queue_selfbench(n: int = 2_000_000, capacity: int = 1024) -> float:
    """Raw ring throughput (tokens/s), measured entirely in C across two
    threads (``wf_queue_selfbench``) — the number the reference's FastFlow
    SPSC queues compete on. Returns 0.0 under ``WF_PYTHON_SHIM=1``."""
    lib = _load()
    if lib is None:
        return 0.0
    return float(lib.wf_queue_selfbench(n, capacity))
