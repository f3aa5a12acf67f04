"""Per-replica statistics — counterpart of ``Stats_Record`` (``wf/stats_record.hpp:50-156``).

The reference counts inputs/outputs/bytes and service times per replica, plus GPU
counters (kernels launched, H2D/D2H bytes, ``wf/stats_record.hpp:76-80``), dumped to
``log/<pid>_<op>_<replica>.log``. Here the equivalents are per-operator host-side
counters updated by the scheduler (batches are counted on host; per-tuple counts come
from batch occupancy), including device-program launches and host<->HBM transfer bytes.
Always on (cheap), dumped via ``dump_to_file`` like ``dump_toFile``
(``wf/stats_record.hpp:109-155``).
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from .observability.metrics import LogHistogram


# one record per operator replica, bumped only by the thread driving that
# replica's chain (driver or its owning segment/pipe thread); the reporter
# reads the plain int counters GIL-atomically and tolerates a one-batch lag
# (the LogHistogram field locks internally).  Recorded for the WF260 lint.
class Stats_Record:  # wf-lint: single-writer[driver, stage]
    def __init__(self, op_name: str, replica_id: int = 0):
        self.op_name = op_name
        self.replica_id = replica_id
        self.start_time = time.monotonic()
        self.inputs_received = 0
        self.bytes_received = 0
        self.outputs_sent = 0
        self.bytes_sent = 0
        self.batches_received = 0
        self.batches_sent = 0
        # device counters (reference GPU fields, wf/stats_record.hpp:76-80)
        self.num_kernels = 0          # compiled-program launches
        self.bytes_copied_hd = 0      # host -> HBM
        self.bytes_copied_dh = 0      # HBM -> host
        #: tuples discarded as OLD (behind the fired-window frontier) by TB
        #: window engines — synced from device state via ``collect_stats``
        self.tuples_dropped_old = 0
        self._service_time_sum = 0.0
        self._service_samples = 0
        #: log-bucket distribution of the sampled service times (p50/p95/p99
        #: via observability.MetricsRegistry; one bisect per SAMPLED launch)
        self.service_hist = LogHistogram()

    def record_input(self, n_tuples: int, n_bytes: int = 0):
        self.inputs_received += int(n_tuples)
        self.bytes_received += int(n_bytes)
        self.batches_received += 1

    def record_output(self, n_tuples: int, n_bytes: int = 0):
        self.outputs_sent += int(n_tuples)
        self.bytes_sent += int(n_bytes)
        self.batches_sent += 1

    def record_launch(self, service_time_s: float = None, hd_bytes: int = 0,
                      dh_bytes: int = 0, exemplar=None):
        """One compiled-program launch. ``service_time_s`` is a MEASURED
        dispatch->completion sample (the chain samples every Nth push with a
        block_until_ready so the async pipeline stays overlapped); pass None on
        unsampled launches — only real samples enter the average.
        ``exemplar`` (a trace id, when causal tracing is on) tags the
        histogram bucket the sample lands in, linking the service-time
        percentiles to a concrete batch in the flight recorder."""
        self.num_kernels += 1
        self.bytes_copied_hd += int(hd_bytes)
        self.bytes_copied_dh += int(dh_bytes)
        if service_time_s is not None:
            self._service_time_sum += float(service_time_s)
            self._service_samples += 1
            self.service_hist.record(service_time_s, exemplar=exemplar)

    @property
    def avg_service_time_us(self) -> float:
        if not self._service_samples:
            return 0.0
        return 1e6 * self._service_time_sum / self._service_samples

    def as_dict(self) -> dict:
        return {
            "operator": self.op_name,
            "replica": self.replica_id,
            "inputs_received": self.inputs_received,
            "outputs_sent": self.outputs_sent,
            "bytes_received": self.bytes_received,
            "bytes_sent": self.bytes_sent,
            "batches_received": self.batches_received,
            "batches_sent": self.batches_sent,
            "num_kernels": self.num_kernels,
            "bytes_copied_hd": self.bytes_copied_hd,
            "bytes_copied_dh": self.bytes_copied_dh,
            "tuples_dropped_old": self.tuples_dropped_old,
            "avg_service_time_us": self.avg_service_time_us,
            "service_time_us": self.service_hist.summary_us(),
            "uptime_s": time.monotonic() - self.start_time,
        }

    def dump_to_file(self, log_dir: str = "log"):
        os.makedirs(log_dir, exist_ok=True)
        path = os.path.join(log_dir,
                            f"{os.getpid()}_{self.op_name}_{self.replica_id}.json")
        with open(path, "w") as f:
            json.dump(self.as_dict(), f, indent=2)
        return path


#: the one profiler session JAX supports: who (if anyone) holds it.  Guarded
#: so a nested/concurrent ``xprof_trace`` fails with a clear message instead
#: of the raw ``start_trace`` error surfacing out of user code.
_xprof_lock = threading.Lock()
_xprof_logdir = None


@contextlib.contextmanager
def xprof_trace(logdir: str):
    """JAX profiler capture around a pipeline run — the Xprof half of the
    reference's tracing story (``TRACE_WINDFLOW`` counters are the other half;
    SURVEY §5). Produces a TensorBoard-loadable trace under ``logdir``::

        with wf.xprof_trace("/tmp/trace"):
            graph.run()

    Works on CPU and TPU backends; on TPU the trace includes per-HLO device
    timing, H2D/D2H transfers, and fusion boundaries — the ground truth behind
    the cost table in docs/ARCHITECTURE.md §5 — each device operation under
    its operator's scope (``Class:name``; the window engine's ``insert`` /
    ``emit``) — and the program's own host spans (``wf.source.*``,
    ``wf.drive.*``, ``wf.chain.*``, ``wf.sink.*``: docs/ARCHITECTURE.md,
    tracing) on the same clock.  Pairs with the host-side
    flight recorder (``trace=`` / ``scripts/wf_trace.py``): load both files
    into Perfetto for device HLO timing beside the per-batch causal timeline.

    One session at a time: JAX's profiler is process-global, and a nested
    ``start_trace`` raises an opaque error from deep inside the profiler.
    This wrapper detects the active session FIRST and raises a
    ``RuntimeError`` that names the holder and the fix."""
    global _xprof_logdir
    import jax
    with _xprof_lock:
        if _xprof_logdir is not None:
            raise RuntimeError(
                f"xprof_trace({logdir!r}): a profiler session is already "
                f"active, capturing to {_xprof_logdir!r} — JAX supports one "
                f"trace per process; nest this region inside the existing "
                f"capture (one file is enough: the trace carries every "
                f"device event between start and stop) or close it first")
        # host tracing by TraceMe alone: JAX's default also runs the Python
        # tracer, whose event per Python call buries the program's own spans
        # (observability/tracing.py::span) and the device plane under a flood
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        try:
            jax.profiler.start_trace(logdir, profiler_options=opts)
        except RuntimeError as e:
            # a session started OUTSIDE this wrapper (TensorBoard capture
            # button, a direct jax.profiler.start_trace) — same root cause,
            # same guidance, original error chained
            raise RuntimeError(
                f"xprof_trace({logdir!r}): jax.profiler.start_trace failed — "
                f"most likely another profiler session (TensorBoard capture, "
                f"a direct start_trace elsewhere in this process) is already "
                f"active; stop it before opening a new capture") from e
        _xprof_logdir = logdir
    try:
        yield logdir
    finally:
        # stop BEFORE releasing the guard: clearing first would open a
        # window where a concurrent xprof_trace passes the guard and hits
        # JAX's still-active profiler with the raw error again
        try:
            jax.profiler.stop_trace()
        finally:
            with _xprof_lock:
                _xprof_logdir = None
