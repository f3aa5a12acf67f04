"""The benchmark's one traffic generator.

A cell's traffic is the ``traffic`` object of ``workloads/<cell>.json``:
parameters only. This generator reads them; a new mix is a new data file.

A generator thread cycles through a pool of host batches made from the seed
and feeds a queue that the system's ``RecordSource`` iterator drains. The
first ``warm_prefix_batches`` are a warm prefix: the generator then waits
until the system has delivered them, and the first batch after that opens the
measured window. It stops feeding ``seconds`` later and ends the stream.

``mode`` says when a batch is offered:

``closed_loop``  a backlog is always ready: the queue is bounded
                 (``queue_depth``) and refilled as fast as the system drains
                 it. ``t_created`` of a batch is the moment the system pulled
                 it.
``open_loop``    batches fall due at ``rate_tuples_per_s``, whatever the system
                 does: timed batch ``k`` is put in the (unbounded) queue at
                 ``t_open + k * batch / rate``, and that due time is its
                 ``t_created``, so a latency counts the wait in the queue. A
                 system that falls behind by more than the pool holds fails
                 the run: size the rate below what it sustains.
"""

import queue
import threading
import time

import numpy as np

#: RecordSource numbers tuples in int32 (``batch.py::CTRL_DTYPE``)
MAX_RECORDS = 2 ** 31 - 1
_END = object()


def make_pool(mod, cfg, traffic, seed):
    rng = np.random.default_rng(seed)
    return mod.make_pool(cfg, rng, traffic["batch"], traffic["pool_batches"])


class Feed:
    def __init__(self, mod, cfg, traffic, pool, seconds):
        self.mode = traffic["mode"]
        if self.mode not in ("closed_loop", "open_loop"):
            raise ValueError(f"traffic mode {self.mode!r}: this generator "
                             f"knows closed_loop and open_loop")
        self.mod, self.cfg, self.pool = mod, cfg, pool
        self.batch = traffic["batch"]
        self.prefix = traffic["warm_prefix_batches"]
        self.seconds = seconds
        self.open_loop = self.mode == "open_loop"
        self.period = (self.batch / traffic["rate_tuples_per_s"]
                       if self.open_loop else None)
        self.q = queue.Queue(0 if self.open_loop else traffic["queue_depth"])
        self.prefix_delivered = threading.Event()
        self.stop = threading.Event()
        self.t_created = []            # per batch pulled, host clock
        self.t_open = None             # t_created of the first timed batch
        self.empty_pulls = 0           # timed pulls that found the queue empty
        self.capped = False            # stopped by MAX_RECORDS, not by time
        self.error = None
        self.thread = threading.Thread(target=self._feed, daemon=True,
                                       name="bench-generator")

    def _put(self, item):
        while not self.stop.is_set():
            try:
                self.q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def _batch(self, j):
        recs = self.pool[j % len(self.pool)]
        self.mod.stamp(self.cfg, recs, j * self.batch)
        return recs

    def _feed(self):
        try:
            j = 0
            while j < self.prefix:
                if not self._put((self._batch(j), None)):
                    return
                j += 1
            while not self.prefix_delivered.wait(0.2):
                if self.stop.is_set():
                    return
            t_first_due = time.perf_counter() + (self.period or 0.0)
            while True:
                if (j + 1) * self.batch > MAX_RECORDS:
                    self.capped = True
                    break
                due = None
                if self.open_loop:
                    due = t_first_due + (j - self.prefix) * self.period
                    if due - t_first_due >= self.seconds:
                        break
                    if self.q.qsize() + 8 > len(self.pool):
                        raise RuntimeError(
                            "open loop: the system fell behind the offered "
                            "rate by more batches than the pool holds")
                elif (self.t_open is not None
                        and time.perf_counter() - self.t_open >= self.seconds):
                    break
                recs = self._batch(j)
                if due is not None and self.stop.wait(
                        max(0.0, due - time.perf_counter())):
                    return
                if not self._put((recs, due)):
                    return
                j += 1
            self._put(_END)
        except BaseException as e:      # noqa: BLE001 - re-raised by the harness
            self.error = e
            self._put(_END)

    def records(self):
        """The blocking iterator ``RecordSource`` drains."""
        while True:
            timed = self.t_open is not None
            try:
                item = self.q.get_nowait()
            except queue.Empty:
                # the pull that opens the window always finds it empty
                if timed:
                    self.empty_pulls += 1
                item = self.q.get()
            if item is _END:
                return
            recs, due = item
            created = time.perf_counter() if due is None else due
            if len(self.t_created) == self.prefix:
                self.t_open = created
            self.t_created.append(created)
            yield recs

    def close(self):
        self.stop.set()
        self.thread.join(timeout=10)
        if self.thread.is_alive():
            raise RuntimeError("the generator thread did not stop")
        if self.error is not None:
            raise self.error
