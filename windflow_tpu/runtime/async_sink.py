"""Asynchronous device->host result shipping — the latency-critical sink path.

The reference's sink receives each window result over an in-memory queue and
timestamps receipt per result (YSB latency vector,
``src/yahoo_test_cpu/ysb_nodes.hpp:200-216``). On TPU the equivalent boundary is a
device->host transfer, and a *synchronous* fetch costs a full host<->device round
trip per batch — paying it inline would gate the whole stream on the slowest link.

:class:`AsyncResultShipper` instead starts a non-blocking device->host copy the
moment a result batch is produced (``jax.Array.copy_to_host_async``) and harvests
completed copies later, so result transfer overlaps both device compute and other
transfers. Receipt latency becomes ``step_time + transfer_time + one round trip``
amortized across everything in flight, instead of one blocking round trip per
batch. This is the same overlap discipline as the reference GPU operators' D2H
``cudaMemcpyAsync`` + next-batch-flush protocol (``wf/win_seq_gpu.hpp:243-260,524``),
applied to the sink boundary.

Usage (see ``bench.py::bench_latency_curve``)::

    shipper = AsyncResultShipper(depth=4)
    for i, batch in enumerate(stream):
        out = step(batch)                       # async dispatch
        shipper.ship(out, tag=i)                # starts D2H copy, never blocks
        for rec in shipper.harvest():           # completed older results
            sink(rec.value, latency=rec.receipt_time - rec.ship_time)
    for rec in shipper.drain():                 # EOS
        sink(rec.value, ...)
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Iterator, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class ShippedResult:
    tag: Any              # caller's identifier (e.g. step index)
    value: Any            # pytree of np.ndarray, on host
    ship_time: float      # perf_counter at ship() (device result was available)
    receipt_time: float   # perf_counter when the host copy completed


class AsyncResultShipper:
    """Overlapped device->host shipping of small result batches.

    ``depth``: harvest() leaves this many newest results in flight (their copies
    may still be running); drain() collects everything.
    """

    def __init__(self, depth: int = 4):
        self.depth = int(depth)
        self._inflight: deque = deque()

    def ship(self, arrays: Any, tag: Any = None) -> None:
        """Start a non-blocking device->host copy of ``arrays`` (a pytree of
        jax.Array). Returns immediately."""
        for leaf in jax.tree.leaves(arrays):
            copy_async = getattr(leaf, "copy_to_host_async", None)
            if copy_async is not None:
                copy_async()
        self._inflight.append((time.perf_counter(), tag, arrays))

    def harvest(self, keep_inflight: Optional[int] = None) -> List[ShippedResult]:
        """Collect results older than the in-flight window. The copies of
        harvested results have had ``depth`` ship() calls of wall time to finish,
        so the final np.asarray is (amortized) a cheap completed-copy read."""
        keep = self.depth if keep_inflight is None else keep_inflight
        out: List[ShippedResult] = []
        while len(self._inflight) > keep:
            ship_t, tag, arrays = self._inflight.popleft()
            host = jax.tree.map(np.asarray, arrays)
            out.append(ShippedResult(tag=tag, value=host, ship_time=ship_t,
                                     receipt_time=time.perf_counter()))
        return out

    def drain(self) -> List[ShippedResult]:
        """EOS: collect everything still in flight."""
        return self.harvest(keep_inflight=0)

    def __len__(self) -> int:
        return len(self._inflight)
