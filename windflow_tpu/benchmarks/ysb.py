"""Yahoo Streaming Benchmark (YSB) — the flagship macro-benchmark.

Counterpart of ``src/yahoo_test_cpu`` (``test_ysb_kf.cpp:18-26``: EventSource ->
Filter -> Project -> Join -> KeyFarm window count -> Sink; campaign fixture
``campaign_generator.hpp``; latency vector ``ysb_nodes.hpp:200-216``). The north-star
metric is tuples/sec/chip + p99 window-result latency (BASELINE.json).

Pipeline (TPU formulation):
1. EventSource: synthetic ad events ``(ad_id, event_type, ts)`` generated on device.
2. Filter: keep ``event_type == VIEW`` (1 of 3 types — 1/3 selectivity like the
   reference generator).
3. Project+Join: map ``ad_id -> campaign_id`` via a constant device-resident table
   (the reference joins against an in-memory campaign map).
4. Key_FFAT: per-campaign tumbling TB window (10-time-unit panes) counting views —
   associative lift/combine, the reference uses an incremental count window.
5. ReduceSink (device) or host Sink recording per-window results + latencies.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..basic import win_type_t
from ..batch import Batch, CTRL_DTYPE
from ..operators.filter import Filter
from ..operators.map import Map
from ..operators.sink import ReduceSink
from ..operators.source import DeviceSource
from ..operators.win_patterns import Key_FFAT
from ..operators.window import WindowSpec
from ..runtime.pipeline import CompiledChain, Pipeline

N_CAMPAIGNS = 100
ADS_PER_CAMPAIGN = 10
N_ADS = N_CAMPAIGNS * ADS_PER_CAMPAIGN
WIN_LEN = 100          # time units per tumbling window (reference: 10s of event time)
EVENTS_PER_TICK = 10   # synthetic event-time rate: ts = i // EVENTS_PER_TICK


def make_ops(num_keys: int = N_CAMPAIGNS, win_len: int = WIN_LEN,
             pane_capacity: int = None, max_wins: int = None):
    """The YSB operator chain after the source (filter -> join -> window count)."""
    # ad -> campaign: static fixture table (campaign_generator.hpp analogue)
    camp_of = jnp.asarray(np.arange(N_ADS) // ADS_PER_CAMPAIGN, CTRL_DTYPE)

    from ..operators.map import BatchMap
    from ..ops.lookup import table_lookup

    filt = Filter(lambda t: t.event_type == 0, name="ysb_filter")
    # per-tuple campaign join via the gather-free small-table lookup (the reference
    # joins a hash map per tuple; jnp.take would serialize at ~5.6 ns/tuple)
    join = BatchMap(lambda p: {"cmp": table_lookup(camp_of, p["ad_id"])},
                    name="ysb_join")

    # Key routing: the window op keys on campaign id (KEYBY re-route on a
    # payload field)
    from ..operators.map import KeyBy
    rekey = KeyBy(lambda t: t.cmp, num_keys, name="ysb_rekey")
    window = Key_FFAT(lambda t: jnp.ones((), jnp.int32), jnp.add,
                      spec=WindowSpec(win_len, win_len, win_type_t.TB),
                      num_keys=num_keys, name="ysb_window",
                      pane_capacity=pane_capacity, max_wins=max_wins)
    return [filt, join, rekey, window]


def make_ops_wmr(num_keys: int = N_CAMPAIGNS, win_len: int = WIN_LEN,
                 map_parallelism: int = 2, **engine_kw):
    """YSB with a Win_MapReduce window stage — the ``test_ysb_wmr.cpp`` variant of
    the reference (each window's content partitioned over MAP workers, partial
    counts combined by REDUCE). ``engine_kw`` (``max_wins``, ``tb_capacity``,
    ...) forwards to the underlying Win_Seq engine — large batches need
    explicit fired-window budgets (the engine's default budget guard raises)."""
    from ..operators.win_patterns import Win_MapReduce
    filt, join, rekey, _ = make_ops(num_keys=num_keys, win_len=win_len)
    window = Win_MapReduce(lambda wid, it: it.size(),
                           lambda wid, it: it.sum(),
                           WindowSpec(win_len, win_len, win_type_t.TB),
                           map_parallelism=map_parallelism, num_keys=num_keys,
                           name="ysb_window_wmr", **engine_kw)
    return [filt, join, rekey, window]


def make_source(total: int, name: str = "ysb_source") -> DeviceSource:
    def gen(i):
        return {"ad_id": (i * 7919) % N_ADS,     # pseudo-random ad
                "event_type": i % 3}
    return DeviceSource(gen, total=total, name=name,
                        key_fn=lambda i: (i * 7919) % N_ADS % N_CAMPAIGNS,
                        ts_fn=lambda i: i // EVENTS_PER_TICK)


def make_pipeline(total: int, batch_size: int = 8192,
                  count_sink: bool = True) -> Pipeline:
    ops = make_ops()
    if count_sink:
        ops.append(ReduceSink(lambda t: t.data, name="ysb_windows_total"))
    src = make_source(total)
    return Pipeline(src, ops, batch_size=batch_size)


def oracle_totals(total: int) -> int:
    """Total view events (the sum of all window counts must equal this)."""
    return len([i for i in range(total) if i % 3 == 0])


def window_counts(ad_id, event_type, ts, win_len: int = WIN_LEN) -> np.ndarray:
    """The plain reference of the window stage, whichever engine implements it
    (``make_ops`` or ``make_ops_wmr``): views per (campaign, window) straight
    from the records' three query columns, int64 ``[N_CAMPAIGNS, n_windows]``.
    A cell of 0 is a window that never fires."""
    ad_id, ts = np.asarray(ad_id, np.int64), np.asarray(ts, np.int64)
    view = np.asarray(event_type) == 0
    n_win = int(ts.max()) // win_len + 1 if ts.size else 0
    cell = ad_id[view] // ADS_PER_CAMPAIGN * n_win + ts[view] // win_len
    return np.bincount(cell, minlength=N_CAMPAIGNS * n_win).reshape(
        N_CAMPAIGNS, n_win)
