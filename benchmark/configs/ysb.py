"""Configuration ``ysb``: what is particular to the Yahoo Streaming Benchmark.

The sizes live in ``ysb.json``; this file turns them into records, into the
system's operator chain, and into the plain reference the results are held to.
The reference is numpy on the logical stream and imports nothing of the
program.
"""

import numpy as np

#: WindFlow's ``event_t`` (``src/yahoo_test_cpu/ysb_common.hpp``): Yahoo's
#: seven fields, the ids 8 bytes wide, padded to a 64-byte line. The source
#: emits whole events; the chain's filter and project drop what the query
#: does not read, as upstream's Filter and Project stages do.
RECORD = np.dtype({
    "names": ["user_id", "page_id", "ad_id", "ad_type", "event_type", "ip",
              "ts"],
    "formats": ["<u8", "<u8", "<u8", "<u4", "<u4", "<u4", "<u8"],
    "offsets": [0, 8, 16, 24, 28, 32, 40],
    "itemsize": 64})
KEY_FIELD = None            # keyed by campaign after the join, as upstream
TS_FIELD = "ts"
#: what the query reads of an event, 4 bytes each as the device holds them
QUERY_COLUMNS = ("ad_id", "event_type", "ts")


def _events_per_window(cfg):
    return cfg["win_len_ms"] * cfg["events_per_ms"]


def make_pool(cfg, rng, batch, n_pool):
    """``n_pool`` host batches of ``batch`` records; ``ts`` is stamped later."""
    n_ads = cfg["n_campaigns"] * cfg["ads_per_campaign"]
    pool = []
    for _ in range(n_pool):
        recs = np.zeros(batch, RECORD)
        recs["user_id"] = rng.integers(0, cfg["n_users"], batch)
        recs["page_id"] = rng.integers(0, cfg["n_pages"], batch)
        recs["ad_id"] = rng.integers(0, n_ads, batch)
        recs["ad_type"] = rng.integers(0, cfg["ad_types"], batch)
        recs["event_type"] = rng.integers(0, cfg["event_types"], batch)
        recs["ip"] = 1
        pool.append(recs)
    return pool


def stamp(cfg, recs, first_index):
    """Event time in ms of records ``first_index ...`` of the logical stream."""
    idx = np.arange(first_index, first_index + len(recs), dtype=np.int64)
    recs["ts"] = idx // cfg["events_per_ms"]


def build_ops(cfg, batch):
    from windflow_tpu.benchmarks import ysb
    if (ysb.N_CAMPAIGNS, ysb.ADS_PER_CAMPAIGN) != (cfg["n_campaigns"],
                                                   cfg["ads_per_campaign"]):
        raise ValueError("the program's YSB fixture is not the configuration's")
    import windflow_tpu as wf
    panes_per_batch = batch // _events_per_window(cfg) + 1
    filt, join, rekey, window = ysb.make_ops(
        win_len=cfg["win_len_ms"], pane_capacity=2 * panes_per_batch + 2,
        max_wins=panes_per_batch + 64)
    project = wf.BatchMap(lambda p: {"ad_id": p["ad_id"]}, name="ysb_project")
    return [filt, project, join, rekey, window]


def program_checks(cfg, ops):
    """Leg A's device check: the window stage took the histogram path."""
    return {"count_lift_off": (0 if ops[-1].count_lift is True else 1, 0)}


def reference(cfg, pool, n_batches, batch, acc_dtype=np.int64):
    """Views per (campaign, window) over the first ``n_batches`` batches of the
    stream, accumulated batch by batch in ``acc_dtype`` (int64: exact; a lower
    precision is the control), and the batch that holds each cell's last view.
    """
    n_c, epw = cfg["n_campaigns"], _events_per_window(cfg)
    n_win = (n_batches * batch - 1) // epw + 1
    value = np.zeros((n_c, n_win), acc_dtype)
    last_batch = np.full((n_c, n_win), -1, np.int64)
    # a record's campaign where it is a view, the spare bin n_c where not
    viewed = [np.where(r["event_type"] == cfg["view_event_type"],
                       r["ad_id"].astype(np.int64) // cfg["ads_per_campaign"],
                       n_c)
              for r in pool]
    for j in range(n_batches):
        p, first = j % len(pool), j * batch
        for w in range(first // epw, (first + batch - 1) // epw + 1):
            sl = slice(max(first, w * epw) - first,
                       min(first + batch, (w + 1) * epw) - first)
            part = np.bincount(viewed[p][sl], minlength=n_c + 1)[:n_c]
            value[:, w] = (value[:, w].astype(np.float64)
                           + part).astype(acc_dtype)
            last_batch[part > 0, w] = j
    value = value.astype(np.int64)
    return {"value": value, "last_batch": last_batch,
            "must_deliver": last_batch >= 0}


def min_bytes_per_batch(cfg, batch):
    """The least traffic one batch needs, whatever implements it: the columns
    the query reads (``QUERY_COLUMNS``) read once, the open windows' counts
    read and written once, each result (key, window id, ts, count) written
    once. The columns that the filter and project drop are no needed work."""
    windows_open = batch // _events_per_window(cfg) + 1
    state = 2 * cfg["n_campaigns"] * windows_open * 4
    results = cfg["n_campaigns"] * windows_open * 16
    return batch * 4 * len(QUERY_COLUMNS) + state + results
