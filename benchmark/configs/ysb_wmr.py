"""Configuration ``ysb_wmr``: the Yahoo Streaming Benchmark with its window
stage as a ``Win_MapReduce`` (upstream ``test_ysb_wmr.cpp``).

Everything of the record, the generator and the query's needed bytes is
``ysb``'s, taken from ``ysb.py`` beside this file, so that the two YSB cells
differ by the window engine alone. What is this configuration's own: the
chain's last stage with its budgets, the checks on that stage, and a
reference written anew, numpy on the logical stream, that imports nothing of
the program.
"""

import importlib.util
import math
import os

import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("bench_cfg_ysb_records", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_ysb = _sibling("ysb.py")
RECORD, KEY_FIELD, TS_FIELD = _ysb.RECORD, _ysb.KEY_FIELD, _ysb.TS_FIELD
make_pool, stamp = _ysb.make_pool, _ysb.stamp
#: the query's needed bytes do not depend on what implements the window
min_bytes_per_batch = _ysb.min_bytes_per_batch

#: what the program's window stage must publish for ``program_checks``
ENGINE_COUNTERS = ("archive_overwrites", "old_drops",
                   "windows_undelivered_at_eos")


def _require_counting_engine():
    """A program whose archive engine cannot say that its ring overwrote a
    live tuple (commit 256d22c runs this chain, and miscounts in silence if
    the ring is too small) cannot be held to this configuration's guarantees:
    it fails here, before the runtime starts, not after a window."""
    from windflow_tpu.observability.names import STAGE_COUNTERS
    missing = [c for c in ENGINE_COUNTERS if c not in STAGE_COUNTERS]
    if missing:
        raise RuntimeError(f"the program publishes no {missing}: ysb_wmr's "
                           f"program_checks cannot be made")


_require_counting_engine()


def _events_per_window(cfg):
    return cfg["win_len_ms"] * cfg["events_per_ms"]


def engine_budgets(cfg, batch):
    """(ring slots per campaign, fired windows per batch), from the deployment.

    A campaign's archive holds the views of its open window and of the batch
    being inserted, before any of them fires: ``(window + batch) / (event
    types x campaigns)`` views on average, a Poisson-like count, plus eight
    standard deviations; the engine rounds up to a power of two. A batch
    completes at most ``batch // window + 1`` windows per campaign, and no
    more than that are open at the end of the stream."""
    epw = _events_per_window(cfg)
    mean = (epw + batch) / (cfg["event_types"] * cfg["n_campaigns"])
    slots = math.ceil(mean + 8 * math.sqrt(mean))
    return slots, cfg["n_campaigns"] * (batch // epw + 1)


def build_ops(cfg, batch):
    from windflow_tpu.benchmarks import ysb
    if (ysb.N_CAMPAIGNS, ysb.ADS_PER_CAMPAIGN) != (cfg["n_campaigns"],
                                                   cfg["ads_per_campaign"]):
        raise ValueError("the program's YSB fixture is not the configuration's")
    import windflow_tpu as wf
    slots, max_wins = engine_budgets(cfg, batch)
    filt, join, rekey, window = ysb.make_ops_wmr(
        win_len=cfg["win_len_ms"], map_parallelism=cfg["map_parallelism"],
        tb_capacity=slots, max_wins=max_wins)
    project = wf.BatchMap(lambda p: {"ad_id": p["ad_id"]}, name="ysb_project")
    return [filt, project, join, rekey, window]


def program_checks(cfg, ops):
    """The window stage is a ``Win_MapReduce`` over the archive engine, its
    ring overwrote no tuple that an unfired window still needed, no event was
    dropped as late, and the EOS flush left no open window behind."""
    from windflow_tpu.operators.win_patterns import Win_MapReduce
    from windflow_tpu.operators.win_seq import Win_Seq
    window = ops[-1]
    is_wmr = (isinstance(window, Win_MapReduce)
              and type(window.engine) is Win_Seq
              and window.M == cfg["map_parallelism"])
    counters = window.stage_counters()
    checks = {"window_not_wmr_over_win_seq": (0 if is_wmr else 1, 0)}
    checks.update({c: (counters[c], 0) for c in ENGINE_COUNTERS})
    return checks


def reference(cfg, pool, n_batches, batch, acc_dtype=np.int64):
    """Views per (campaign, window) over the first ``n_batches`` batches of the
    stream, counted straight from the records: every view's campaign from its
    ``ad_id``, its window from its event time (``stamp``'s), one ``bincount``
    a batch, accumulated batch by batch in ``acc_dtype`` (int64: exact; a
    lower precision is the control), and the batch that holds each cell's
    last view."""
    n_c, epw = cfg["n_campaigns"], _events_per_window(cfg)
    n_win = (n_batches * batch - 1) // epw + 1
    value = np.zeros((n_c, n_win), acc_dtype)
    last_batch = np.full((n_c, n_win), -1, np.int64)
    views = []                      # per pool batch: (offset, campaign) of views
    for recs in pool:
        at = np.flatnonzero(recs["event_type"] == cfg["view_event_type"])
        views.append((at, recs["ad_id"][at].astype(np.int64)
                      // cfg["ads_per_campaign"]))
    for j in range(n_batches):
        at, campaign = views[j % len(pool)]
        ts = (j * batch + at) // cfg["events_per_ms"]
        win = ts // cfg["win_len_ms"]
        w0 = (j * batch) // epw
        span = (j * batch + batch - 1) // epw - w0 + 1
        part = np.bincount(campaign * span + (win - w0),
                           minlength=n_c * span).reshape(n_c, span)
        cols = slice(w0, w0 + span)
        value[:, cols] = (value[:, cols].astype(np.float64)
                          + part).astype(acc_dtype)
        last_batch[:, cols][part > 0] = j
    value = value.astype(np.int64)
    return {"value": value, "last_batch": last_batch,
            "must_deliver": last_batch >= 0}
