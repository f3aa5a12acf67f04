"""Configuration ``kff_lag``: ``kff``'s keyed time-based sliding-window sum
through ``Key_FFAT`` with upstream's per-key triggering (``Triggerer_TB``, a
descriptor a key), over a stream whose keys live in partitions that do not
advance together: a quarter of the keys run behind the rest by a lag of
their own, up to four windows (Flink's FLIP-182 names this skew between
splits).

Records, values, key order and the window's shape are ``kff``'s, taken from
``kff.py`` beside this file (which takes the records from ``kcb.py``). What is
this configuration's own: the lags drawn from the seed and the stamps that
apply them, the per-key window stage and its budgets, the checks, and a
reference written anew, numpy on the logical stream, that imports nothing of
the program.
"""

import importlib.util
import math
import os

import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("bench_cfg_kff_for_lag",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_kff = _sibling("kff.py")
RECORD, KEY_FIELD, TS_FIELD = _kff.RECORD, _kff.KEY_FIELD, _kff.TS_FIELD
#: the stream's bytes are kff's: a lag moves a stamp, not a byte
min_bytes_per_batch = _kff.min_bytes_per_batch
#: held at 0: lanes folded into a slot an unfired pane held, tuples dropped
#: as late, windows the EOS flush left open
ENGINE_COUNTERS = _kff.ENGINE_COUNTERS
#: the largest per-key watermark less the smallest: at least a window, or the
#: run's keys did not lag
SPREAD_COUNTER = "ffat_key_clock_spread"


def _require_counting_engine():
    """A program that cannot say how far its keys' clocks lie apart, or that
    a lane overran its key's ring, cannot be held to this configuration's
    guarantees: it fails here, before the runtime starts."""
    from windflow_tpu.observability.names import STAGE_COUNTERS
    missing = [c for c in ENGINE_COUNTERS + (SPREAD_COUNTER,)
               if c not in STAGE_COUNTERS]
    if missing:
        raise RuntimeError(f"the program publishes no {missing}: kff_lag's "
                           f"program_checks cannot be made")


_require_counting_engine()


class LagRecords(np.ndarray):
    """A pool batch: ``kff``'s records, and the lags drawn once from the seed
    for the whole stream: ``lag`` a key (int64, 0 for a key in step) and
    ``lane_lag``, the lag of each lane's key (keys go round, so every pool
    batch shares one such array). ``stamp`` applies it; the reference reads
    ``lag``."""

    lag = None
    lane_lag = None


def draw_lags(cfg, rng):
    """``lag_share`` of the keys, chosen without replacement, each with a lag
    uniform over whole ticks in [1, ``max_lag``]; 0 for the others."""
    n_k = cfg["n_keys"]
    lag = np.zeros(n_k, np.int64)
    behind = rng.choice(n_k, int(round(cfg["lag_share"] * n_k)),
                        replace=False)
    lag[behind] = rng.integers(1, cfg["max_lag"] + 1, len(behind))
    return lag


def make_pool(cfg, rng, batch, n_pool):
    """``kff``'s pool (every batch's values drawn first, so that a zero
    ``lag_share`` gives ``kff``'s stream from the same seed), then the keys'
    lags."""
    pool = _kff.make_pool(cfg, rng, batch, n_pool)
    lag = draw_lags(cfg, rng)
    lane_lag = np.tile(lag, batch // cfg["n_keys"])
    out = []
    for recs in pool:
        recs = recs.view(LagRecords)
        recs.lag, recs.lane_lag = lag, lane_lag
        out.append(recs)
    return out


def stamp(cfg, recs, first_index):
    """Records ``first_index ...`` of the logical stream: ``kcb``'s
    progressive id within the key, and ``ts`` the position less the lag of
    the record's key, never below 0. In one buffer, written in place, as
    ``kff_late``'s stamp."""
    col = np.arange(first_index, first_index + len(recs), dtype=np.int64)
    recs["id"] = (col // cfg["n_keys"]).view(np.uint64)
    np.subtract(col, recs.lane_lag, out=col)
    np.maximum(col, 0, out=col)
    recs["ts"] = col.view(np.uint64)


def engine_budgets(cfg, batch):
    """(ring slots a key, fired windows of all keys a batch), from the
    deployment.

    A key's ring is ``kff``'s: its own first unfired pane is the first of its
    oldest open window, at most a window before its next tuple, and a batch
    carries at most a batch's ticks of each key (64 + 64 + 1; the engine
    rounds up to a power of two, 256). On the per-key path the fired-window
    budget is one list over all keys: a batch moves a key's clock by at most
    a batch, so each key fires at most ``kff``'s 65, 512 x 65 = 33,280 in all
    (the default, ``batch / slide + 64`` = 128 for all keys together, would
    fall behind)."""
    slots, wins = _kff.engine_budgets(cfg, batch)
    return slots, cfg["n_keys"] * wins


def build_ops(cfg, batch):
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu.basic import win_type_t
    slots, wins = engine_budgets(cfg, batch)
    return [wf.Key_FFAT(lambda t: t.value, jnp.add,
                        spec=wf.WindowSpec(cfg["win_len"], cfg["slide"],
                                           win_type_t.TB, delay=cfg["delay"]),
                        num_keys=cfg["n_keys"], name="kff_lag_window",
                        global_time=False, pane_capacity=slots,
                        max_wins=wins)]


def global_time_ops(cfg, batch):
    """The control of the per-key semantics: ``kff``'s window stage, one
    clock for every key, over this stream. It drops a lagging key's tuples
    once the fastest keys' clock has fired their windows, and must come out
    as not correct."""
    return _kff.build_ops(cfg, batch)


def structure_checks(cfg, window):
    """The window stage is a ``Key_FFAT`` on the per-key time-based path at
    the configuration's window and allowed lateness, with a lift that reads
    the tuple, and its two budgets are the deployment's at the batch its
    fired-window budget stands for."""
    from windflow_tpu.operators.win_patterns import Key_FFAT
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    is_per_key = (type(window) is Key_FFAT and not window.global_time
                  and not window.count_lift and not window.spec.is_cb
                  and window.spec.delay == cfg["delay"]
                  and (window.spec.win_len, window.spec.slide,
                       window.num_keys)
                  == (cfg["win_len"], cfg["slide"], cfg["n_keys"])
                  and (window.pane_len, window.wpanes, window.spanes)
                  == (pane, cfg["win_len"] // pane, cfg["slide"] // pane))
    counters = window.stage_counters()
    have = tuple(counters.get(b) for b in _kff.ENGINE_BUDGETS)
    per_key, rest = divmod(counters.get("fired_window_budget", 0),
                           cfg["n_keys"])
    batch = (per_key - 1) * cfg["slide"]
    want = None
    if not rest and batch > 0 and batch % pane == 0:
        slots, wins = engine_budgets(cfg, batch)
        want = (_kff._next_pow2(slots), wins)
    return {"window_not_key_ffat_on_per_key_time": (0 if is_per_key else 1,
                                                    0),
            "engine_budgets_not_the_deployments": (0 if have == want else 1,
                                                   0)}


def program_checks(cfg, ops):
    """``structure_checks`` of the chain's last stage, ``kff``'s three
    counters at 0, and ``key_clocks_not_skewed``: a run whose keys' clocks
    never lay a window apart did not exercise what this configuration is
    for."""
    counters = ops[-1].stage_counters()
    checks = structure_checks(cfg, ops[-1])
    checks.update({c: (counters[c], 0) for c in ENGINE_COUNTERS})
    checks["key_clocks_not_skewed"] = (
        0 if counters.get(SPREAD_COUNTER, 0) >= cfg["win_len"] else 1, 0)
    return checks


# ---- the reference --------------------------------------------------------


def _lane_panes(cfg, recs, batch):
    """One pool batch's pane partials of each key, by pane relative to the
    batch's first tick, shifted by the key's lag: ``(values, counts, row)``,
    ``[ppb + 1, key]`` each, where row ``e`` of key ``k`` is the stream's
    pane ``j * ppb + row0[k] + e`` for the batch at ``j * batch`` (before
    the clamp at tick 0). A lag ``q * pane + r`` moves the pane boundaries
    of a key by ``r`` in position and its panes by ``q``."""
    n_k = cfg["n_keys"]
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    ppb = batch // pane
    q, r = np.divmod(recs.lag, pane)
    pos = np.arange(batch, dtype=np.int64)
    key = recs["key"].astype(np.int64)
    e = (pos - r[key]) // pane + 1                 # in [0, ppb]
    cell = e * n_k + key
    return cell, -1 - q, ppb + 1


def reference(cfg, pool, n_batches, batch, acc_dtype=np.float64):
    """Sum of ``value`` per (key, window) over the first ``n_batches``
    batches, under per-key ``Triggerer_TB`` with no allowed lateness: a
    key's tuple at position ``p`` has ``ts = max(0, p - lag[key])`` and adds
    to every window ``w`` of its key with ``ts`` in ``[w * slide, w * slide
    + win_len)``; since each key is in order no tuple comes after a window
    of its key that holds it has fired, so none is dropped, and the end of
    the stream flushes every window of a key that starts at or before the
    key's last ``ts``.

    A pane's tuples are added one by one within a pool batch, a pane's two
    pieces across a batch boundary (a lag shifts a key's panes off the
    batches') in order, and a window's panes one by one, oldest first, all
    in ``acc_dtype`` (float64: exact, the values are small integers; a lower
    precision is the control). ``last_batch`` is the batch that holds the
    window's last tuple of that key, later in the stream for a lagging key;
    ``must_deliver`` the windows with a tuple of that key."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    pane = math.gcd(win, slide)
    wpanes, spanes = win // pane, slide // pane
    if batch % slide or batch % n_k or pane % n_k:
        raise ValueError("kff_lag's reference wants whole slides a batch and "
                         "whole rounds of the keys a pane")
    ppb = batch // pane
    lag = pool[0].lag
    n_ticks = n_batches * batch
    n_win = (n_ticks - 1) // slide + 1          # windows that start by then
    s = np.zeros(((n_win - 1) * spanes + wpanes, n_k), acc_dtype)
    cols = np.arange(n_k)
    parts = []
    for recs in pool:                           # a pool batch's partials
        cell, row0, n_rows = _lane_panes(cfg, recs, batch)
        if np.dtype(acc_dtype) == np.float64:   # exact: small integers
            v = np.bincount(cell, recs["value"].astype(np.float64),
                            n_rows * n_k)
        else:                                   # tuple by tuple
            v = np.zeros(n_rows * n_k, acc_dtype)
            np.add.at(v, cell, recs["value"].astype(acc_dtype))
        parts.append(v.reshape(n_rows, n_k))
    rows = np.arange(ppb + 1)[:, None] + row0[None, :]
    for j in range(n_batches):
        at = j * ppb + rows
        if at.min() >= 0:
            s[at, cols] += parts[j % len(pool)]
        else:                       # a lagging key's ticks clamp to pane 0
            np.add.at(s, (np.maximum(at, 0), np.broadcast_to(cols, at.shape)),
                      parts[j % len(pool)])
    value = np.zeros((n_win, n_k), acc_dtype)
    for k in range(wpanes):                     # pane by pane, in acc_dtype
        np.add(value, s[k:k + (n_win - 1) * spanes + 1:spanes], out=value)
    del s
    # a key's last position, and its last ts; a window with a tuple of the
    # key starts at or before that ts (the key has a tuple every n_k ticks)
    last_pos = n_ticks - n_k + cols
    last_ts = np.maximum(last_pos - lag, 0)
    must = np.arange(n_win)[:, None] * slide <= last_ts[None, :]
    # the window's last tuple of the key: the latest of its positions with
    # ts before the window's end (p - lag < end), or the key's last
    end_pos = np.minimum(np.arange(n_win)[:, None] * slide + win - 1
                         + lag[None, :], last_pos[None, :])
    end_pos -= (end_pos - cols[None, :]) % n_k
    last_batch = np.where(must, end_pos // batch, -1).astype(np.int32)
    return {"value": value.T.astype(np.float64, copy=False),
            "last_batch": last_batch.T, "must_deliver": must.T}
