"""Configuration ``kff_late``: ``kff``'s keyed time-based sliding-window sum
through ``Key_FFAT`` over a stream in which a tenth of the tuples arrive late
(the delayed events of Beam's NEXmark generator), with an allowed lateness
(upstream's ``triggering_delay``) shorter than the worst delay.

Records, values, key order and the window's shape are ``kff``'s, taken from
``kff.py`` beside this file (which takes the records from ``kcb.py``). What is
this configuration's own: the delays drawn from the seed and the stamps that
apply them, the ring budget with the delay's and the stragglers' panes, the
checks, and a reference written anew from upstream's ``Triggerer_TB``
semantics, numpy on the logical stream, that imports nothing of the program.
"""

import importlib.util
import math
import os

import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("bench_cfg_kff_for_late",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_kff = _sibling("kff.py")
RECORD, KEY_FIELD, TS_FIELD = _kff.RECORD, _kff.KEY_FIELD, _kff.TS_FIELD
QUERY_COLUMNS = _kff.QUERY_COLUMNS
#: the stream's bytes are kff's: lateness moves a stamp, not a byte
min_bytes_per_batch = _kff.min_bytes_per_batch
#: held at 0: lanes folded into a slot an unfired pane held, tuples dropped
#: as late (none at this shape), windows the EOS flush left open
ENGINE_COUNTERS = _kff.ENGINE_COUNTERS
#: lanes folded after a window holding them had fired: more than 0, or the
#: run exercised no lateness
LATE_COUNTER = "ffat_late_lanes"


def _require_counting_engine():
    """A program that cannot say how many lanes came after a window holding
    them had fired cannot show that this stream exercised its late
    semantics: it fails here, before the runtime starts."""
    from windflow_tpu.observability.names import STAGE_COUNTERS
    missing = [c for c in ENGINE_COUNTERS + (LATE_COUNTER,)
               if c not in STAGE_COUNTERS]
    if missing:
        raise RuntimeError(f"the program publishes no {missing}: kff_late's "
                           f"program_checks cannot be made")


_require_counting_engine()


class LateRecords(np.ndarray):
    """A pool batch: ``kff``'s records, and ``offset``, each record's ``ts``
    less its batch's first index before the clip at 0: its place in the batch
    less its delay (int64; the delay is 0 for a tuple in order), drawn once
    from the seed. ``stamp`` applies it; the reference reads it."""

    offset = None


def make_pool(cfg, rng, batch, n_pool):
    """``kff``'s pool (every batch's values drawn first, so that a zero
    ``late_share`` gives ``kff``'s stream from the same seed), then each
    batch's delays: a record is late with probability ``late_share``, by a
    delay uniform in [1, ``max_delay``]."""
    out = []
    for recs in _kff.make_pool(cfg, rng, batch, n_pool):
        late = rng.random(batch) < cfg["late_share"]
        d = rng.integers(1, cfg["max_delay"] + 1, batch)
        recs = recs.view(LateRecords)
        recs.offset = np.arange(batch) - np.where(late, d, 0)
        out.append(recs)
    return out


def stamp(cfg, recs, first_index):
    """Records ``first_index ...`` of the logical stream: ``kcb``'s
    progressive id within the key, and ``ts`` the position less the record's
    delay, never below 0. In one buffer, written in place: the generator
    thread shares the host with the system it feeds, and this costs what
    ``kcb``'s stamp costs."""
    col = np.arange(first_index, first_index + len(recs), dtype=np.int64)
    col //= cfg["n_keys"]
    recs["id"] = col.view(np.uint64)
    np.add(recs.offset, first_index, out=col)
    np.maximum(col, 0, out=col)
    recs["ts"] = col.view(np.uint64)


def engine_budgets(cfg, batch):
    """(ring slots a key, fired windows a key a batch), from the deployment.

    ``kff``'s, and the ring holds more panes: the first unfired pane lies a
    window and the allowed lateness behind the watermark, and the watermark
    lies behind the batch's first tick by at most ``max_delay`` (the last
    tuple of the batch before is at most that late). So a batch folds into at
    most ``kff``'s 64 + 64 + 1 panes plus the delay's 8 and the stragglers'
    20 (157; the engine rounds up to a power of two, 256, as ``kff``'s). The
    fired windows stay ``kff``'s 65: the watermark moves a batch a batch
    unless a whole slide of a batch's tail comes late."""
    slots, wins = _kff.engine_budgets(cfg, batch)
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    return (slots + -(-cfg["delay"] // pane) + -(-cfg["max_delay"] // pane),
            wins)


def build_ops(cfg, batch):
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu.basic import win_type_t
    slots, wins = engine_budgets(cfg, batch)
    return [wf.Key_FFAT(lambda t: t.value, jnp.add,
                        spec=wf.WindowSpec(cfg["win_len"], cfg["slide"],
                                           win_type_t.TB, delay=cfg["delay"]),
                        num_keys=cfg["n_keys"], name="kff_late_window",
                        pane_capacity=slots, max_wins=wins)]


def structure_checks(cfg, window):
    """``kff``'s first check with the deployment's allowed lateness besides,
    and the two budgets this configuration's, at the batch the fired-window
    budget stands for."""
    first = "window_not_key_ffat_value_fold_on_global_time"
    is_kff = (_kff.structure_checks(cfg, window)[first] == (0, 0)
              and window.spec.delay == cfg["delay"])
    counters = window.stage_counters()
    have = tuple(counters.get(b) for b in _kff.ENGINE_BUDGETS)
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    batch = (counters.get("fired_window_budget", 0) - 1) * cfg["slide"]
    want = None
    if batch > 0 and batch % pane == 0:
        slots, wins = engine_budgets(cfg, batch)
        want = (_kff._next_pow2(slots), wins)
    return {first: (0 if is_kff else 1, 0),
            "engine_budgets_not_the_deployments": (0 if have == want else 1,
                                                   0)}


def program_checks(cfg, ops):
    """``kff``'s five, and ``late_lanes_absent``: a run in which no lane came
    after a window holding it had fired did not exercise what this
    configuration is for."""
    counters = ops[-1].stage_counters()
    checks = structure_checks(cfg, ops[-1])
    checks.update({c: (counters[c], 0) for c in ENGINE_COUNTERS})
    checks["late_lanes_absent"] = (
        0 if counters.get(LATE_COUNTER, 0) > 0 else 1, 0)
    return checks


# ---- the reference --------------------------------------------------------


def fired_windows(cfg, watermark):
    """Windows fired once the largest ``ts`` seen is ``watermark``
    (``Triggerer_TB``): window ``w`` covers ``[w * slide, w * slide +
    win_len)`` and fires when ``w * slide + win_len + delay <= watermark``;
    the count of such ``w``."""
    return max(0, (watermark - cfg["delay"] - cfg["win_len"])
               // cfg["slide"] + 1)


def _horizons(cfg, pool, n_batches, batch):
    """Per batch, the windows fired before it (the first one still open),
    and the stream's largest ``ts``."""
    most = [int(recs.offset.max()) for recs in pool]
    first_open = np.zeros(n_batches, np.int64)
    wm, fired = -1, 0
    for j in range(n_batches):
        first_open[j] = fired
        wm = max(wm, j * batch + max(most[j % len(pool)], -j * batch))
        fired = max(fired, fired_windows(cfg, wm))
    return first_open, wm


def _batch_part(cfg, recs, j, batch, c, acc_dtype, in_order):
    """What one batch adds, in coordinates relative to its first tick
    (``j * batch``, a whole number of panes and slides): the in-order
    tuples' pane sums (``[panes, key]`` in ``acc_dtype``, tuple by tuple,
    from pane ``lp0``), the late tuples' window sums as differences
    (``[windows, key]`` int64 from window ``c``), the windows the batch
    reaches (``[windows, key]`` bool from window ``w0``), and the counts of
    late and dropped tuples. ``c`` is the first window open before the batch,
    relative."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    pane = math.gcd(win, slide)
    wpanes, spanes = win // pane, slide // pane
    t0 = j * batch
    rts = np.maximum(recs.offset, -t0)                      # ts - t0
    key = recs["key"].astype(np.int64)
    v = recs["value"].astype(np.int64)
    last_w = rts // slide
    # the first window holding ts, and never one before window 0
    first_w = np.maximum((rts - win) // slide + 1, -(t0 // slide))
    if in_order:
        whole = np.ones(batch, bool)
        late = np.zeros(batch, bool)
    else:
        whole = first_w >= c                  # every window holding it open
        late = (first_w < c) & (last_w >= c)  # some fired: the open ones
    lp = rts // pane
    lp0 = int(lp[whole].min()) if whole.any() else 0
    n_lp = int(lp[whole].max()) - lp0 + 1 if whole.any() else 0
    cell = (lp[whole] - lp0) * n_k + key[whole]
    if np.dtype(acc_dtype) == np.float64:        # exact: small integers
        panes = np.bincount(cell, v[whole], n_lp * n_k).reshape(n_lp, n_k)
    else:                                        # tuple by tuple
        panes = np.zeros(n_lp * n_k, acc_dtype)
        np.add.at(panes, cell, v[whole].astype(acc_dtype))
        panes = panes.reshape(n_lp, n_k)
    counts = np.bincount(cell, None, n_lp * n_k).reshape(n_lp, n_k)
    # late tuples: + v at window c, - v one past the last window holding ts
    n_lw = max(1, int(last_w.max()) - c + 2)
    ends = (last_w[late] + 1 - c) * n_k + key[late]
    late_diff = (np.bincount(key[late], v[late], n_lw * n_k)
                 - np.bincount(ends, v[late], n_lw * n_k)).reshape(n_lw, n_k)
    late_hits = np.cumsum((np.bincount(key[late], None, n_lw * n_k)
                           - np.bincount(ends, None, n_lw * n_k)
                           ).reshape(n_lw, n_k), axis=0)[:-1]
    # windows the batch reaches: those holding a counted pane, from the one
    # whose last pane is lp0, and those the late tuples reach, from c
    w0 = min(c, -(-(lp0 - wpanes + 1) // spanes))
    w1 = max(c + n_lw - 1, (lp0 + n_lp - 1) // spanes + 1)
    edge = np.zeros((n_lp + 1, n_k), np.int64)
    np.cumsum(counts, axis=0, out=edge[1:])
    first_p = np.clip(np.arange(w0, w1) * spanes - lp0, 0, n_lp)
    last_p = np.clip(np.arange(w0, w1) * spanes + wpanes - lp0, 0, n_lp)
    reached = edge[last_p] > edge[first_p]
    reached[c - w0:c - w0 + n_lw - 1] |= late_hits > 0
    return {"panes": panes, "lp0": lp0,
            "late": late_diff.astype(np.int32),
            "reached": reached, "w0": w0, "n_late": int(np.count_nonzero(late)),
            "n_dropped": int(np.count_nonzero(~whole & ~late))}


def reference(cfg, pool, n_batches, batch, acc_dtype=np.float64,
              in_order=False):
    """Sum of ``value`` per (key, window) over the first ``n_batches``
    batches, under ``Triggerer_TB``'s semantics at batch granularity:

    - the watermark after batch ``b`` is the largest ``ts`` pushed so far,
      and after each batch every window with ``end + delay <= watermark``
      fires (``fired_windows``);
    - a tuple of batch ``b`` adds to the windows ``[max(first window holding
      ts, first window unfired after b - 1), last window holding ts]``: all
      of them for a tuple in order, the open ones for a late one, none (a
      drop) when every one has fired;
    - at the end of the stream every window that starts at or before the
      largest ``ts`` is flushed with what it holds.

    In-order tuples are added one by one into their pane and a window's
    panes one by one, oldest first, in ``acc_dtype``; a window's late part
    is added last (float64: exact, the values are small integers; a lower
    precision is the control). ``in_order=True``, the other control, counts
    every tuple in every window that holds it. ``last_batch`` is the batch
    of a window's last counted tuple; ``must_deliver`` says which windows
    have one. ``late_lanes`` and ``old_drops`` count the tuples that came
    after a window holding them had fired, and those dropped."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    pane = math.gcd(win, slide)
    wpanes, spanes = win // pane, slide // pane
    if batch % slide:
        raise ValueError("kff_late's reference wants whole slides a batch")
    ppb, wpb = batch // pane, batch // slide
    first_open, wm = _horizons(cfg, pool, n_batches, batch)
    n_win = wm // slide + 1                     # windows that start by then
    s = np.zeros(((n_win - 1) * spanes + wpanes, n_k), acc_dtype)
    late = np.zeros((n_win + 1, n_k), np.int32)
    last_batch = np.full((n_win, n_k), -1, np.int32)
    parts, n_late, n_dropped = {}, 0, 0
    for j in range(n_batches):
        p = j % len(pool)
        recs = pool[p]
        c = int(first_open[j]) - j * wpb
        # the same pool batch against the same relative horizon adds the
        # same, once no tuple of it is clipped at tick 0 or window 0 (its
        # largest delay is at most batch - min(offset))
        early = j * batch < win + slide + batch - int(recs.offset.min())
        tag = (p, c, j if early else None)
        if tag not in parts:
            parts[tag] = _batch_part(cfg, recs, j, batch, c, acc_dtype,
                                     in_order)
        part = parts[tag]
        n_late += part["n_late"]
        n_dropped += part["n_dropped"]
        lo = j * ppb + part["lp0"]
        s[lo:lo + len(part["panes"])] += part["panes"]
        lo = j * wpb + c
        late[lo:lo + len(part["late"])] += part["late"]
        lo = j * wpb + part["w0"]
        reached = part["reached"][max(0, -lo):n_win - lo]
        view = last_batch[max(0, lo):max(0, lo) + len(reached)]
        view[reached] = j
    value = np.zeros((n_win, n_k), acc_dtype)
    for k in range(wpanes):                     # pane by pane, in acc_dtype
        np.add(value, s[k:k + (n_win - 1) * spanes + 1:spanes], out=value)
    del s
    np.cumsum(late, axis=0, out=late)
    for lo in range(0, n_win, 1 << 14):         # the late part, last
        np.add(value[lo:lo + (1 << 14)],
               late[lo:min(n_win, lo + (1 << 14))].astype(acc_dtype),
               out=value[lo:lo + (1 << 14)])
    del late
    return {"value": value.T.astype(np.float64, copy=False),
            "last_batch": last_batch.T, "must_deliver": last_batch.T >= 0,
            "late_lanes": n_late, "old_drops": n_dropped}


def in_order_results(mod, cfg, pool, n_batches, batch):
    """The second control (``run.py::run_cell``'s ``control=``): the
    reference that counts every tuple in every window that holds it, put in
    the program's place. It has to come out as not correct, through the late
    lanes alone."""
    exp = reference(cfg, pool, n_batches, batch, in_order=True)
    key, wid = np.nonzero(exp["must_deliver"])
    return key, wid, exp["value"][key, wid]
