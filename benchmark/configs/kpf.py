"""Configuration ``kpf``: the keyed time-based sliding-window sum through
``Pane_Farm`` (upstream ``src/mp_test_gpu``, the ``Pane_Farm_GPU`` time-based
tests; panes after Li et al., SIGMOD Record 2005).

Records, generator, stamps and key order are ``kcb``'s, taken from ``kcb.py``
beside this file, so that the two keyed cells differ by the window stage
alone. What is this configuration's own: the window stage with a budget for
each of its two engines, the checks on both, the needed bytes, and a
reference written anew, numpy on the logical stream, that imports nothing of
the program.
"""

import importlib.util
import math
import os

import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("bench_cfg_kcb_records", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_kcb = _sibling("kcb.py")
RECORD, KEY_FIELD, TS_FIELD = _kcb.RECORD, _kcb.KEY_FIELD, _kcb.TS_FIELD
stamp = _kcb.stamp
#: what the query reads of a tuple, 4 bytes each as the device holds them
QUERY_COLUMNS = ("key", "ts", "value")
#: a result, of a pane or of a window: key, id, ts, sum
RESULT_BYTES = 16

STAGES = ("plq", "wlq")
#: what the program's window stage must publish, for each of its two engines
ENGINE_COUNTERS = ("archive_overwrites", "old_drops",
                   "windows_undelivered_at_eos")
ENGINE_BUDGETS = ("archive_slots", "fired_window_budget")


def _require_counting_stages():
    """A program whose ``Pane_Farm`` cannot say, for each of its engines, that
    a ring overwrote a live tuple or pane result, dropped one as late or left
    a window behind at EOS cannot be held to this configuration's guarantees:
    it fails here, before the runtime starts, not after a window."""
    from windflow_tpu.observability.names import STAGE_COUNTERS
    missing = [f"{s}_{c}" for s in STAGES for c in ENGINE_COUNTERS
               if f"{s}_{c}" not in STAGE_COUNTERS]
    if missing:
        raise RuntimeError(f"the program publishes no {missing}: kpf's "
                           f"program_checks cannot be made")


_require_counting_stages()


def _shapes(cfg, batch):
    """(pane in ticks, tuples a key a pane, panes a batch): keys go round, a
    tick a tuple, so a pane of a multiple of ``n_keys`` ticks holds the same
    count of every key, and a batch of whole panes closes whole panes."""
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    if batch % pane or pane % cfg["n_keys"]:
        raise ValueError("kpf's reference wants whole panes a batch and "
                         "whole rounds of the keys a pane")
    return pane, pane // cfg["n_keys"], batch // pane


def make_pool(cfg, rng, batch, n_pool):
    _shapes(cfg, batch)
    # kcb's generator reads n_keys and v_max; its own shape check is of a
    # count-based slide, which this configuration has none of
    return _kcb.make_pool(dict(cfg, win_len=1, slide=1), rng, batch, n_pool)


def engine_budgets(cfg, batch):
    """(PLQ ring slots a key, PLQ fired panes a batch, WLQ ring slots a key,
    WLQ fired windows a batch), from the deployment.

    Before a batch's panes fire, a key's PLQ ring holds the tuples of its open
    pane and the batch's share: ``pane / n_keys + batch / n_keys`` (128 +
    2,048; the engine rounds up to a power of two, 4,096). A batch closes at
    most ``batch // pane + 1`` panes a key (the pane left open by the batch
    before, and its own but the last). The WLQ ring holds the pane results of
    an open window and a batch's: ``win_len / pane + batch // pane + 1`` (16 +
    16 + 1, rounded to 64), and a batch's pane results close at most ``batch
    // slide + 1`` windows a key. No more than either fired budget is open at
    the end of the stream."""
    pane, per_key, _ = _shapes(cfg, batch)
    n_k = cfg["n_keys"]
    return (per_key + batch // n_k, n_k * (batch // pane + 1),
            cfg["win_len"] // pane + batch // pane + 1,
            n_k * (batch // cfg["slide"] + 1))


def build_ops(cfg, batch):
    import windflow_tpu as wf
    from windflow_tpu.basic import win_type_t
    plq_slots, plq_wins, wlq_slots, wlq_wins = engine_budgets(cfg, batch)
    project = wf.BatchMap(lambda p: {"value": p["value"]}, name="kpf_project")
    window = wf.Pane_Farm(
        lambda pid, it: it.sum("value"), lambda wid, it: it.sum(),
        wf.WindowSpec(cfg["win_len"], cfg["slide"], win_type_t.TB),
        num_keys=cfg["n_keys"], name="kpf_window",
        plq_slots=plq_slots, plq_max_wins=plq_wins,
        wlq_slots=wlq_slots, wlq_max_wins=wlq_wins)
    return [project, window]


def _next_pow2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def structure_checks(cfg, window):
    """The window stage is a ``Pane_Farm`` over two ``Win_Seq`` at the
    configuration's window, and its four budgets are the deployment's at the
    batch its fired-pane budget stands for."""
    from windflow_tpu.operators.win_patterns import Pane_Farm
    from windflow_tpu.operators.win_seq import Win_Seq
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    is_pf = (isinstance(window, Pane_Farm)
             and type(window.plq) is Win_Seq and type(window.wlq) is Win_Seq
             and (window.pane_len, window.wpanes, window.spanes)
             == (pane, cfg["win_len"] // pane, cfg["slide"] // pane))
    counters = window.stage_counters()
    have = tuple(counters.get(f"{s}_{b}") for s in STAGES
                 for b in ENGINE_BUDGETS)
    batch = (counters.get("plq_fired_window_budget", 0) // cfg["n_keys"]
             - 1) * pane
    want = None
    if batch > 0:
        p_slots, p_wins, w_slots, w_wins = engine_budgets(cfg, batch)
        want = (_next_pow2(p_slots), p_wins, _next_pow2(w_slots), w_wins)
    return {"window_not_pane_farm_over_two_win_seq": (0 if is_pf else 1, 0),
            "engine_budgets_not_the_deployments": (0 if have == want else 1,
                                                   0)}


def program_checks(cfg, ops):
    """``structure_checks`` of the chain's last stage, and for each of its
    engines: the ring overwrote nothing that an unfired pane or window still
    needed, nothing was dropped as late, and the EOS flush left nothing open
    behind."""
    counters = ops[-1].stage_counters()
    checks = structure_checks(cfg, ops[-1])
    checks.update({f"{s}_{c}": (counters[f"{s}_{c}"], 0)
                   for s in STAGES for c in ENGINE_COUNTERS})
    return checks


def reference(cfg, pool, n_batches, batch, acc_dtype=np.float64):
    """Sum of ``value`` per (key, window) over the first ``n_batches`` batches:
    window ``w`` of a key covers its tuples with ``ts`` in ``[w * slide, w *
    slide + win_len)``, the last ones partial (end of stream). A pane's tuples
    are added one by one and a window's panes one by one, in ``acc_dtype``
    (float64: exact, the values are small integers; a lower precision is the
    control). ``last_batch`` is the batch that holds the window's last tuple;
    every window that starts inside the stream holds a tuple of every key."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    pane, per_key, ppb = _shapes(cfg, batch)
    wpanes, spanes = win // pane, slide // pane
    pane_sums = []                  # per pool batch: [pane of the batch, key]
    for recs in pool:
        v = recs["value"].reshape(ppb, per_key, n_k)
        acc = np.zeros((ppb, n_k), acc_dtype)
        for r in range(per_key):                # tuple by tuple, in acc_dtype
            acc = (acc + v[:, r].astype(acc_dtype)).astype(acc_dtype)
        pane_sums.append(acc)
    s = np.concatenate([pane_sums[j % len(pool)] for j in range(n_batches)])
    n_ticks = n_batches * batch
    n_win = (n_ticks - 1) // slide + 1          # windows that start in the stream
    s = np.concatenate([s, np.zeros((n_win * spanes + wpanes - len(s), n_k),
                                    acc_dtype)])
    value = np.zeros((n_win, n_k), acc_dtype)
    for k in range(wpanes):                     # pane by pane, in acc_dtype
        value = (value + s[k:k + n_win * spanes:spanes]).astype(acc_dtype)
    # a window's last tuple is the one before its end, or the stream's last
    last_tick = np.minimum(np.arange(n_win) * slide + win, n_ticks) - 1
    last_batch = np.broadcast_to(last_tick // batch, (n_k, n_win))
    return {"value": value.T.astype(np.float64), "last_batch": last_batch,
            "must_deliver": np.ones((n_k, n_win), bool)}


def min_bytes_per_batch(cfg, batch):
    """The least traffic one batch needs, whatever implements the window: the
    columns the query reads (``QUERY_COLUMNS``) read once, each pane result
    (key, id, ts, sum) written and read once, each window result written
    once."""
    pane, _, ppb = _shapes(cfg, batch)
    pane_results = cfg["n_keys"] * ppb
    window_results = cfg["n_keys"] * (batch // cfg["slide"])
    return (batch * 4 * len(QUERY_COLUMNS)
            + 2 * pane_results * RESULT_BYTES + window_results * RESULT_BYTES)
