"""Configuration ``kff``: the keyed time-based sliding-window sum through
``Key_FFAT`` (upstream ``src/mp_test_gpu``, the ``Key_FFAT_GPU`` time-based
tests; FlatFAT after Tangwongsan et al., VLDB 2015).

Records, generator, stamps and key order are ``kcb``'s, taken from ``kcb.py``
beside this file, and the window is ``kpf``'s asked at a slide a quarter as
long: 64 panes a window. What is this configuration's own: the window stage
with its ring and fired-window budgets, the checks on it, the needed bytes,
and a reference written anew, numpy on the logical stream, that imports
nothing of the program.
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
#: a pane partial as any implementation must keep it: the sum, and how many
#: tuples it holds (a window without a tuple is not delivered)
PARTIAL_BYTES = 8
#: a window result: key, id, ts, sum
RESULT_BYTES = 16

#: what the program's window stage must publish: lanes folded into a ring
#: slot whose pane had not fired, tuples dropped as late, windows the EOS
#: flush left open
ENGINE_COUNTERS = ("ffat_ring_overruns", "old_drops",
                   "windows_undelivered_at_eos")
ENGINE_BUDGETS = ("ffat_pane_slots", "fired_window_budget")


def _require_counting_engine():
    """A program whose ``Key_FFAT`` cannot say that a lane overran the ring,
    was dropped as late or that the EOS flush left a window behind cannot be
    held to this configuration's guarantees: it fails here, before the
    runtime starts, not after a window."""
    from windflow_tpu.observability.names import STAGE_COUNTERS
    missing = [c for c in ENGINE_COUNTERS if c not in STAGE_COUNTERS]
    if missing:
        raise RuntimeError(f"the program publishes no {missing}: kff's "
                           f"program_checks cannot be made")


_require_counting_engine()


def _shapes(cfg, batch):
    """(pane in ticks, tuples a key a pane, panes a batch): keys go round, a
    tick a tuple, so a pane of a multiple of ``n_keys`` ticks holds the same
    count of every key, and a batch of whole panes closes whole panes."""
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    if batch % pane or pane % cfg["n_keys"]:
        raise ValueError("kff's reference wants whole panes a batch and "
                         "whole rounds of the keys a pane")
    return pane, pane // cfg["n_keys"], batch // pane


def make_pool(cfg, rng, batch, n_pool):
    _shapes(cfg, batch)
    # kcb's generator reads n_keys and v_max; its own shape check is of a
    # count-based slide, which this configuration has none of
    return _kcb.make_pool(dict(cfg, win_len=1, slide=1), rng, batch, n_pool)


def engine_budgets(cfg, batch):
    """(ring slots a key, fired windows a key a batch), from the deployment.

    When a batch arrives, the first pane that has not fired is the first of
    the oldest open window, which may start a whole window before the batch:
    the ring holds that window's panes, the batch's and the one the batch
    before left open, ``win_len / pane + batch // pane + 1`` (64 + 64 + 1; the
    engine rounds up to a power of two, 256). A batch's last tick closes at
    most ``batch // slide + 1`` windows a key (65); the ``win_len / slide``
    windows open at the end of the stream go out in passes of as many."""
    pane, _, ppb = _shapes(cfg, batch)
    return cfg["win_len"] // pane + ppb + 1, batch // cfg["slide"] + 1


def build_ops(cfg, batch):
    import jax.numpy as jnp
    import windflow_tpu as wf
    from windflow_tpu.basic import win_type_t
    slots, wins = engine_budgets(cfg, batch)
    return [wf.Key_FFAT(lambda t: t.value, jnp.add,
                        spec=wf.WindowSpec(cfg["win_len"], cfg["slide"],
                                           win_type_t.TB),
                        num_keys=cfg["n_keys"], name="kff_window",
                        pane_capacity=slots, max_wins=wins)]


def _next_pow2(n):
    return 1 << max(0, int(n) - 1).bit_length()


def structure_checks(cfg, window):
    """The window stage is a ``Key_FFAT`` on the global-time path at the
    configuration's window, with a lift that reads the tuple (the value fold,
    not the count histogram), and its two budgets are the deployment's at the
    batch its fired-window budget stands for."""
    from windflow_tpu.operators.win_patterns import Key_FFAT
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    is_kff = (type(window) is Key_FFAT and window.global_time
              and not window.count_lift and not window.spec.is_cb
              and (window.spec.win_len, window.spec.slide, window.num_keys)
              == (cfg["win_len"], cfg["slide"], cfg["n_keys"])
              and (window.pane_len, window.wpanes, window.spanes)
              == (pane, cfg["win_len"] // pane, cfg["slide"] // pane))
    counters = window.stage_counters()
    have = tuple(counters.get(b) for b in ENGINE_BUDGETS)
    batch = (counters.get("fired_window_budget", 0) - 1) * cfg["slide"]
    want = None
    if batch > 0 and batch % pane == 0:
        slots, wins = engine_budgets(cfg, batch)
        want = (_next_pow2(slots), wins)
    return {"window_not_key_ffat_value_fold_on_global_time":
            (0 if is_kff else 1, 0),
            "engine_budgets_not_the_deployments": (0 if have == want else 1,
                                                   0)}


def program_checks(cfg, ops):
    """``structure_checks`` of the chain's last stage, and its counters: no
    lane was folded into a ring slot that an unfired pane still held, nothing
    was dropped as late, and the EOS flush left nothing open behind."""
    counters = ops[-1].stage_counters()
    checks = structure_checks(cfg, ops[-1])
    checks.update({c: (counters[c], 0) for c in ENGINE_COUNTERS})
    return checks


def reference(cfg, pool, n_batches, batch, acc_dtype=np.float64):
    """Sum of ``value`` per (key, window) over the first ``n_batches`` batches:
    window ``w`` of a key covers its tuples with ``ts`` in ``[w * slide, w *
    slide + win_len)``, the last ones partial (end of stream). A pane's tuples
    are added one by one and a window's panes one by one, oldest first, in
    ``acc_dtype`` (float64: exact, the values are small integers; a lower
    precision is the control). ``last_batch`` is the batch that holds the
    window's last tuple; every window that starts inside the stream holds a
    tuple of every key."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    pane, per_key, ppb = _shapes(cfg, batch)
    wpanes, spanes = win // pane, slide // pane
    partials = []                   # per pool batch: [pane of the batch, key]
    for recs in pool:
        v = recs["value"].reshape(ppb, per_key, n_k)
        acc = np.zeros((ppb, n_k), acc_dtype)
        for r in range(per_key):                # tuple by tuple, in acc_dtype
            np.add(acc, v[:, r].astype(acc_dtype), out=acc)
        partials.append(acc)
    n_ticks = n_batches * batch
    n_win = (n_ticks - 1) // slide + 1          # windows that start in the stream
    # the stream's panes, then empty ones as far as the last window reaches
    s = np.zeros(((n_win - 1) * spanes + wpanes, n_k), acc_dtype)
    for j in range(n_batches):
        s[j * ppb:(j + 1) * ppb] = partials[j % len(pool)]
    value = np.zeros((n_win, n_k), acc_dtype)
    for k in range(wpanes):                     # pane by pane, in acc_dtype
        np.add(value, s[k:k + (n_win - 1) * spanes + 1:spanes], out=value)
    # a window's last tuple is the one before its end, or the stream's last
    last_tick = np.minimum(np.arange(n_win) * slide + win, n_ticks) - 1
    last_batch = np.broadcast_to(last_tick // batch, (n_k, n_win))
    return {"value": value.T.astype(np.float64), "last_batch": last_batch,
            "must_deliver": np.ones((n_k, n_win), bool)}


def min_bytes_per_batch(cfg, batch):
    """The least traffic one batch needs, whatever implements the window: the
    columns the query reads (``QUERY_COLUMNS``) read once, each pane partial a
    batch closes written and read once, each window result written once."""
    _, _, ppb = _shapes(cfg, batch)
    partials = cfg["n_keys"] * ppb
    results = cfg["n_keys"] * (batch // cfg["slide"])
    return (batch * 4 * len(QUERY_COLUMNS) + 2 * partials * PARTIAL_BYTES
            + results * RESULT_BYTES)
