"""Configuration ``kff_drawdown``: each symbol's trailing maximum drawdown
through ``Key_FFAT``, a non-commutative combine over a result struct.

Over the last window of a symbol's ticks, the drawdown is the largest fall from
a price to any later, lower price (maximum drawdown, Magdon-Ismail and Atiya,
Risk 2004, over a sliding window). The query is this configuration's own;
upstream's ``Key_FFAT`` tests sum the value. It is written in upstream's
``Key_FFAT`` API as a lift and a combine over a ``result_t`` that holds the
window's highest and lowest prices and its drawdown, and a ``Map`` that yields
the drawdown: ``lift(t) = (hi, lo, dd) = (p, p, 0)``, ``combine(a, b) =
(max(a.hi, b.hi), min(a.lo, b.lo), max(a.dd, b.dd, a.hi - b.lo))``. The
combine is associative and not commutative (``a`` is the earlier operand), its
third field reads the other two, and its identity ``(0, 2^30, 0)`` is no
single scalar.

Records, key order, stamps, window and budgets are ``kff``'s, taken from
``kff.py`` beside this file. What is this configuration's own: the prices (a
per-key random walk drawn from the seed), the window stage and its ``Map``, the
checks, the needed bytes, and a reference written anew, numpy on the logical
stream, that imports nothing of the program.
"""

import importlib.util
import math
import os
from typing import NamedTuple

import numpy as np


def _sibling(name):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), name)
    spec = importlib.util.spec_from_file_location("bench_cfg_kff_for_dd", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_kff = _sibling("kff.py")
RECORD, KEY_FIELD, TS_FIELD = _kff.RECORD, _kff.KEY_FIELD, _kff.TS_FIELD
stamp = _kff.stamp
engine_budgets = _kff.engine_budgets
QUERY_COLUMNS = _kff.QUERY_COLUMNS
#: a pane partial as any implementation must keep it: hi, lo, dd, and how
#: many tuples it holds (a window without a tuple is not delivered)
PARTIAL_BYTES = 16
#: a window result: key, id, ts, drawdown
RESULT_BYTES = 16
#: held at 0: lanes folded into a slot an unfired pane held, tuples dropped
#: as late, windows the EOS flush left open
ENGINE_COUNTERS = _kff.ENGINE_COUNTERS
#: the (key, pane) runs the engine's ordered fold combined: above 0, or the
#: run's window stage did not take the path this configuration is for
ORDERED_COUNTER = "ffat_ordered_runs"


def _require_counting_engine():
    """A program that cannot say that its ordered fold ran, that a lane
    overran the ring, was dropped as late or that the EOS flush left a
    window behind cannot be held to this configuration's guarantees: it
    fails here, before the runtime starts."""
    from windflow_tpu.observability.names import STAGE_COUNTERS
    missing = [c for c in ENGINE_COUNTERS + (ORDERED_COUNTER,)
               if c not in STAGE_COUNTERS]
    if missing:
        raise RuntimeError(f"the program publishes no {missing}: "
                           f"kff_drawdown's program_checks cannot be made")


_require_counting_engine()


# ---- the query ------------------------------------------------------------


class Drawdown(NamedTuple):
    """A run of ticks' result struct: its highest and lowest price and its
    largest fall from a price to a later, lower one."""
    hi: object
    lo: object
    dd: object


def lift(t):
    import jax.numpy as jnp
    return Drawdown(t.value, t.value, jnp.zeros_like(t.value))


def combine(a, b):
    """``a`` holds the earlier ticks: its peak may fall to ``b``'s trough."""
    import jax.numpy as jnp
    return Drawdown(jnp.maximum(a.hi, b.hi), jnp.minimum(a.lo, b.lo),
                    jnp.maximum(jnp.maximum(a.dd, b.dd), a.hi - b.lo))


def build_ops(cfg, batch, combine_fn=combine):
    import windflow_tpu as wf
    from windflow_tpu.basic import win_type_t
    slots, wins = engine_budgets(cfg, batch)
    return [wf.Key_FFAT(lift, combine_fn, identity=Drawdown(*cfg["identity"]),
                        spec=wf.WindowSpec(cfg["win_len"], cfg["slide"],
                                           win_type_t.TB),
                        num_keys=cfg["n_keys"], name="kff_drawdown_window",
                        pane_capacity=slots, max_wins=wins),
            wf.Map(lambda t: t.data.dd, name="kff_drawdown_dd")]


# ---- the controls: each must come out as not correct ----------------------


def leafwise(a, b):
    """The combine a leaf-by-leaf fold computes: the cross term dropped,
    ``dd = max(a.dd, b.dd)``, so no fall across two runs is seen."""
    import jax.numpy as jnp
    return Drawdown(jnp.maximum(a.hi, b.hi), jnp.minimum(a.lo, b.lo),
                    jnp.maximum(a.dd, b.dd))


def leafwise_ops(cfg, batch):
    return build_ops(cfg, batch, combine_fn=leafwise)


def halves_reduce(combine_fn, xs, axis=0):
    """A window's panes reduced as a tree that pairs element ``i`` with
    element ``i + n // 2`` (an odd last one carried): right for two and
    three panes, a later peak before an earlier trough from four on. Put in
    the place of ``win_seqffat.ordered_reduce``."""
    import jax
    import jax.numpy as jnp
    n = jax.tree.leaves(xs)[0].shape[axis]
    while n > 1:
        half = n // 2

        def cut(lo, hi):
            return jax.tree.map(
                lambda x: jax.lax.slice_in_dim(x, lo, hi, axis=axis), xs)
        xs = jax.tree.map(lambda c, r: jnp.concatenate([c, r], axis=axis),
                          combine_fn(cut(0, half), cut(half, 2 * half)),
                          cut(2 * half, n))
        n = half + n - 2 * half
    return jax.tree.map(lambda x: jnp.squeeze(x, axis=axis), xs)


# ---- the stream -----------------------------------------------------------


def make_pool(cfg, rng, batch, n_pool):
    """``kff``'s records (keys round robin), every ``value`` a price: each
    key's walk starts uniform over ``price_start`` and steps uniform over
    ``[-price_step, price_step]``, clipped to ``[1, price_max]``; it runs on
    from one pool batch to the next."""
    _kff._shapes(cfg, batch)            # whole panes a batch, keys a pane
    n_k = cfg["n_keys"]
    per_key = batch // n_k
    lo, hi = cfg["price_start"]
    walk = rng.integers(-cfg["price_step"], cfg["price_step"] + 1,
                        (n_pool * per_key, n_k))
    walk[0] = rng.integers(lo, hi, n_k)
    np.cumsum(walk, axis=0, out=walk)
    np.clip(walk, 1, cfg["price_max"], out=walk)
    pool = []
    for j in range(n_pool):
        recs = np.zeros(batch, RECORD)
        recs["key"] = np.arange(batch, dtype=np.int64) % n_k
        recs["value"] = walk[j * per_key:(j + 1) * per_key].reshape(-1)
        pool.append(recs)
    return pool


# ---- the checks -----------------------------------------------------------


def structure_checks(cfg, ops):
    """Stage 0 is a ``Key_FFAT`` on the global-time path at the
    configuration's window, with a combine that is none of ``jnp.add``,
    ``maximum`` or ``minimum``, and its two budgets are the deployment's at
    the batch its fired-window budget stands for; the last stage is the
    ``Map``."""
    import jax.numpy as jnp
    from windflow_tpu.operators.map import Map
    from windflow_tpu.operators.win_patterns import Key_FFAT
    window = ops[0]
    pane = math.gcd(cfg["win_len"], cfg["slide"])
    is_kff = (type(window) is Key_FFAT and window.global_time
              and not window.spec.is_cb
              and window.combine not in (jnp.add, jnp.maximum, jnp.minimum)
              and (window.spec.win_len, window.spec.slide, window.num_keys)
              == (cfg["win_len"], cfg["slide"], cfg["n_keys"])
              and (window.pane_len, window.wpanes, window.spanes)
              == (pane, cfg["win_len"] // pane, cfg["slide"] // pane))
    counters = window.stage_counters()
    have = tuple(counters.get(b) for b in _kff.ENGINE_BUDGETS)
    batch = (counters.get("fired_window_budget", 0) - 1) * cfg["slide"]
    want = None
    if batch > 0 and batch % pane == 0:
        slots, wins = engine_budgets(cfg, batch)
        want = (_kff._next_pow2(slots), wins)
    return {"window_not_key_ffat_ordered_fold_on_global_time":
            (0 if is_kff else 1, 0),
            "engine_budgets_not_the_deployments": (0 if have == want else 1,
                                                   0),
            "last_stage_not_the_map": (0 if len(ops) == 2
                                       and type(ops[-1]) is Map else 1, 0)}


def program_checks(cfg, ops):
    """``structure_checks``, ``kff``'s three counters of the window stage at
    0, and ``ordered_fold_absent``: 1 unless the ordered fold combined a
    run."""
    counters = ops[0].stage_counters()
    checks = structure_checks(cfg, ops)
    checks.update({c: (counters[c], 0) for c in ENGINE_COUNTERS})
    checks["ordered_fold_absent"] = (
        0 if counters.get(ORDERED_COUNTER, 0) > 0 else 1, 0)
    return checks


# ---- the reference --------------------------------------------------------


def _combine(a, b, out):
    """The combine in numpy, into ``out`` (``a`` may be ``out``): the cross
    term first, while ``a.hi`` is the earlier ticks'."""
    cross = a[0] - b[1]
    np.maximum(a[2], b[2], out=out[2])
    np.maximum(out[2], cross, out=out[2])
    np.maximum(a[0], b[0], out=out[0])
    np.minimum(a[1], b[1], out=out[1])


def reference(cfg, pool, n_batches, batch, acc_dtype=np.int32):
    """Drawdown per (key, window) over the first ``n_batches`` batches:
    window ``w`` of a key covers its tuples with ``ts`` in ``[w * slide, w *
    slide + win_len)``, the last ones partial (they end at the stream's last
    tuple). A pane's (hi, lo, dd) is folded tuple by tuple, oldest first (32
    steps a pool batch over ``[panes, keys]``), and a window's panes left to
    right (64 steps over ``[windows, keys]``), in ``acc_dtype`` (int32:
    exact, as the configuration states; a lower precision is the control).
    ``last_batch`` is the batch that holds the window's last tuple; every
    window that starts inside the stream holds a tuple of every key."""
    n_k, win, slide = cfg["n_keys"], cfg["win_len"], cfg["slide"]
    pane, per_key, ppb = _kff._shapes(cfg, batch)
    wpanes, spanes = win // pane, slide // pane
    ident = [np.asarray(i).astype(acc_dtype) for i in cfg["identity"]]

    def fresh(shape):
        return [np.full(shape, i, acc_dtype) for i in ident]
    summaries = []                  # per pool batch: [pane of the batch, key]
    for recs in pool:
        v = recs["value"].reshape(ppb, per_key, n_k).astype(acc_dtype)
        acc = fresh((ppb, n_k))
        for r in range(per_key):                # tuple by tuple
            p = v[:, r]
            _combine(acc, (p, p, np.zeros_like(p)), acc)
        summaries.append(acc)
    n_ticks = n_batches * batch
    n_win = (n_ticks - 1) // slide + 1          # windows that start in the stream
    # the stream's panes, then empty ones as far as the last window reaches
    s = fresh(((n_win - 1) * spanes + wpanes, n_k))
    for j in range(n_batches):
        for leaf, part in zip(s, summaries[j % len(pool)]):
            leaf[j * ppb:(j + 1) * ppb] = part
    value = fresh((n_win, n_k))
    for k in range(wpanes):                     # pane by pane, oldest first
        _combine(value, [leaf[k:k + (n_win - 1) * spanes + 1:spanes]
                         for leaf in s], value)
    del s
    # a window's last tuple is the one before its end, or the stream's last
    last_tick = np.minimum(np.arange(n_win) * slide + win, n_ticks) - 1
    last_batch = np.broadcast_to(last_tick // batch, (n_k, n_win))
    return {"value": value[2].T.astype(np.float64), "last_batch": last_batch,
            "must_deliver": np.ones((n_k, n_win), bool)}


def min_bytes_per_batch(cfg, batch):
    """The least traffic one batch needs, whatever implements the window: the
    columns the query reads (``QUERY_COLUMNS``) read once, each pane partial a
    batch closes written and read once, each window result written once."""
    _, _, ppb = _kff._shapes(cfg, batch)
    partials = cfg["n_keys"] * ppb
    results = cfg["n_keys"] * (batch // cfg["slide"])
    return (batch * 4 * len(QUERY_COLUMNS) + 2 * partials * PARTIAL_BYTES
            + results * RESULT_BYTES)
