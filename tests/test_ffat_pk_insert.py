"""The per-key time-based insert of ``Win_SeqFFAT`` folds an additive integer
lift in ``keyed_pane_fold``'s contraction, on panes relative to each key's
first unfired one, turns each key's row into the ring, writes the pane ids in
closed form and takes the watermark by a select-reduce over the key one-hot.
The formulation it replaced — four scatters over the lanes — is kept HERE as
the reference: both insert the same batches, one after another with the emit
between them, and must agree on every state leaf after every batch, bit for
bit, whichever branch of the fold a batch takes."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_step_programs import operations
from test_ysb_wmr_config import equations
from windflow_tpu.basic import win_type_t
from windflow_tpu.batch import Batch, CTRL_DTYPE, TupleRef
from windflow_tpu.operators.win_seqffat import Win_SeqFFAT, _b
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.ops.histogram import SPILL_M
from windflow_tpu.ops.lookup import table_lookup
from windflow_tpu.ops.segment import segment_reduce

K = 8                   # keys, round robin
C = 4096                # lanes a batch: four chunks of 1,024
PANE = 256              # ticks a pane = the slide: a chunk spans 4 panes
WPANES = 16             # panes a window
LAG = 4 * WPANES * PANE  # a lagging key's largest lag: four windows
LAGGING = (2, 5)        # a quarter of the keys lag
LEAVES = ("panes", "pane_count", "pane_of", "count", "wm", "dropped_old",
          "ring_overruns")


def parent_insert(op, state, batch):
    """``Win_SeqFFAT._insert``'s time-based per-key arm as it stood before the
    fold rode the contraction: four scatters over the lanes (value, count
    and pane id into ``[K*P]``, the watermark into ``[K]``)."""
    K, P = op.num_keys, op.P
    valid = batch.valid
    first_win = table_lookup(state.next_win, batch.key)
    horizon = first_win * op.spec.slide
    kept = valid & (batch.ts >= horizon)
    n_dropped = jnp.sum((valid & ~kept).astype(CTRL_DTYPE))
    valid = kept
    pane = batch.ts // op.pane_len
    slot = pane % P
    seg = jnp.where(valid, batch.key * P + slot, K * P)
    ring_overruns = state.ring_overruns + jnp.sum(
        (valid & (pane >= first_win * op.spanes + P)).astype(CTRL_DTYPE))
    lifted = jax.vmap(op.lift)(
        TupleRef(key=batch.key, id=batch.id, ts=batch.ts, data=batch.payload))
    upd = segment_reduce(lifted, seg, valid, K * P,
                         combine=None if op.combine is jnp.add else op.combine,
                         identity=op.identity)
    cnt_upd = segment_reduce(valid.astype(CTRL_DTYPE), seg, valid, K * P)
    pane_id_upd = jax.ops.segment_max(pane, seg, num_segments=K * P)
    touched = cnt_upd.reshape(K, P) > 0
    new_pane_of = jnp.where(touched, pane_id_upd.reshape(K, P), state.pane_of)
    fresh = touched & (new_pane_of != state.pane_of)

    def fold(tbl, u):
        u = u.reshape((K, P) + u.shape[1:])
        t = jnp.where(_b(fresh, tbl), jnp.asarray(op.identity, tbl.dtype), tbl)
        m = _b(touched, tbl)
        if op.combine is jnp.add:
            return jnp.where(m, t + u, t)
        return jnp.where(m, op.combine(t, u), t)

    counts_add = jnp.sum(cnt_upd.reshape(K, P), axis=1)
    ts_max = jax.ops.segment_max(jnp.where(valid, batch.ts, -1), batch.key,
                                 num_segments=K)
    wm_new = jnp.maximum(state.wm, ts_max)
    panes = jax.tree.map(fold, state.panes, upd)
    pane_count = jnp.where(fresh, 0, state.pane_count) + cnt_upd.reshape(K, P)
    return dataclasses.replace(
        state, panes=panes, pane_count=pane_count, pane_of=new_pane_of,
        count=state.count + counts_add, wm=wm_new,
        dropped_old=state.dropped_old + n_dropped,
        ring_overruns=ring_overruns)


def make_op(dtype=jnp.int32, slots=64, lift=None, combine=jnp.add):
    op = Win_SeqFFAT(lift or (lambda t: t.data["v"]), combine,
                     spec=WindowSpec(WPANES * PANE, PANE, win_type_t.TB),
                     num_keys=K, pane_capacity=64, max_wins=K * 20,
                     global_time=False,
                     identity=(jnp.iinfo(dtype).min if combine is jnp.maximum
                               else 0))
    op.P = slots            # the constructor rounds a ring to a power of two
    return op


def values(rng, dtype, extremes):
    """Integers in [0, 96], or a leaf's extremes and its neighbours, so that
    sums wrap at the leaf's width."""
    if not extremes:
        return rng.integers(0, 97, C).astype(dtype)
    info = np.iinfo(dtype)
    picks = np.array([info.min, info.min + 1, info.max - 1, info.max, 0, 1],
                     dtype)
    return picks[rng.integers(0, len(picks), C)]


def stream_batch(j, lag, rng, dtype, extremes=False, n_valid=C):
    """Batch ``j`` of a keyed stream, one tick a tuple, keys round robin, a
    key's ``ts`` its position less its lag, never below 0."""
    pos = j * C + np.arange(C)
    key = pos % K
    ts = np.maximum(pos - lag[key], 0)
    return key, ts, values(rng, dtype, extremes), np.arange(C) < n_valid


def as_batch(key, ts, v, valid):
    return Batch(key=jnp.asarray(key, CTRL_DTYPE),
                 id=jnp.arange(len(key), dtype=CTRL_DTYPE),
                 ts=jnp.asarray(ts, CTRL_DTYPE), payload={"v": jnp.asarray(v)},
                 valid=jnp.asarray(valid))


def stragglers(j, key, ts, rng):
    """40 lanes a chunk moved 10 panes back (still within their keys'
    open windows): under ``SPILL_M`` a chunk, so the partial branch."""
    ts = ts.copy()
    for c in range(C // 1024):
        lanes = 1024 * c + rng.choice(1024, 40, replace=False)
        ts[lanes] -= 10 * PANE
    assert 40 <= SPILL_M
    return key, ts


def overrun(j, key, ts, rng):
    """Three lanes of key 0 a whole ring past the rest of the batch."""
    ts = ts.copy()
    lanes = np.flatnonzero(key == 0)[-3:]
    ts[lanes] += 64 * PANE
    return key, ts


def old(j, key, ts, rng):
    """100 lanes of keys in step stamped 0: behind their keys' horizons."""
    ts = ts.copy()
    lanes = rng.choice(np.flatnonzero(~np.isin(key, LAGGING)), 100,
                       replace=False)
    ts[lanes] = 0
    return key, ts


#: name: (leaf dtype, ring slots, extreme values, batches, what batch 8's
#: lanes undergo). Every stream starts with its lagging keys clamped at 0.
CASES = {
    "in_order_lags": (jnp.int32, 64, False, 12, None),
    "stragglers_partial_branch": (jnp.int32, 64, False, 10, stragglers),
    "ring_overrun": (jnp.int32, 64, False, 11, overrun),
    "old_drops": (jnp.int32, 64, False, 10, old),
    "ring_not_a_power_of_two": (jnp.int32, 48, False, 12, None),
    "int8_extremes": (jnp.int8, 64, True, 10, None),
    "uint32_extremes": (jnp.uint32, 64, True, 10, None),
    "int32_extremes": (jnp.int32, 64, True, 10, None),
}


def branch_counts(state):
    return (int(state.fold_fallbacks), int(state.fold_partials),
            int(state.fold_spill_lanes))


@pytest.mark.parametrize("name", sorted(CASES))
def test_the_contraction_form_equals_the_four_scatters(name):
    dtype, slots, extremes, n_batches, mutate = CASES[name]
    op = make_op(dtype, slots)
    rng = np.random.default_rng(sorted(CASES).index(name))
    lag = np.zeros(K, np.int64)
    lag[list(LAGGING)] = rng.integers(LAG // 2, LAG + 1, len(LAGGING))
    state = op.init_state({"v": jax.ShapeDtypeStruct((), dtype)})
    new = jax.jit(op._insert)
    ref = jax.jit(lambda st, b: parent_insert(op, st, b))
    emit = jax.jit(lambda st: op._emit(st, op.max_wins, False))
    seen = []
    for j in range(n_batches):
        key, ts, v, valid = stream_batch(
            j, lag, rng, dtype, extremes,
            n_valid=C - 1000 if j == n_batches - 1 else C)
        if mutate is not None and j == 8:
            key, ts = mutate(j, key, ts, rng)
        batch = as_batch(key, ts, v, valid)
        got, want = new(state, batch), ref(state, batch)
        for leaf in LEAVES:
            a, b = np.asarray(getattr(got, leaf)), np.asarray(
                getattr(want, leaf))
            assert a.dtype == b.dtype and np.array_equal(a, b), (j, leaf)
        seen.append(branch_counts(got))
        state, _ = emit(got)
    assert op._fold_rides
    # every stream starts with a lagging key's ticks clamped at 0: a quarter
    # of a chunk 8 or more panes behind the rest, the whole batch's scatters
    assert seen[0][0] == 1
    # then, once every key's first window has closed, the fast branch, the
    # batch that moved stragglers back taking the partial branch
    steady = [tuple(np.subtract(b, a)) for a, b in zip(seen[5:], seen[6:])]
    if mutate is stragglers:
        assert steady[2] == (0, 1, 40 * C // 1024)
        steady.pop(2)
    if mutate is overrun:
        # the jumped lanes spill; key 0's later lanes fall behind its horizon
        assert int(state.ring_overruns) == 3
        assert int(state.dropped_old) > 0
        steady = steady[:2]
    if mutate is old:
        assert int(state.dropped_old) == 100
    assert steady and all(s == (0, 0, 0) for s in steady), seen
    # the horizons passed the ring: the rotation met a wrapped one
    assert int(np.max(np.asarray(state.next_win))) > slots


def scatter_shapes(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return sorted(eqn.outvars[0].aval.shape for eqn, _ in equations(jaxpr)
                  if eqn.primitive.name.startswith("scatter"))


@pytest.mark.parametrize("kind", ["float_lift", "maximum_combine",
                                  "odd_capacity"])
def test_what_cannot_ride_keeps_the_parents_program(kind):
    """A float lift, a combine other than ``jnp.add`` and a capacity of no
    whole chunks keep the scatters, equation for equation the parent's, and
    publish no fold counter."""
    n = 1000 if kind == "odd_capacity" else C
    dtype = jnp.float32 if kind == "float_lift" else jnp.int32
    op = make_op(dtype, combine=(jnp.maximum if kind == "maximum_combine"
                                 else jnp.add))
    state = op.init_state({"v": jax.ShapeDtypeStruct((), dtype)})
    rng = np.random.default_rng(3)
    key, ts, v, valid = stream_batch(0, np.zeros(K, np.int64), rng,
                                     np.int32)
    batch = as_batch(key[:n], ts[:n], v[:n].astype(dtype), valid[:n])
    new = jax.make_jaxpr(op._insert)(state, batch).jaxpr
    ref = jax.make_jaxpr(lambda s, b: parent_insert(op, s, b))(
        state, batch).jaxpr
    assert operations(equations(new)) == operations(equations(ref))
    assert not op._fold_rides
    shapes = scatter_shapes(op._insert, state, batch)
    assert shapes[0] == (K,) and set(shapes[1:]) == {(K * op.P,)}
    op.collect_stats(op._insert(state, batch))
    assert not {"ffat_fold_fallbacks", "ffat_fold_partials",
                "ffat_fold_spill_lanes"} & set(op.stage_counters())


def test_the_riding_form_scatters_only_in_its_fallbacks_and_publishes():
    """Over the lanes the riding insert scatters only in ``keyed_pane_fold``'s
    ``scatter`` branches and the overrun ``cond``; the watermark takes none;
    ``collect_stats`` publishes the three counters as the state holds them."""
    op = make_op()
    state = op.init_state({"v": jax.ShapeDtypeStruct((), jnp.int32)})
    rng = np.random.default_rng(5)
    lag = np.zeros(K, np.int64)
    lag[list(LAGGING)] = LAG
    batch = as_batch(*stream_batch(0, lag, rng, np.int32))
    jaxpr = jax.make_jaxpr(op._insert)(state, batch).jaxpr
    paths = [path for eqn, path in equations(jaxpr)
             if eqn.primitive.name.startswith("scatter")]
    assert paths and all("scatter" in p.split("/") or "overrun" in p.split("/")
                         for p in paths), paths
    assert sum("overrun" in p.split("/") for p in paths) == 1
    assert not any(eqn.primitive.name.startswith("scatter")
                   and "keys" in path.split("/")
                   for eqn, path in equations(jaxpr))
    state = jax.jit(op._insert)(state, batch)
    op.collect_stats(state)
    counters = op.stage_counters()
    assert (counters["ffat_fold_fallbacks"], counters["ffat_fold_partials"],
            counters["ffat_fold_spill_lanes"]) == branch_counts(state)
    assert counters["ffat_fold_fallbacks"] == 1       # the clamped start


def test_a_count_based_state_carries_no_fold_counters():
    op = Win_SeqFFAT(lambda t: t.data["v"], jnp.add,
                     spec=WindowSpec(64, 32, win_type_t.CB), num_keys=K)
    state = op.init_state({"v": jax.ShapeDtypeStruct((), jnp.int32)})
    assert state.fold_fallbacks is state.fold_partials is None
    assert state.fold_spill_lanes is state.ring_overruns is None
