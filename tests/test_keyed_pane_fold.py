"""``ops/histogram.py::keyed_pane_fold``: the occupancy counts and an additive
fold of integers in one chunk-local one-hot contraction, bit for bit
``jax.ops.segment_sum`` and numpy's histogram whatever the batch holds,
with value leaves or none (the count histogram), and whichever of its three
branches the batch takes (fast, partial, whole: a numpy account of the
locality test says which, and how many lanes the partial branch scatters),
and the one call site, ``Win_SeqFFAT._g_insert``: which lifts ride the
contraction (the code sees it in the lift's result), which keep the scatter
beside the counts, and the counters of the batches that left the fast
branch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_histogram_lookup import tpu_default_dot  # noqa: F401 - a fixture
from windflow_tpu.basic import win_type_t
from windflow_tpu.batch import Batch
from windflow_tpu.observability.names import STAGE_COUNTERS
from windflow_tpu.ops.histogram import (DEFAULT_CHUNK, DEFAULT_L, FOLD_FAST,
                                        FOLD_PARTIAL, FOLD_WHOLE, K_TILE,
                                        SPILL_M, _place_group, keyed_pane_fold,
                                        pane_fold_applies)
from windflow_tpu.operators.win_patterns import Key_FFAT
from windflow_tpu.operators.window import WindowSpec


def in_order_panes(C, lanes_a_pane, first=0):
    return (np.arange(C) // lanes_a_pane + first).astype(np.int32)


def full_range(rng, dtype, C):
    info = np.iinfo(dtype)
    v = rng.integers(info.min, info.max, C, endpoint=True).astype(dtype)
    v[:4] = info.min, info.max, info.min, info.max
    return v


def one_cell(value):
    """Every lane of 131,072 in one (key, pane): a limb column sums to
    255 x 131,072 > 2^24 in one cell, past what one f32 placement dot holds
    (8,192 lanes would not reach it). ``value`` None: the top 2^20 values of
    int32 at random, so that the chunks' limb sums are odd numbers whose
    total f32 would round (equal values sum to multiples of 2^10, which f32
    holds far past 2^24: the placement in one dot passes those by luck)."""
    def make(rng):
        C = 131072
        assert 255 * C > 1 << 24 and _place_group(C // 1024, 1024) == 64
        values = (np.full(C, value) if value is not None
                  else rng.integers((1 << 31) - (1 << 20), 1 << 31, C))
        return dict(key=np.ones(C, np.int32), pane=np.full(C, 5, np.int32),
                    valid=np.ones(C, bool), values=values.astype(np.int32),
                    K=2, P=16)
    return make


def of_dtype(dtype, K=5, P=32, C=8192, live=0.7):
    def make(rng):
        return dict(key=rng.integers(0, K, C).astype(np.int32),
                    pane=in_order_panes(C, 200, first=P - 3),   # wraps the ring
                    valid=rng.random(C) < live,
                    values=full_range(rng, dtype, C), K=K, P=P)
    return make


def sums_that_wrap(rng):
    C = 8192
    return dict(key=rng.integers(0, 3, C).astype(np.int32),
                pane=in_order_panes(C, 4096),
                valid=np.ones(C, bool),
                values=rng.integers((1 << 31) - 1000, 1 << 31, C).astype(
                    np.int32), K=3, P=8)


def masked_lanes(rng):
    case = of_dtype(np.int32, live=0.3)(rng)
    case["valid"][1024:2048] = False            # a chunk with no live lane
    return case


def two_leaves(rng):
    case = of_dtype(np.int32)(rng)
    case["values"] = {"a": case["values"],
                      "b": full_range(rng, np.int16, 8192)}
    return case


def breaks_locality(rng):
    C = 4096
    return dict(key=rng.integers(0, 11, C).astype(np.int32),
                pane=rng.integers(0, 1000, C).astype(np.int32),
                valid=rng.random(C) < 0.5,
                values=full_range(rng, np.int32, C), K=11, P=64,
                branch=FOLD_WHOLE)


def branch_of(pane, valid, chunk=DEFAULT_CHUNK, L=DEFAULT_L, M=SPILL_M):
    """numpy: (branch, spilled) of ``keyed_pane_fold`` on these lanes. Fast
    where every valid lane lies fewer than ``L`` panes past its chunk's
    oldest; else partial, where no chunk has more than ``M`` valid lanes
    ``L`` or more panes behind its newest (those are scattered), else
    whole. Lanes that are not whole chunks take the whole branch."""
    if len(pane) % chunk or len(pane) < chunk:
        return FOLD_WHOLE, 0
    p = np.asarray(pane, np.int64).reshape(-1, chunk)
    v = np.asarray(valid, bool).reshape(-1, chunk)
    oldest = np.where(v, p, np.iinfo(np.int64).max).min(axis=1, keepdims=True)
    if not (v & (p - oldest >= L)).any():
        return FOLD_FAST, 0
    newest = np.where(v, p, np.iinfo(np.int64).min).max(axis=1, keepdims=True)
    behind = (v & (p <= newest - L)).sum(axis=1)
    return ((FOLD_WHOLE, 0) if (behind > M).any()
            else (FOLD_PARTIAL, int(behind.sum())))


def late_stream(dtype=np.int32, late=0.1, max_delay=3 * DEFAULT_L, K=13,
                P=64, C=16384, live=1.0, lanes_a_pane=256, values=None):
    """A tenth of the lanes (``late``) arrive up to ``max_delay`` panes late;
    the rest are in order, four panes a chunk, and the ring wraps."""
    def make(rng):
        lane = np.arange(C)
        d = np.where(rng.random(C) < late,
                     rng.integers(1, max_delay * lanes_a_pane, C), 0)
        pane = ((lane + 40 * lanes_a_pane - d) // lanes_a_pane).astype(
            np.int32)
        return dict(key=rng.integers(0, K, C).astype(np.int32), pane=pane,
                    valid=rng.random(C) < live,
                    values=(values(rng, C) if values is not None
                            else full_range(rng, dtype, C)),
                    K=K, P=P, branch=FOLD_PARTIAL)
    return make


def near_the_ends(sign):
    """Values within 1,000 of int32's top (or bottom): the sums wrap."""
    def values(rng, C):
        top = rng.integers((1 << 31) - 1000, 1 << 31, C)
        return (top if sign > 0 else -top - 1).astype(np.int32)
    return values


def spilling(n):
    """In order, but ``n`` lanes of chunk 3 (spread over it) lie ten panes
    behind the chunk's newest: partial up to ``SPILL_M``, whole past it."""
    def make(rng):
        case = of_dtype(np.int32, K=9, P=64, C=8192)(rng)
        lanes = 3 * DEFAULT_CHUNK + np.sort(
            rng.choice(DEFAULT_CHUNK, n, replace=False))
        case["pane"][lanes] = case["pane"][4 * DEFAULT_CHUNK - 1] - 10
        case["valid"][lanes] = True
        case["branch"] = FOLD_PARTIAL if n <= SPILL_M else FOLD_WHOLE
        return case
    return make


def far_future_outlier(rng):
    """One lane of chunk 2 lies 1,000 panes ahead: the chunk's window moves
    to it and every other lane of the chunk falls behind: whole."""
    case = of_dtype(np.int32, K=9, P=64, C=8192, live=1.0)(rng)
    case["pane"][2 * DEFAULT_CHUNK + 17] += 1000
    case["branch"] = FOLD_WHOLE
    return case


def behind_and_past_the_wrap(rng):
    """Stragglers behind each chunk's window and in-order lanes whose panes
    cross the ring's end (``pane % P`` wraps to 0) in the same chunks."""
    case = late_stream(K=6, P=16, C=8192, max_delay=20)(rng)
    case["pane"] = case["pane"] - 40 + 13                 # crosses 16, 32
    return case


def valueless(make):
    """The same lanes with no value leaf: the count histogram alone."""
    def made(rng):
        return dict(make(rng), values=())
    return made


def sorted_ts(C, K, P):
    """Non-decreasing panes, 157 lanes a pane: under the locality bound."""
    def make(rng):
        return dict(key=rng.integers(0, K, C).astype(np.int32),
                    pane=in_order_panes(C, 157, first=5),
                    valid=rng.random(C) < 0.7, values=(), K=K, P=P)
    return make


def empty_chunks(rng):
    C = 4096
    valid = np.zeros(C, bool)
    valid[2048:2100] = True                     # chunks 0, 1 and 3 dead
    return dict(key=np.zeros(C, np.int32), pane=np.zeros(C, np.int32),
                valid=valid, values=(), K=3, P=16)


def odd_capacity(rng):
    """1,000 lanes, not a whole chunk: the scatters, statically."""
    C = 1000
    return dict(key=rng.integers(0, 3, C).astype(np.int32),
                pane=rng.integers(0, 100, C).astype(np.int32),
                valid=rng.random(C) < 0.5, values=(), K=3, P=16,
                branch=FOLD_WHOLE)


def fuzzed_geometry(rng):
    """Random C, K and P; each chunk's panes a base that jumps ahead by up
    to three rings, and offsets under the locality bound: the fast branch
    over many wraps of the ring."""
    C = DEFAULT_CHUNK * int(rng.integers(2, 9))
    K, P = int(rng.integers(2, 300)), int(rng.integers(8, 4096))
    bases = np.cumsum(rng.integers(0, 3 * P, C // DEFAULT_CHUNK))
    pane = np.repeat(bases, DEFAULT_CHUNK) + rng.integers(0, DEFAULT_L, C)
    return dict(key=rng.integers(0, K, C).astype(np.int32),
                pane=pane.astype(np.int32), valid=rng.random(C) < rng.random(),
                values=(), K=K, P=P)


#: the count-only cases (``values=()``) are named ``valueless_*``, which
#: sorts after the others: the index in ``sorted(CASES)`` seeds a case, and
#: the others keep theirs
CASES = {
    "int32_full_domain": of_dtype(np.int32),
    "int32_sums_that_wrap": sums_that_wrap,
    "int8": of_dtype(np.int8),
    "int16": of_dtype(np.int16),
    "uint8": of_dtype(np.uint8),
    "uint16": of_dtype(np.uint16),
    "uint32": of_dtype(np.uint32),
    "masked_lanes": masked_lanes,
    "one_cell_at_int32_max": one_cell((1 << 31) - 1),
    "one_cell_at_int32_min": one_cell(-(1 << 31)),
    "one_cell_of_odd_sums": one_cell(None),
    "keys_above_the_tile": of_dtype(np.int32, K=K_TILE + 188, C=4096),
    "keys_below_the_tile": of_dtype(np.int32, K=7),
    "ring_smaller_than_locality": of_dtype(np.int32, P=4),
    "two_leaves": two_leaves,
    "breaks_locality": breaks_locality,
    "late_int32": late_stream(),
    "late_int8": late_stream(np.int8),
    "late_uint8": late_stream(np.uint8),
    "late_int16": late_stream(np.int16),
    "late_uint32": late_stream(np.uint32),
    "late_dead_lanes": late_stream(live=0.6),
    "late_near_int32_max": late_stream(values=near_the_ends(+1)),
    "late_near_int32_min": late_stream(values=near_the_ends(-1)),
    "late_keys_above_the_tile": late_stream(K=K_TILE + 188),
    "spills_exactly_m": spilling(SPILL_M),
    "spills_m_plus_1": spilling(SPILL_M + 1),
    "far_future_outlier": far_future_outlier,
    "behind_and_past_the_wrap": behind_and_past_the_wrap,
    "valueless_sorted_ts_4096_7_64": sorted_ts(4096, 7, 64),
    "valueless_sorted_ts_8192_100_256": sorted_ts(8192, 100, 256),
    "valueless_wraparound": valueless(of_dtype(np.int32, K=5, P=32,
                                               C=4096, live=1.0)),
    "valueless_empty_chunks": empty_chunks,
    "valueless_odd_capacity": odd_capacity,
    "valueless_ring_smaller_than_locality": valueless(of_dtype(np.int32,
                                                               P=4)),
    "valueless_keys_above_the_tile": valueless(
        of_dtype(np.int32, K=K_TILE + 188, C=4096)),
    "valueless_masked_lanes": valueless(masked_lanes),
    "valueless_breaks_locality": valueless(breaks_locality),
    "valueless_late": valueless(late_stream()),
    "valueless_late_dead_lanes": valueless(late_stream(live=0.6)),
    "valueless_spills_exactly_m": valueless(spilling(SPILL_M)),
    "valueless_spills_m_plus_1": valueless(spilling(SPILL_M + 1)),
    "valueless_far_future_outlier": valueless(far_future_outlier),
    "valueless_behind_and_past_the_wrap": valueless(behind_and_past_the_wrap),
    "valueless_one_cell": valueless(one_cell(1)),
    "valueless_fuzzed_geometry_0": fuzzed_geometry,
    "valueless_fuzzed_geometry_1": fuzzed_geometry,
    "valueless_fuzzed_geometry_2": fuzzed_geometry,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_fold_equals_segment_sum_bit_for_bit(tpu_default_dot, name):  # noqa: F811
    case = CASES[name](np.random.default_rng(sorted(CASES).index(name)))
    K, P = case["K"], case["P"]
    key, pane, valid = (jnp.asarray(case[f]) for f in ("key", "pane", "valid"))
    values = jax.tree.map(jnp.asarray, case["values"])
    assert pane_fold_applies(values) == bool(jax.tree.leaves(values))
    counts, folds, branch, spilled = jax.jit(
        lambda *a: keyed_pane_fold(*a, K, P))(key, pane, valid, values)
    assert (int(branch), int(spilled)) == branch_of(case["pane"],
                                                    case["valid"])
    assert int(branch) == case.get("branch", FOLD_FAST)
    seg = jnp.where(valid, key * P + pane % P, K * P)

    def want(v):
        return jax.ops.segment_sum(jnp.where(valid, v, 0), seg,
                                   num_segments=K * P).reshape(K, P)
    np.testing.assert_array_equal(counts, want(jnp.ones_like(key)))
    hist = np.zeros((K, P), np.int32)
    np.add.at(hist, (case["key"][case["valid"]],
                     case["pane"][case["valid"]] % P), 1)
    np.testing.assert_array_equal(counts, hist)
    assert counts.dtype == jnp.int32
    for got, v in zip(jax.tree.leaves(folds), jax.tree.leaves(values)):
        assert got.dtype == v.dtype
        np.testing.assert_array_equal(got, want(v))
    assert jax.tree.structure(folds) == jax.tree.structure(values)
    if name.startswith("one_cell_at"):
        assert int(folds[1, 5]) == (-131072 if name.endswith("max") else 0)
        assert int(counts[1, 5]) == 131072


# ---- the call site: Win_SeqFFAT._g_insert --------------------------------

C, K = 4096, 4


def engine(lift, combine=jnp.add, identity=0):
    op = Key_FFAT(lift, combine, identity=identity,
                  spec=WindowSpec(64, 16, win_type_t.TB), num_keys=K,
                  pane_capacity=512, max_wins=8)
    op.bind_geometry(C)
    return op


def batch_of(ts, capacity=C, seed=0):
    rng = np.random.default_rng(seed)
    return Batch.of(
        {"i": rng.integers(-(1 << 31), 1 << 31, capacity).astype(np.int32),
         "f": rng.standard_normal(capacity).astype(np.float32),
         "m": rng.integers(0, 9, (capacity, 3)).astype(np.int32)},
        key=rng.integers(0, K, capacity), id=np.arange(capacity),
        ts=np.asarray(ts)[:capacity])


def scatters(jaxpr, branch=None):
    """(primitive, index of the ``cond`` branch it lies in, None outside any)
    of every scatter equation, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name.startswith("scatter"):
            yield eqn.primitive.name, branch
        for name, param in eqn.params.items():
            for i, sub in enumerate(
                    param if isinstance(param, (list, tuple)) else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    inside = (i if eqn.primitive.name == "cond"
                              and name == "branches" and branch is None
                              else branch)
                    yield from scatters(sub, inside)


def pane_sums(batch, leaf, P, pane_len=16):
    """numpy: wrapping int32 sums per (key, pane % P)."""
    want = np.zeros((K, P), np.int64)
    np.add.at(want, (np.asarray(batch.key),
                     (np.asarray(batch.ts) // pane_len) % P),
              np.asarray(batch.payload[leaf]).astype(np.int64))
    return want.astype(np.int32)                    # wraps


def pane_counts(batch, P, pane_len=16):
    """numpy: the lanes per (key, pane % P)."""
    want = np.zeros((K, P), np.int32)
    np.add.at(want, (np.asarray(batch.key),
                     (np.asarray(batch.ts) // pane_len) % P), 1)
    return want


IN_ORDER = np.arange(C) // 16         # 256 lanes a pane, 4 panes a chunk
#: what ``collect_stats`` publishes of the branches the value fold took
FOLD_COUNTERS = ("ffat_fold_fallbacks", "ffat_fold_partials",
                 "ffat_fold_spill_lanes")


def test_an_integer_lift_rides_the_contraction_and_counts_its_fallbacks():
    op = engine(lambda t: t.data["i"])
    state = op.init_state(jax.eval_shape(
        lambda b: jax.tree.map(lambda x: x[0], b.payload), batch_of(IN_ORDER)))
    insert = jax.jit(op._g_insert)
    first = batch_of(IN_ORDER)
    # every scatter of the program lies in the outer cond's false side,
    # the fallbacks'
    found = list(scatters(jax.make_jaxpr(op._g_insert)(state, first).jaxpr))
    assert found and all(branch == 0 for _, branch in found), found
    state = insert(state, first)
    np.testing.assert_array_equal(state.panes, pane_sums(first, "i", op.P))
    assert int(state.cnt.sum()) == C
    op.collect_stats(state)
    assert {k: op.stage_counters()[k] for k in FOLD_COUNTERS} == dict.fromkeys(
        FOLD_COUNTERS, 0)
    assert op.stage_counters()["ffat_ring_overruns"] == 0
    assert set(FOLD_COUNTERS) <= set(STAGE_COUNTERS)
    # a few stragglers 9 to 12 panes back: the locality test fails, the
    # lanes near each chunk's newest pane stay in the contraction and the
    # stragglers alone are scattered; the answer is the same
    late = IN_ORDER + 16 * 16
    behind = np.random.default_rng(5).choice(C, 40, replace=False)
    late[behind] -= 16 * np.random.default_rng(6).integers(9, 13, 40)
    stragglers = batch_of(late, seed=2)
    state = insert(state, stragglers)
    # ticks shuffled over 16 panes: a chunk spills past what the partial
    # branch scatters, the whole batch takes the scatters
    shuffled = batch_of(np.random.default_rng(7).permutation(IN_ORDER), seed=1)
    state = insert(state, shuffled)
    np.testing.assert_array_equal(
        state.panes,
        pane_sums(first, "i", op.P) + pane_sums(stragglers, "i", op.P)
        + pane_sums(shuffled, "i", op.P))
    assert int(state.cnt.sum()) == 3 * C
    op.collect_stats(state)
    assert {k: op.stage_counters()[k] for k in FOLD_COUNTERS} == {
        "ffat_fold_fallbacks": 1, "ffat_fold_partials": 1,
        "ffat_fold_spill_lanes": 40}
    assert branch_of(late // 16, np.ones(C, bool)) == (FOLD_PARTIAL, 40)


@pytest.mark.parametrize("name,lift,combine,identity,capacity", [
    ("float_lift", lambda t: t.data["f"], jnp.add, 0.0, C),
    ("maximum_combine", lambda t: t.data["i"], jnp.maximum,
     -(1 << 31), C),
    ("rank_2_leaf", lambda t: t.data["m"], jnp.add, 0, C),
    ("odd_capacity", lambda t: t.data["i"], jnp.add, 0, C - 96),
])
def test_other_lifts_and_combines_keep_the_scatter_path(name, lift, combine,
                                                        identity, capacity):
    op = engine(lift, combine, identity)
    batch = batch_of(IN_ORDER, capacity)
    state = op.init_state(jax.eval_shape(
        lambda b: jax.tree.map(lambda x: x[0], b.payload), batch))
    found = list(scatters(jax.make_jaxpr(op._g_insert)(state, batch).jaxpr))
    # the value fold's scatter stands outside any cond, as before
    assert any(branch is None for _, branch in found), found
    state = jax.jit(op._g_insert)(state, batch)
    np.testing.assert_array_equal(state.cnt, pane_counts(batch, op.P))
    op.collect_stats(state)
    counters = op.stage_counters()
    # the counts took keyed_pane_fold, and its counters say which branch:
    # the fast one, or (odd capacity) the whole batch's scatters statically
    assert {k: counters[k] for k in FOLD_COUNTERS} == {
        "ffat_fold_fallbacks": int(name == "odd_capacity"),
        "ffat_fold_partials": 0, "ffat_fold_spill_lanes": 0}
    assert counters["ffat_ring_overruns"] == 0
    if name == "odd_capacity":
        np.testing.assert_array_equal(state.panes,
                                      pane_sums(batch, "i", op.P))


def test_a_count_lift_passes_the_new_leaf_through():
    """``ysb``'s branch: the overrun counter leaves ``_g_insert`` as the
    variable that went in (no operation: nothing is folded from the lift) and
    is not published; the counts take ``keyed_pane_fold`` with no value
    leaf, whose three counters say which branch it took."""
    op = engine(lambda t: 1)
    batch = batch_of(IN_ORDER)
    state = op.init_state(jax.eval_shape(
        lambda b: jax.tree.map(lambda x: x[0], b.payload), batch))
    jaxpr = jax.make_jaxpr(op._g_insert)(state, batch).jaxpr
    fields = [f.name for f in dataclasses.fields(state)
              if getattr(state, f.name) is not None]
    assert len(fields) == len(jax.tree.leaves(state))     # a leaf a field
    came_in = dict(zip(fields, jaxpr.invars))
    went_out = dict(zip(fields, jaxpr.outvars))
    assert went_out["ring_overruns"] is came_in["ring_overruns"]
    for leaf in ("fold_fallbacks", "fold_partials", "fold_spill_lanes",
                 "cnt"):
        assert went_out[leaf] is not came_in[leaf], leaf
    # stragglers a chunk: the counts' partial branch
    late = IN_ORDER + 16 * 16
    late[::64] -= 16 * 10
    stragglers = batch_of(late)
    state = jax.jit(op._g_insert)(state, stragglers)
    np.testing.assert_array_equal(state.cnt, pane_counts(stragglers, op.P))
    np.testing.assert_array_equal(state.panes, state.cnt)
    op.collect_stats(state)
    assert op.count_lift is True
    counters = op.stage_counters()
    assert "ffat_ring_overruns" not in counters
    assert {k: counters[k] for k in FOLD_COUNTERS} == {
        "ffat_fold_fallbacks": 0, "ffat_fold_partials": 1,
        "ffat_fold_spill_lanes": C // 64}
