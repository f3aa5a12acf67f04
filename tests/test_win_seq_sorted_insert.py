"""``Win_Seq._insert`` archives a batch in the order one stable sort makes and
moves the rings as rows (``ops/segment.py``: ``sort_segments``,
``enumerate_runs``, ``take_windows``), each row's window of the sorted columns
taken once for all the columns that share a buffer, the rows as long as a
slice's price buys (``_row_geometry``). The formulation it replaced — rank back
in stream order, one per-lane scatter per table, two per-lane reductions per
key — is kept HERE as the reference: both run over the same consecutive
batches and must agree bit for bit on every state leaf after every batch and
on every emitted batch."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_ysb_wmr_config import equations
from windflow_tpu.basic import win_type_t
from windflow_tpu.batch import Batch, CTRL_DTYPE
from windflow_tpu.observability.names import STAGE_COUNTERS, STAGE_GAUGES
from windflow_tpu.operators import win_seq
from windflow_tpu.operators.win_seq import Win_Seq
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.ops.lookup import table_lookup
from windflow_tpu.ops.segment import (SLICE_GBPS, SLICE_US, enumerate_runs,
                                      segment_rank, segment_reduce,
                                      sort_segments, take_windows,
                                      window_groups)

#: the leaves the per-lane form had (``runs_written`` came with the rows)
LEAVES = ("arch_payload", "arch_id", "arch_ts", "arch_pos", "count", "wm",
          "next_win", "overwrites", "dropped_old")


def reference_insert(op, state, batch):
    """``Win_Seq._insert`` as it stood before the sorted-order rows."""
    K, A = op.num_keys, op.A
    valid = batch.valid
    dropped_old = state.dropped_old
    if not op.spec.is_cb:
        horizon = table_lookup(state.next_win, batch.key) * op.spec.slide
        fresh = valid & (batch.ts >= horizon)
        dropped_old = dropped_old + jnp.sum(valid & ~fresh, dtype=CTRL_DTYPE)
        valid = fresh
    rank = segment_rank(batch.key, valid)
    pos = table_lookup(state.count, batch.key) + rank
    slot = pos % A
    flat = jnp.where(valid, batch.key * A + slot, K * A)       # OOB -> dropped

    def scat(tbl, v):
        return tbl.reshape((K * A,) + tbl.shape[2:]).at[flat].set(
            v, mode="drop").reshape(tbl.shape)

    counts_add = segment_reduce(valid.astype(CTRL_DTYPE), batch.key, valid, K)
    ts_max = segment_reduce(batch.ts, batch.key, valid, K,
                            combine=jnp.maximum, identity=-1)
    count = state.count + counts_add
    stamp = state.arch_pos if op.spec.is_cb else state.arch_ts
    lost = ((state.arch_pos >= 0)
            & (stamp >= (state.next_win * op.spec.slide)[:, None])
            & (state.arch_pos < (count - A)[:, None]))
    overwrites = (state.overwrites + jnp.sum(lost, dtype=CTRL_DTYPE)
                  + jnp.sum(jnp.maximum(counts_add - A, 0)))
    return dataclasses.replace(
        state,
        arch_payload=jax.tree.map(scat, state.arch_payload, batch.payload),
        arch_id=scat(state.arch_id, batch.id),
        arch_ts=scat(state.arch_ts, batch.ts),
        arch_pos=scat(state.arch_pos, pos),
        count=count,
        wm=jnp.maximum(state.wm, ts_max),
        overwrites=overwrites,
        dropped_old=dropped_old,
    )


def keys_round_robin(rng, c, k, j):
    return (np.arange(c) + j * c) % k


def keys_one_hot(rng, c, k, j):
    return np.full(c, 3 % k)


def keys_uniform(rng, c, k, j):
    return rng.integers(0, k, c)


def keys_zipf(rng, c, k, j):
    return np.minimum(rng.zipf(1.3, c) - 1, k - 1)


def keys_beyond_the_table(rng, c, k, j):
    """A third of the lanes name a key at or past K (the per-lane form dropped
    those in its scatters; below 0 it wrapped into the last key's ring, which
    the rows do not reproduce: ``test_keys_outside_the_table...``)."""
    key = keys_zipf(rng, c, k, j)
    return np.where(rng.random(c) < 0.33, k + rng.integers(0, 5, c), key)


def valid_all(rng, c, j):
    return np.ones(c, bool)


def valid_holes(rng, c, j):
    return rng.random(c) < 0.7


def valid_a_third(rng, c, j):
    return rng.random(c) < 1 / 3


def valid_holes_and_an_empty_batch(rng, c, j):
    return np.zeros(c, bool) if j == 2 else rng.random(c) < 0.6


def ts_in_order(rng, c, j, span):
    return np.sort(rng.integers(j * span, (j + 1) * span, c))


def ts_with_stragglers(rng, c, j, span):
    """A fifth of a batch lags up to three batches behind: OLD once the
    windows there have fired."""
    ts = ts_in_order(rng, c, j, span)
    late = rng.random(c) < 0.2
    return np.where(late, np.maximum(ts - rng.integers(0, 3 * span, c), 0), ts)


def payload_scalar(rng, c):
    return {"v": rng.integers(0, 97, c).astype(np.int32)}


def payload_2d_leaf(rng, c):
    return {"v": rng.integers(0, 97, c).astype(np.int32),
            "m": rng.random((c, 3)).astype(np.float32)}


def payload_mixed_leaves(rng, c):
    """Two 32-bit columns of different dtypes (they share a buffer with id
    and ts, the floats as their bit patterns: a NaN's payload and the sign of
    a zero survive) and a rank-2 leaf (a gather of its own)."""
    f = rng.standard_normal(c).astype(np.float32)
    f[::7], f[3::11] = -0.0, np.float32(np.nan)
    f.view(np.uint32)[5::13] = 0x7FC12345            # a NaN with a payload
    return {"v": rng.integers(0, 97, c).astype(np.int32), "f": f,
            "m": rng.random((c, 3)).astype(np.float32)}


@dataclasses.dataclass(frozen=True)
class Case:
    name: str
    win_type: win_type_t = win_type_t.CB
    keys: callable = keys_round_robin
    valid: callable = valid_all
    ts: callable = ts_in_order
    payload: callable = payload_scalar
    win: int = 32
    slide: int = 16
    K: int = 8
    C: int = 256
    batches: int = 6
    capacity: int = None          # ring slots a key (archive_capacity / tb_capacity)
    max_wins: int = None
    want_T: int = None            # the row length bind_geometry must arrive at
    loses: bool = False           # the ring is too small on purpose
    bind: bool = True             # bind_geometry(C) before the first batch
    groups: int = 1               # take_windows gathers a pass
    slice_us: float = None        # SLICE_US for the case (None: the chip's)


CB, TB = win_type_t.CB, win_type_t.TB
#: at the chip's price of a slice these small batches would move as one or
#: two long rows a key; on a chip where a slice is nearly free (SLICE_US
#: 0.01) they are cut into many short ones, and every way a key's lanes can
#: lie across ring rows occurs
MANY_SHORT_ROWS = [
    Case("cb_round_robin", want_T=16),
    Case("tb_round_robin", TB, win=400, slide=200, capacity=256, want_T=16),
    # one key takes every batch: C / T + 2 rows at most; 240 lanes a batch
    # into rows of 8 from a count that is 0 only once
    Case("cb_one_hot_key", keys=keys_one_hot, C=240, K=8),
    Case("tb_one_hot_key", TB, keys=keys_one_hot, C=240, K=8, win=400,
         slide=200, capacity=1024),
    # K = 200 > C: most keys hold a lane or two, the rows are as many as the
    # lanes whatever their length, so they are one slot long
    Case("cb_zipf_many_keys", keys=keys_zipf, K=200, C=256, win=8, slide=4,
         want_T=1),
    Case("tb_zipf_many_keys", TB, keys=keys_zipf, K=200, C=256, win=300,
         slide=100, capacity=512, want_T=1),
    Case("cb_invalid_lanes", keys=keys_zipf, valid=valid_holes, K=16),
    Case("tb_invalid_lanes_and_an_empty_batch", TB, keys=keys_zipf,
         valid=valid_holes_and_an_empty_batch, K=16, win=400, slide=200,
         capacity=512),
    Case("cb_an_empty_batch", keys=keys_zipf,
         valid=valid_holes_and_an_empty_batch, K=16),
    Case("tb_old_tuples", TB, keys=keys_zipf, ts=ts_with_stragglers, K=6,
         win=300, slide=150, capacity=1024, batches=8),
    # C = 250 over 8 keys: 31.25 lanes a key a batch into rows of 16, so every
    # count but the first starts inside a row
    Case("cb_count_mid_row", C=250, K=8, want_T=16),
    # a ring of 64 slots a key filled 32 a batch: wraps its end every other
    # batch, and mid-row from the third
    Case("cb_ring_wraps", C=250, K=8, win=16, slide=8, capacity=64,
         batches=10, valid=valid_holes),
    Case("tb_ring_wraps", TB, C=250, K=8, win=500, slide=250, capacity=64,
         batches=10, valid=valid_holes),
    # 250 lanes of one key into a ring of 128, rows of 16: only the last 128
    # are written, 122 a batch count as overwritten, and they start and end
    # inside one ring row, which the head and the last body run share
    Case("cb_more_than_a_ring_in_one_batch", keys=keys_one_hot, C=250, K=8,
         win=16, slide=8, capacity=128, loses=True, want_T=16),
    Case("tb_more_than_a_ring_in_one_batch", TB, keys=keys_zipf, C=256, K=5,
         win=400, slide=200, capacity=32, loses=True, max_wins=64),
    Case("cb_2d_payload_leaf", keys=keys_zipf, valid=valid_holes, K=9,
         payload=payload_2d_leaf, groups=2),
    Case("tb_2d_payload_leaf", TB, keys=keys_zipf, valid=valid_holes, K=9,
         payload=payload_2d_leaf, win=400, slide=200, capacity=512, groups=2),
    Case("cb_keys_beyond_the_table", keys=keys_beyond_the_table, K=7),
    Case("tb_keys_beyond_the_table", TB, keys=keys_beyond_the_table, K=7,
         win=400, slide=200, capacity=512),
    # more keys than lanes: never more than a run a lane, rows of one slot
    Case("cb_more_keys_than_lanes", keys=keys_zipf, K=64, C=16, win=4,
         slide=2, batches=10, want_T=1),
    Case("tb_more_keys_than_lanes", TB, keys=keys_round_robin, K=64, C=16,
         win=40, slide=20, capacity=8, batches=10, max_wins=64, want_T=1),
    # the ring is shorter than the row length the batch alone would choose:
    # T = A = 4
    Case("tb_ring_smaller_than_a_row", TB, K=2, C=256, win=8, slide=8,
         capacity=4, loses=True, max_wins=64, want_T=4),
    Case("cb_ring_smaller_than_a_row", K=2, C=256, win=4, slide=4,
         capacity=8, loses=True, max_wins=96, want_T=8),
    # a batch four times the provisional geometry of the constructor (256
    # lanes: 12 rows of 32), all on one key: the rows follow the batch
    Case("tb_batch_larger_than_the_bound_geometry", TB, keys=keys_one_hot,
         K=4, C=1024, win=400, slide=200, capacity=2048, bind=False),
]
#: the shapes the rule meets at the chip's prices
AT_THE_CHIPS_PRICES = [
    # kpf's WLQ: 512 keys, rings of 64 slots, 8,704 pane results a batch, 17
    # a key: T = A, a key's ring is one row, and the head and the last chunk
    # are that same row whenever a key's count passes a multiple of 64
    Case("tb_wlq_shape_whole_rings", TB, K=512, C=8704, win=4000, slide=1000,
         capacity=64, max_wins=2048, batches=5, want_T=64),
    # kpf's PLQ (512 keys, rings of 4,096) and ysb_wmr's engine (100 keys,
    # rings of 8,192, a third of the lanes live) at a sixty-fourth and a
    # thirty-second of their batch
    Case("tb_plq_shape_reduced_batch", TB, K=512, C=16384, win=512, slide=512,
         capacity=4096, max_wins=1100, batches=4, want_T=128),
    Case("tb_ysb_wmr_shape_reduced_batch", TB, keys=keys_uniform,
         valid=valid_a_third, K=100, C=32768, win=2000, slide=2000,
         capacity=8192, max_wins=256, batches=4, want_T=512),
    # 250 lanes of one key into a ring that is one row of 64: the last 64 are
    # written, through the head and through one body chunk of the same row
    Case("cb_more_than_a_ring_of_one_key_whole_rings", keys=keys_one_hot,
         C=250, K=8, win=16, slide=8, capacity=64, loses=True, want_T=64),
    Case("tb_more_than_a_ring_of_one_key_whole_rings", TB, keys=keys_one_hot,
         C=250, K=8, win=60, slide=30, capacity=64, loses=True, max_wins=64,
         want_T=64),
    Case("tb_an_empty_batch_long_rows", TB, keys=keys_zipf,
         valid=valid_holes_and_an_empty_batch, K=16, win=400, slide=200,
         capacity=512, want_T=64),
    Case("cb_mixed_leaves_two_groups", keys=keys_zipf, valid=valid_holes, K=9,
         payload=payload_mixed_leaves, groups=2, want_T=128),
    Case("tb_mixed_leaves_two_groups", TB, keys=keys_zipf, valid=valid_holes,
         K=9, payload=payload_mixed_leaves, win=400, slide=200, capacity=512,
         groups=2, want_T=128),
]
CASES = ([dataclasses.replace(case, slice_us=0.01) for case in MANY_SHORT_ROWS]
         + AT_THE_CHIPS_PRICES)


def make_op(case, fn=None):
    cb = case.win_type == CB
    op = Win_Seq(fn or (lambda wid, it: it.sum("v")),
                 WindowSpec(case.win, case.slide, case.win_type),
                 num_keys=case.K, name=case.name, max_wins=case.max_wins,
                 archive_capacity=case.capacity if cb else None,
                 tb_capacity=None if cb else case.capacity)
    if case.bind:
        op.bind_geometry(case.C)
    return op


def stream(case, seed=11):
    rng = np.random.default_rng(seed)
    span = 2 * case.slide                       # event time a batch
    nxt = 0
    for j in range(case.batches):
        valid = case.valid(rng, case.C, j)
        yield Batch.of(
            case.payload(rng, case.C),
            key=case.keys(rng, case.C, case.K, j).astype(np.int32),
            id=(nxt + np.arange(case.C)).astype(np.int32),
            ts=case.ts(rng, case.C, j, span).astype(np.int32), valid=valid)
        nxt += case.C


def assert_same(got, want, what):
    """Bit for bit: floats are compared as the words they are."""
    got_l, tree_g = jax.tree.flatten(got)
    want_l, tree_w = jax.tree.flatten(want)
    assert tree_g == tree_w, what
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype and g.shape == w.shape, what
        g, w = np.asarray(g), np.asarray(w)
        if g.dtype.kind == "f":
            g, w = g.view(np.uint32), w.view(np.uint32)
        np.testing.assert_array_equal(g, w, what)


def live_rows(out):
    ok = np.asarray(out.valid)
    return jax.tree.map(lambda a: np.asarray(a)[ok],
                        (out.key, out.id, out.ts, out.payload))


def window_fn(wid, it):
    """Reads every archive table, so that an emission differs when any slot
    of a fired window does."""
    return {"n": it.size(), "v": it.sum("v"),
            "ids": jnp.sum(jnp.where(it.mask, it.ids, 0)),
            "ts": jnp.max(jnp.where(it.mask, it.ts, -1))}


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_sorted_insert_equals_the_per_lane_scatters(case, monkeypatch):
    if case.slice_us is not None:
        monkeypatch.setattr(win_seq, "SLICE_US", case.slice_us)
    op = make_op(case, window_fn)
    assert case.want_T in (None, op.run_len)
    assert op.A % op.run_len == 0
    W = op._resolve_w(case.C)
    new_step = jax.jit(op.apply)

    def old(state, batch):
        return op._emit(reference_insert(op, state, batch), W, flush=False)
    old_step = jax.jit(old)
    first = next(stream(case))
    s_new = s_old = op.init_state(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), first.payload))
    emitted = rows = 0
    for j, batch in enumerate(stream(case)):
        s_new, out_new = new_step(s_new, batch)
        s_old, out_old = old_step(s_old, batch)
        for leaf in LEAVES:
            assert_same(getattr(s_new, leaf), getattr(s_old, leaf),
                        f"{leaf} after batch {j}")
        assert_same((out_new.valid, live_rows(out_new)),
                    (out_old.valid, live_rows(out_old)), f"emitted by batch {j}")
        emitted += int(np.asarray(out_new.valid).sum())
        written = int(np.asarray(s_new.runs_written)) - rows
        rows += written
        assert written <= op.num_keys + op._row_geometry(case.C)[1]
    flush = jax.jit(lambda st: op._emit(st, W, flush=True))
    (_, flush_new), (_, flush_old) = flush(s_new), flush(s_old)
    assert_same(live_rows(flush_new), live_rows(flush_old), "flush")
    assert emitted + int(np.asarray(flush_new.valid).sum()) > 0
    assert (int(np.asarray(s_new.overwrites)) > 0) == case.loses
    op.collect_stats(s_new)
    assert op.stage_counters()["archive_run_groups"] == case.groups
    if case.ts is ts_with_stragglers:
        assert int(np.asarray(s_new.dropped_old)) > 0


def test_more_than_a_ring_of_one_key_keeps_the_latest_tuple_in_every_slot():
    """The slots after such a batch, spelled out: positions n - A .. n - 1 of
    the key, each in slot position % A, and an exact ``overwrites``."""
    case = Case("overflow", keys=keys_one_hot, C=240, K=4, capacity=128,
                batches=1)
    op = make_op(case)
    batch = next(stream(case))
    st = jax.jit(op._insert)(op.init_state(
        {"v": jax.ShapeDtypeStruct((), jnp.int32)}), batch)
    pos = np.arange(240 - 128, 240)
    np.testing.assert_array_equal(np.asarray(st.arch_pos)[3][pos % 128], pos)
    np.testing.assert_array_equal(np.asarray(st.arch_id)[3][pos % 128], pos)
    np.testing.assert_array_equal(
        np.asarray(st.arch_payload["v"])[3][pos % 128],
        np.asarray(batch.payload["v"])[pos])
    assert (np.asarray(st.arch_pos)[[0, 1, 2]] == -1).all()
    assert int(st.overwrites) == 240 - 128 and int(st.count[3]) == 240
    assert int(st.wm[3]) == int(np.asarray(batch.ts).max())


def test_keys_outside_the_table_are_dropped_like_invalid_lanes():
    """Below 0 too, where the per-lane scatter wrapped into the last key's
    ring: a lane is live when it is valid and its key is in ``[0, K)``."""
    op = Win_Seq(lambda wid, it: it.sum("v"), WindowSpec(4, 4, CB), num_keys=3)
    op.bind_geometry(8)
    key = np.asarray([0, -1, 2, 3, -5, 2, 7, 0], np.int32)
    batch = Batch.of({"v": np.arange(8, dtype=np.int32)}, key=key,
                     id=np.arange(8, dtype=np.int32),
                     ts=np.arange(8, dtype=np.int32), valid=np.ones(8, bool))
    st = jax.jit(op._insert)(op.init_state(
        {"v": jax.ShapeDtypeStruct((), jnp.int32)}), batch)
    assert np.asarray(st.count).tolist() == [2, 0, 2]
    assert np.asarray(st.wm).tolist() == [7, -1, 5]
    ids = np.asarray(st.arch_id)[np.asarray(st.arch_pos) >= 0]
    assert sorted(ids.tolist()) == [0, 2, 5, 7]


@pytest.mark.parametrize("c,k,a,want", [
    (1_048_576, 100, 8_192, 2_048),       # ysb_wmr: 712 rows of 2,048
    (1_048_576, 512, 4_096, 1_024),       # kpf's PLQ: 2,048 rows of 1,024
    (8_704, 512, 64, 64),                 # kpf's WLQ: 1,160 whole rings
    (1_048_576, 512, 2_097_152, 1_024), (256, 8, 512, 128), (16, 1024, 32, 1),
    (256, 2, 4, 4), (1, 1, 2, 1), (1, 1, 1, 1)])
def test_row_length_follows_the_shapes(c, k, a, want):
    """The row length is the cheapest for a batch's two passes at the chip's
    two prices, and the longer where two cost the same."""
    op = Win_Seq(lambda wid, it: it.sum("v"), WindowSpec(4, 4, TB), num_keys=k,
                 tb_capacity=a)
    op.bind_geometry(c)
    T = op.run_len
    assert T == want and a % T == 0

    def cost(t):
        rows = k + min(c, c // t + min(k, c))
        return rows * (SLICE_US + t * 16 / (SLICE_GBPS * 1e3))
    lengths = [1 << e for e in range(a.bit_length())]
    assert lengths[-1] == a and T in lengths
    assert all(cost(T) <= cost(t) for t in lengths)
    assert all(cost(T) < cost(t) for t in lengths if t > T)
    assert op.run_rows == min(c, c // T + min(k, c))
    assert op.stage_counters()["archive_run_rows"] == k + op.run_rows


@pytest.mark.parametrize("payload,columns,groups", [
    (payload_scalar, 3, 1), (payload_mixed_leaves, 5, 2)])
def test_a_pass_takes_each_row_window_once_for_all_the_columns(
        payload, columns, groups):
    """The traced ``_insert``: what reads the sorted, padded columns is
    ``archive_run_groups`` gathers a pass (two passes), one start a ring row
    each, not one gather a column; the buffers they read hold every column
    between them."""
    case = Case("takes", TB, keys=keys_zipf, K=16, C=256, win=400, slide=200,
                capacity=512, payload=payload)
    op = make_op(case)
    batch = next(stream(case))
    state = op.init_state(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), batch.payload))
    jaxpr = jax.make_jaxpr(op._insert)(state, batch).jaxpr
    T, rows = op.run_len, op.run_rows
    lanes = case.C + 2 * T
    takes = [eqn for eqn, _ in equations(jaxpr)
             if eqn.primitive.name == "gather"
             and eqn.invars[0].aval.shape[1:2] == (lanes,)]
    assert len(jax.tree.leaves((batch.payload, batch.id, batch.ts))) == columns
    assert op._budget_gauges()["archive_run_groups"] == groups
    assert len(takes) == 2 * groups
    # a start a row: the head pass has a row a key, the body pass the listed
    assert sorted(eqn.invars[1].aval.shape[0] for eqn in takes) == sorted(
        [case.K, rows] * groups)
    assert sum(eqn.invars[0].aval.shape[0] for eqn in takes) == 2 * columns


def test_sorted_order_primitives():
    key = jnp.asarray([2, 0, 9, 2, 1, 2, -1, 0], jnp.int32)
    ok = jnp.asarray([1, 1, 1, 1, 1, 0, 1, 1], bool)
    val = jnp.arange(8, dtype=jnp.int32) * 10
    (s_val,), first, n = sort_segments((val,), key, ok, 3)
    assert np.asarray(first).tolist() == [0, 2, 3]
    assert np.asarray(n).tolist() == [2, 1, 2]
    assert np.asarray(s_val)[:5].tolist() == [10, 70, 40, 0, 30]
    k, i, live = enumerate_runs(jnp.asarray([2, 0, 3], jnp.int32), 7)
    assert np.asarray(live).tolist() == [True] * 5 + [False] * 2
    assert np.asarray(k)[:5].tolist() == [0, 0, 2, 2, 2]
    assert np.asarray(i)[:5].tolist() == [0, 1, 0, 1, 2]
    win = take_windows(jnp.arange(10), jnp.asarray([0, 3, 7]), 3)
    assert np.asarray(win).tolist() == [[0, 1, 2], [3, 4, 5], [7, 8, 9]]
    # a pytree of columns: the 32-bit ones of rank 1 share a gather whatever
    # their dtype, the others ride with their like
    columns = {"i": jnp.arange(10), "f": -jnp.arange(10, dtype=jnp.float32),
               "u": jnp.arange(10, dtype=jnp.uint32), "b": jnp.arange(10) % 2 > 0,
               "m": jnp.arange(20).reshape(10, 2), "h": jnp.arange(10, dtype=jnp.int16)}
    leaves = jax.tree.leaves(columns)                 # b, f, h, i, m, u
    assert list(window_groups(leaves).values()) == [[0], [1, 3, 5], [2], [4]]
    wins = jax.jit(lambda c: take_windows(c, jnp.asarray([0, 3, 7]), 3))(columns)
    for name, column in columns.items():
        want = np.stack([np.asarray(column)[s:s + 3] for s in (0, 3, 7)])
        assert wins[name].dtype == column.dtype
        np.testing.assert_array_equal(np.asarray(wins[name]), want)


@pytest.mark.parametrize("c,block", [(100, 4), (1000, 8), (64, 64), (5000, 1024)])
def test_range_max_equals_a_loop(c, block, monkeypatch):
    """Three levels of blocks at (100, 4) and (1000, 8); ranges that are empty,
    one lane, inside one block, and the whole column."""
    from windflow_tpu.ops import segment
    monkeypatch.setattr(segment, "RANGE_BLOCK", block)
    rng = np.random.default_rng(c)
    values = rng.integers(-50, 1000, c).astype(np.int32)
    first = np.concatenate([[0, 0, c - 1, 3], rng.integers(0, c, 40)])
    n = np.concatenate([[c, 0, 1, 2], rng.integers(0, c, 40)])
    n = np.minimum(n, c - first)
    got = jax.jit(lambda v, f, m: segment.range_max(v, f, m, -77))(
        values, first.astype(np.int32), n.astype(np.int32))
    want = [values[f:f + m].max(initial=-77) for f, m in zip(first, n)]
    assert np.asarray(got).tolist() == want


def test_the_rows_are_counted(monkeypatch):
    """``archive_run_len`` and ``archive_run_rows`` at ``bind_geometry``,
    ``archive_run_groups`` once an insert has seen the payload,
    ``archive_runs_written`` after a run: 8 keys round-robin, 32 lanes a key
    a batch into rows of 16 from an aligned count, so 2 rows a key a batch."""
    case = CASES[0]
    monkeypatch.setattr(win_seq, "SLICE_US", case.slice_us)
    op = make_op(case)
    gauges = {"archive_slots": op.A, "archive_run_len": 16,
              "archive_run_rows": 8 + 256 // 16 + 8}
    assert op.stage_counters() == gauges
    assert set(gauges) | {"archive_run_groups"} <= set(STAGE_GAUGES)
    assert "archive_runs_written" in STAGE_COUNTERS
    step = jax.jit(op.apply)
    st = op.init_state({"v": jax.ShapeDtypeStruct((), jnp.int32)})
    for batch in stream(case):
        st, _ = step(st, batch)
    op.collect_stats(st)
    counters = op.stage_counters()
    assert counters["archive_runs_written"] == case.batches * 8 * 2
    assert counters["archive_run_len"] == 16
    # payload, id and ts in one gather a pass: 2 x 32 slices a batch, not 6 x
    assert counters["archive_run_groups"] == 1
    assert counters["archive_overwrites"] == 0
