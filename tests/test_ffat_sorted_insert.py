"""The count-based insert of ``Win_SeqFFAT`` folds a batch in the order one
sort makes (``ops/segment.py::segment_run_fold``) and writes per run. The
formulation it replaced — rank back in stream order, then one per-lane scatter
per table — is kept HERE as the reference: both run over the same consecutive
batches and must agree on every state leaf after every batch and on every
emitted batch, bit for bit where the arithmetic is exact."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu.basic import win_type_t
from windflow_tpu.batch import Batch, CTRL_DTYPE, TupleRef
from windflow_tpu.observability.names import STAGE_GAUGES
from windflow_tpu.operators.win_seqffat import Win_SeqFFAT, _b
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.ops.lookup import table_lookup
from windflow_tpu.ops.segment import (run_budget, segment_rank,
                                      segment_reduce, segment_run_fold)


def reference_insert(op, state, batch):
    """``Win_SeqFFAT._insert`` for count-based windows as it stood before the
    sorted-order fold: ``segment_rank`` returns to stream order, every table
    is one ``segment_reduce`` over the lanes."""
    K, P = op.num_keys, op.P
    valid = batch.valid
    rank = segment_rank(batch.key, valid)
    pos = table_lookup(state.count, batch.key) + rank
    pane = pos // op.pane_len
    slot = pane % P
    seg = jnp.where(valid, batch.key * P + slot, K * P)
    lifted = jax.vmap(op.lift)(
        TupleRef(key=batch.key, id=batch.id, ts=batch.ts, data=batch.payload))
    upd = segment_reduce(lifted, seg, valid, K * P,
                         combine=None if op.combine is jnp.add else op.combine,
                         identity=op.identity)
    cnt_upd = segment_reduce(valid.astype(CTRL_DTYPE), seg, valid, K * P)
    pane_id_upd = segment_reduce(pane, seg, valid, K * P,
                                 combine=jnp.maximum, identity=-1)
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

    counts_add = segment_reduce(valid.astype(CTRL_DTYPE), batch.key, valid, K)
    ts_max = segment_reduce(batch.ts, batch.key, valid, K,
                            combine=jnp.maximum, identity=-1)
    return dataclasses.replace(
        state,
        panes=jax.tree.map(fold, state.panes, upd),
        pane_count=(jnp.where(fresh, 0, state.pane_count)
                    + cnt_upd.reshape(K, P)),
        pane_of=new_pane_of,
        count=state.count + counts_add,
        wm=jnp.maximum(state.wm, ts_max),
        # a combine on whole structs counts the (key, pane) runs it folded
        ordered_runs=(None if state.ordered_runs is None else
                      state.ordered_runs + jnp.sum(touched.astype(CTRL_DTYPE))),
    )


def matmul2(a, b):
    """Associative, not commutative: 2x2 matrix product on trailing dims."""
    return jnp.einsum("...ij,...jk->...ik", a, b)


def shear(t):
    v = t.data["v"].astype(jnp.float32) * 0.01
    one = jnp.ones_like(v)
    return jnp.stack([jnp.stack([one, v]), jnp.stack([v * 0.5, one])])


def affine(p, q):
    """Associative, not commutative, exact on wrapping integers, with a true
    identity (1, 0): the maps x -> a*x + b under composition, p first."""
    return jnp.stack([p[..., 0] * q[..., 0],
                      p[..., 1] * q[..., 0] + q[..., 1]], axis=-1)


class Lifts:
    value = staticmethod(lambda t: t.data["v"])
    near_overflow = staticmethod(
        lambda t: t.data["v"] + jnp.int32(2**31 - 40))
    float_value = staticmethod(lambda t: t.data["v"].astype(jnp.float32) * 0.37)
    affine = staticmethod(lambda t: jnp.stack([t.data["v"] % 5 + 1, t.id]))
    pytree = staticmethod(lambda t: {
        "sum": t.data["v"],
        "vec": jnp.stack([t.data["v"], t.data["v"] * 2 + 1, t.ts]),
        "f": t.data["v"].astype(jnp.float32) * 0.25})


def keys_round_robin(rng, c, k, j):
    return (np.arange(c) + j * c) % k


def keys_one_hot(rng, c, k, j):
    return np.full(c, 3 % k)


def keys_zipf(rng, c, k, j):
    return np.minimum(rng.zipf(1.3, c) - 1, k - 1)


def valid_all(rng, c, j):
    return np.ones(c, bool)


def valid_holes(rng, c, j):
    return rng.random(c) < 0.7


def valid_holes_and_an_empty_batch(rng, c, j):
    return np.zeros(c, bool) if j == 2 else rng.random(c) < 0.6


@dataclasses.dataclass(frozen=True)
class Case:
    name: str
    keys: callable = keys_round_robin
    valid: callable = valid_all
    lift: callable = Lifts.value
    combine: callable = jnp.add
    identity: object = 0
    win: int = 32
    slide: int = 16
    K: int = 8
    C: int = 256
    batches: int = 6
    exact: bool = True
    pane_capacity: int = None


CASES = [
    Case("round_robin"),
    # one key takes the whole batch: C / pane_len + 2 runs at most, and the
    # 37 tuples of batch 0's head start every later batch mid-pane
    Case("one_hot_key", keys=keys_one_hot, K=8, C=240, win=32, slide=16),
    # more keys than C / pane_len: most runs are a key's only one
    Case("zipf_many_keys", keys=keys_zipf, K=96, C=256, win=64, slide=32),
    Case("invalid_lanes", keys=keys_zipf, valid=valid_holes, K=16),
    Case("all_invalid_batch", keys=keys_zipf,
         valid=valid_holes_and_an_empty_batch, K=16),
    # C = 250 over 8 keys: 31.25 tuples a key a batch, pane_len 16, so every
    # key's count starts every batch but the first inside a pane
    Case("count_mid_pane", C=250, K=8, win=32, slide=16),
    # a ring of 8 slots a key, 8 keys, 16 panes a key by the last batch
    Case("ring_wraps", C=256, K=8, win=32, slide=16, batches=10,
         pane_capacity=8, valid=valid_holes),
    Case("noncommutative_float", lift=shear, combine=matmul2,
         identity=np.eye(2, dtype=np.float32), keys=keys_zipf, K=5,
         exact=False),
    Case("noncommutative_exact", keys=keys_zipf, valid=valid_holes, K=7,
         lift=Lifts.affine, combine=affine,
         identity=np.array([1, 0], np.int32)),
    Case("float_add", lift=Lifts.float_value, keys=keys_zipf, K=12,
         identity=0.0, exact=False),
    Case("int32_near_overflow", lift=Lifts.near_overflow, keys=keys_zipf,
         valid=valid_holes, K=6),
    Case("pytree_2d_leaf", lift=Lifts.pytree, keys=keys_zipf,
         valid=valid_holes, K=9),
    Case("pane_len_1", win=5, slide=3, K=6, C=64, keys=keys_zipf,
         valid=valid_holes),
    Case("tumbling_long_pane", win=512, slide=512, K=4, C=256, batches=8),
]


def make_op(case):
    op = Win_SeqFFAT(case.lift, case.combine,
                     spec=WindowSpec(case.win, case.slide, win_type_t.CB),
                     identity=case.identity, num_keys=case.K,
                     pane_capacity=case.pane_capacity, name=case.name)
    op.bind_geometry(case.C)
    return op


def stream(case, seed=11):
    rng = np.random.default_rng(seed)
    nxt = np.zeros(case.K, np.int64)
    for j in range(case.batches):
        key = case.keys(rng, case.C, case.K, j).astype(np.int32)
        valid = case.valid(rng, case.C, j)
        ident = np.zeros(case.C, np.int32)
        for i in np.flatnonzero(valid):        # progressive id within the key
            ident[i] = nxt[key[i]]
            nxt[key[i]] += 1
        yield Batch.of(
            {"v": rng.integers(0, 97, case.C).astype(np.int32)}, key=key,
            id=ident, ts=rng.integers(0, 10_000, case.C).astype(np.int32),
            valid=valid)


def assert_same(got, want, exact, what):
    got_l, tree_g = jax.tree.flatten(got)
    want_l, tree_w = jax.tree.flatten(want)
    assert tree_g == tree_w, what
    for g, w in zip(got_l, want_l):
        assert g.dtype == w.dtype and g.shape == w.shape, what
        if exact or not jnp.issubdtype(g.dtype, jnp.floating):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w), what)
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=1e-5, err_msg=what)


def live_rows(out):
    ok = np.asarray(out.valid)
    return jax.tree.map(lambda a: np.asarray(a)[ok],
                        (out.key, out.id, out.ts, out.payload))


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_sorted_insert_equals_the_per_lane_scatters(case):
    op = make_op(case)
    W = op._resolve_w(case.C)
    new_step = jax.jit(op.apply)

    def old(state, batch):
        return op._emit(reference_insert(op, state, batch), W, flush=False)
    old_step = jax.jit(old)
    first = next(stream(case))
    s_new = s_old = op.init_state(jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), first.payload))
    emitted = 0
    for j, batch in enumerate(stream(case)):
        s_new, out_new = new_step(s_new, batch)
        s_old, out_old = old_step(s_old, batch)
        assert_same(s_new, s_old, case.exact, f"state after batch {j}")
        np.testing.assert_array_equal(np.asarray(out_new.valid),
                                      np.asarray(out_old.valid))
        assert_same(live_rows(out_new), live_rows(out_old), case.exact,
                    f"emitted by batch {j}")
        emitted += int(np.asarray(out_new.valid).sum())
    flush = jax.jit(lambda st: op._emit(st, W, flush=True))
    (_, flush_new), (_, flush_old) = flush(s_new), flush(s_old)
    assert_same(live_rows(flush_new), live_rows(flush_old), case.exact, "flush")
    assert emitted + int(np.asarray(flush_new.valid).sum()) > 0


def runs_in(case, batch, count):
    """(key, pane) groups the batch holds, counted lane by lane."""
    seen, groups = dict(enumerate(np.asarray(count).tolist())), set()
    for k, ok in zip(np.asarray(batch.key).tolist(),
                     np.asarray(batch.valid).tolist()):
        if ok:
            groups.add((k, seen[k] // np.gcd(case.win, case.slide)))
            seen[k] += 1
    return groups


@pytest.mark.parametrize("case", CASES, ids=lambda c: c.name)
def test_runs_never_exceed_the_budget(case):
    """The compiled step holds no such check: here, batch by batch, the
    (key, pane) groups counted lane by lane are exactly the live rows of
    ``segment_run_fold`` and fit ``run_budget``."""
    op = make_op(case)
    budget = run_budget(case.C, case.K, op.pane_len)
    assert budget <= case.C
    count = jnp.zeros((case.K,), jnp.int32)
    for batch in stream(case):
        runs = segment_run_fold([(batch.ts, jnp.maximum, -1)], batch.key,
                                batch.valid, case.K, count, op.pane_len)
        assert runs.live.shape == (budget,)
        want = runs_in(case, batch, count)
        assert len(want) <= budget
        live = np.asarray(runs.live)
        got = list(zip(np.asarray(runs.key)[live].tolist(),
                       np.asarray(runs.chunk)[live].tolist()))
        assert got == sorted(want)
        assert int(np.asarray(runs.length)[live].min(initial=1)) >= 1
        assert int(np.asarray(runs.length).sum()) == int(
            np.asarray(runs.key_count).sum()) == int(np.asarray(batch.valid).sum())
        count = count + runs.key_count


@pytest.mark.parametrize("c,k,run_len,want", [
    (1_048_576, 512, 512, 3_072),      # the kcb cell: 2,048 + 2 * 512
    (256, 8, 16, 32), (256, 96, 32, 200), (64, 6, 1, 64), (8, 100, 4, 8)])
def test_run_budget(c, k, run_len, want):
    assert run_budget(c, k, run_len) == want


def test_worst_case_fills_the_budget_exactly():
    """Every key present, each starting on the last position of a pane and
    ending on the first of another: n // L + 2 runs a key, the bound."""
    K, L, n = 3, 8, 18                                        # 18 = 1 + 8 + 8 + 1
    key = np.repeat(np.arange(K), n).astype(np.int32)
    runs = segment_run_fold([(jnp.ones((K * n,), jnp.int32), None, 0)],
                            jnp.asarray(key), jnp.ones((K * n,), bool), K,
                            jnp.full((K,), L - 1, jnp.int32), L)
    assert run_budget(K * n, K, L) == 12
    assert np.asarray(runs.live).all()
    np.testing.assert_array_equal(np.asarray(runs.folded[0]),
                                  np.tile([1, 8, 8, 1], K))
    np.testing.assert_array_equal(np.asarray(runs.chunk),
                                  np.tile([0, 1, 2, 3], K))


def test_keys_outside_the_table_are_dropped_like_invalid_lanes():
    key = jnp.asarray([0, 5, -1, 1, 0, 7, 1], jnp.int32)
    val = jnp.asarray([1, 10, 100, 2, 3, 1000, 4], jnp.int32)
    runs = segment_run_fold([(val, jnp.add, 0)], key, jnp.ones((7,), bool), 2,
                            jnp.zeros((2,), jnp.int32), 4)
    live = np.asarray(runs.live)
    assert np.asarray(runs.key)[live].tolist() == [0, 1]
    assert np.asarray(runs.folded[0])[live].tolist() == [4, 6]
    assert np.asarray(runs.key_count).tolist() == [2, 2]


def test_geometry_gauges_are_published_at_bind_geometry():
    op = make_op(CASES[0])
    want = {"ffat_run_budget": run_budget(256, 8, 16), "ffat_keys": 8,
            "ffat_pane_slots": op.P}
    assert op.stage_counters() == want
    assert set(want) <= set(STAGE_GAUGES)
    op.bind_geometry(1024)
    assert op.stage_counters()["ffat_run_budget"] == run_budget(1024, 8, 16)
    # the drop counter joins them, it does not replace them
    op.collect_stats(op.init_state({"v": jax.ShapeDtypeStruct((), jnp.int32)}))
    assert op.stage_counters() == {
        **want, "ffat_run_budget": run_budget(1024, 8, 16),
        "ffat_pane_slots": op.P, "old_drops": 0}
    tb = Win_SeqFFAT(Lifts.value, jnp.add, spec=WindowSpec(32, 16, win_type_t.TB),
                     num_keys=8, global_time=False)
    # a time-based spec has no run budget; its fired-window budget joins the
    # ring's sizes once max_wins= or the first apply has settled it (the
    # global-time path knows it from the ring: tests/test_kff_config.py)
    assert tb.stage_counters() == {"ffat_keys": 8, "ffat_pane_slots": tb.P}
