"""Order-correctness of the FFAT pane-sharing engine with a NON-commutative
associative combine. The reference FlatFAT maintains prefix and suffix partials
precisely so that non-commutative combines associate in stream order
(wf/flatfat.hpp:80-133); here order is preserved because pane partials are folded
tuple by tuple in stream order, the ring's partial on the left, and a window's panes
are reduced by ``ops/segment.py::ordered_reduce`` (neighbours paired, the older on
the left), with the combine called on whole structs.

Every arm of the engine is run: the global-time path (time-based), the per-key
time-based path and count-based windows, at 2, 3, 4, 5 and 64 panes a window (a
window reduction that pairs pane ``i`` with pane ``i + n // 2`` is right at 2 and
3 and wrong from 4 on)."""

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import win_type_t
from windflow_tpu.batch import Batch
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.operators.win_seqffat import Win_SeqFFAT
from windflow_tpu.ops.segment import identity_like, ordered_reduce


def matmul2(a, b):
    """Associative, non-commutative: 2x2 matrix product along trailing dims."""
    return jnp.einsum("...ij,...jk->...ik", a, b)


def lift(t):
    # tuple value v -> [[1, v], [0, 1]] (shear matrices compose non-commutatively
    # only if mixed; use rotation-ish asymmetric form to expose ordering bugs)
    v = t.v
    one = jnp.ones_like(v)
    zero = jnp.zeros_like(v)
    return jnp.stack([jnp.stack([one, v]), jnp.stack([v * 0.5, one])])


def test_ffat_noncommutative_matches_sequential():
    total, K, L, S = 96, 2, 8, 4
    spec = WindowSpec(L, S, win_type_t.CB)
    op = Win_SeqFFAT(lift, matmul2, spec=spec,
                     identity=jnp.eye(2, dtype=jnp.float32), num_keys=K, name="mm")

    src = wf.Source(lambda i: {"v": (i % 5).astype(jnp.float32) * 0.1},
                    total=total, num_keys=K)
    got = {}

    def cb(view):
        if view is None:
            return
        for k, w, m in zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"])):
            got[(k, w)] = m

    wf.Pipeline(src, [op], wf.Sink(cb), batch_size=32).run()

    # sequential oracle
    per_key = {k: [] for k in range(K)}
    for i in range(total):
        per_key[i % K].append((i % 5) * 0.1)
    for k, vals in per_key.items():
        n = len(vals)
        hi = (n - 1) // S + 1
        for w in range(hi):
            content = vals[w * S: w * S + L]
            m = np.eye(2, dtype=np.float32)
            for v in content:
                m = m @ np.array([[1, v], [v * 0.5, 1]], np.float32)
            np.testing.assert_allclose(got[(k, w)], m, rtol=1e-4, atol=1e-5)


# ---- every arm, 2 to 64 panes a window -----------------------------------

K = 2
PANE = 4            # ticks a pane (time-based), tuples of a key (count-based)
BATCH = 128
#: arm -> (count-based, global time)
ARMS = {"global_time": (False, True), "per_key_time": (False, False),
        "count_based": (True, None)}
PANES = [2, 3, 4, 5, 64]


def wrapping_matmul2(a, b):
    """2x2 integer matrix product, wrapping at 32 bits: associative and
    exact in any order of evaluation, and not commutative."""
    return jnp.sum(a[..., :, :, None] * b[..., None, :, :], axis=-2)


def int_matrix(t):
    v = t.v
    return jnp.stack([jnp.stack([v + 1, v]), jnp.stack([jnp.ones_like(v), 2 - v])])


class Drawdown(NamedTuple):
    """The largest fall from a price to a later, lower one, with the
    highest and lowest prices it was read from."""
    hi: jnp.ndarray
    lo: jnp.ndarray
    dd: jnp.ndarray


def drawdown_lift(t):
    return Drawdown(t.v, t.v, jnp.zeros_like(t.v))


def drawdown_combine(a, b):
    """Associative; ``a`` is the earlier operand (``a.hi - b.lo``)."""
    return Drawdown(jnp.maximum(a.hi, b.hi), jnp.minimum(a.lo, b.lo),
                    jnp.maximum(jnp.maximum(a.dd, b.dd), a.hi - b.lo))


#: prices lie in [1, 2^24): 0 and 2^30 stand for minus and plus infinity
DRAWDOWN_IDENTITY = Drawdown(0, 1 << 30, 0)


def engine(arm, n_panes, lift_fn, combine, identity):
    cb, global_time = ARMS[arm]
    spec = WindowSpec(n_panes * PANE, PANE,
                      win_type_t.CB if cb else win_type_t.TB)
    return Win_SeqFFAT(lift_fn, combine, spec=spec, identity=identity,
                       num_keys=K, global_time=global_time, name=arm)


def stream(n_panes, values):
    """Keys round robin, one tick a tuple, a window and six slides of each
    key's tuples more than a window holds."""
    total = K * PANE * (n_panes + 6) * (1 if n_panes > 8 else 3)
    return np.arange(total) % K, np.arange(total), values[:total]


def run(ops, keys, ts, vals):
    """``ops`` behind a host source: {(key, window id): payload}."""
    src = wf.Source(lambda i: {"v": jnp.asarray(vals)[i]}, total=len(keys),
                    num_keys=K, key_fn=lambda i: jnp.asarray(keys)[i],
                    ts_fn=lambda i: jnp.asarray(ts)[i])
    got = {}

    def deliver(view):
        if view is None:
            return
        for k, w, m in zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"])):
            assert (k, w) not in got
            got[(k, w)] = m
    wf.Pipeline(src, ops, wf.Sink(deliver), batch_size=BATCH).run()
    return got


def windows(arm, n_panes, keys, ts, vals):
    """{(key, window id): the key's values in the window, in stream order}
    for every window that holds a tuple."""
    cb = ARMS[arm][0]
    L = n_panes * PANE
    out = {}
    for k in range(K):
        mine = keys == k
        pos = np.arange(mine.sum()) if cb else ts[mine]
        v = vals[mine]
        for w in range(int(pos.max()) // PANE + 1):
            inside = (pos >= w * PANE) & (pos < w * PANE + L)
            if inside.any():
                out[(k, w)] = v[inside]
    return out


def matrix_fold(content):
    """The content's lifts multiplied left to right, wrapping at 32 bits."""
    m = np.eye(2, dtype=np.int64)
    for v in content.astype(np.int64):
        m = (m @ np.array([[v + 1, v], [1, 2 - v]])) & 0xFFFFFFFF
    return m.astype(np.uint32).view(np.int32)


@pytest.mark.parametrize("n_panes", PANES)
@pytest.mark.parametrize("arm", list(ARMS))
def test_matrix_products_equal_a_sequential_fold(arm, n_panes):
    keys, ts, vals = stream(n_panes, np.random.default_rng(n_panes).integers(
        0, 8, 4096).astype(np.int32))
    op = engine(arm, n_panes, int_matrix, wrapping_matmul2,
                np.eye(2, dtype=np.int32))
    got = run([op], keys, ts, vals)
    want = windows(arm, n_panes, keys, ts, vals)
    assert set(got) == set(want)
    for cell, content in want.items():
        np.testing.assert_array_equal(got[cell], matrix_fold(content),
                                      err_msg=str(cell))
    # the ordered fold ran and said so; an additive engine publishes nothing
    assert op.stage_counters()["ffat_ordered_runs"] > 0


def drawdown(content):
    """Brute force: the largest ``p_i - p_j`` over ``i <= j``."""
    return max(int(np.max(content[:j + 1]) - content[j])
               for j in range(len(content)))


@pytest.mark.parametrize("n_panes", [4, 64])
@pytest.mark.parametrize("arm", list(ARMS))
def test_drawdown_struct_with_a_tuple_identity(arm, n_panes):
    """A combine whose third field reads the other two, over a lifted
    struct, with one identity a field, through ``Map`` to one int32."""
    rng = np.random.default_rng(100 + n_panes)
    walk = rng.integers(100_000, 10_000_000) + np.cumsum(
        rng.integers(-64, 65, 4096))
    keys, ts, vals = stream(n_panes, np.clip(walk, 1, (1 << 24) - 1)
                            .astype(np.int32))
    op = engine(arm, n_panes, drawdown_lift, drawdown_combine,
                DRAWDOWN_IDENTITY)
    got = run([op, wf.Map(lambda t: t.data.dd)], keys, ts, vals)
    want = windows(arm, n_panes, keys, ts, vals)
    assert set(got) == set(want)
    assert {c: int(v) for c, v in got.items()} == {
        c: drawdown(content) for c, content in want.items()}
    assert max(got.values()) > 0
    assert op.stage_counters()["ffat_ordered_runs"] > 0


def test_additive_engines_publish_no_ordered_runs():
    op = engine("global_time", 4, lambda t: t.v, jnp.add, 0)
    keys, ts, vals = stream(4, np.arange(4096, dtype=np.int32) % 7)
    run([op], keys, ts, vals)
    assert "ffat_ordered_runs" not in op.stage_counters()


@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 7, 8, 9, 64])
def test_ordered_reduce_keeps_operands_in_axis_order(n):
    """Along the middle axis of ``[3, n, ...]``: matrix products (one leaf)
    and drawdowns (a struct of three) equal the left-to-right fold."""
    vals = np.random.default_rng(n).integers(0, 8, (3, n)).astype(np.int32)
    v = jnp.asarray(vals)
    mats = jnp.stack([jnp.stack([v + 1, v], -1),
                      jnp.stack([jnp.ones_like(v), 2 - v], -1)], -2)
    got = ordered_reduce(wrapping_matmul2, mats, axis=1)
    assert got.shape == (3, 2, 2)
    for row, g in zip(vals, np.asarray(got)):
        np.testing.assert_array_equal(g, matrix_fold(row))
    p = v * 1000 + 1
    dd = ordered_reduce(drawdown_combine, Drawdown(p, p, jnp.zeros_like(p)),
                        axis=1)
    assert np.asarray(dd.dd).tolist() == [drawdown(r * 1000 + 1) for r in vals]


@pytest.mark.parametrize("combine", [jnp.maximum, jnp.minimum])
@pytest.mark.parametrize("arm", list(ARMS))
def test_max_and_min_reduce_leaf_by_leaf_outside_the_ordered_scope(arm,
                                                                   combine):
    """``jnp.maximum`` and ``jnp.minimum`` act leaf by leaf: the windows
    are right, no ``ordered`` scope is lowered and no ordered runs are
    counted; the drawdown's step, on the same arm, carries the scope."""
    big = np.iinfo(np.int32).max
    ident = -big if combine is jnp.maximum else big
    keys, ts, vals = stream(5, np.random.default_rng(5).integers(
        -1000, 1000, 4096).astype(np.int32))
    op = engine(arm, 5, lambda t: t.v, combine, ident)
    got = run([op], keys, ts, vals)
    want = windows(arm, 5, keys, ts, vals)
    fold = np.max if combine is jnp.maximum else np.min
    assert {c: int(v) for c, v in got.items()} == {
        c: int(fold(content)) for c, content in want.items()}
    assert "ffat_ordered_runs" not in op.stage_counters()

    def lowered(op):
        spec = {"v": jax.ShapeDtypeStruct((), jnp.int32)}
        batch = Batch.empty(BATCH, spec)
        return jax.jit(op.apply).lower(op.init_state(spec), batch).as_text(
            debug_info=True)
    assert "/ordered/" not in lowered(engine(arm, 5, lambda t: t.v, combine,
                                             ident))
    assert "/emit/reduce/ordered/" in lowered(engine(
        arm, 5, drawdown_lift, drawdown_combine, DRAWDOWN_IDENTITY))


@pytest.mark.parametrize("identity, want", [
    (0, Drawdown(0, 0, 0)),
    (Drawdown(1, 2, 3), Drawdown(1, 2, 3)),
    (Drawdown(0, np.int32(1 << 30), 0), Drawdown(0, 1 << 30, 0)),
])
def test_the_identity_is_a_pytree_prefix_of_the_lift(identity, want):
    lifted = Drawdown(*(jnp.zeros(3, jnp.int32),) * 3)
    got = identity_like(identity, lifted)
    assert jax.tree.structure(got) == jax.tree.structure(lifted)
    assert tuple(got) == tuple(want)


@pytest.mark.parametrize("identity", [(0, 1 << 30, 0), [0, 1 << 30, 0],
                                      Drawdown(0, (1, 2), 0),
                                      {"hi": 0, "lo": 1, "dd": 0}])
def test_an_identity_of_another_structure_is_refused(identity):
    lifted = Drawdown(*(jnp.zeros(3, jnp.int32),) * 3)
    with pytest.raises(ValueError, match="no pytree prefix"):
        identity_like(identity, lifted)
