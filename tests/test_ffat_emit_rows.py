"""``Win_SeqFFAT._emit``'s two ways of reading a fired window's panes: whole
``[P]`` ring rows of its key, masked by the window's pane range, or its
``wpanes`` slots one element each. On random rings (never-written slots,
slots that hold panes already fired, a window whose panes wrap across slot 0,
list rows past the due windows, the EOS flush's list) both give the same
``Batch`` leaf for leaf and what a numpy fold of the ring gives; the rule
picks rows from the shapes and the combine alone."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu.operators.win_seqffat as engine
from windflow_tpu.basic import win_type_t
from windflow_tpu.operators.win_seqffat import Win_SeqFFAT
from windflow_tpu.operators.window import WindowSpec

CASES = {
    # name: (spec, keys, ring slots, fired-window budget, combine, rows by
    # the rule)
    # kff_lag's window at rehearsal size: 64 panes a window, 256 slots, the
    # list of 65 windows a key for 8 keys
    "kff_lag": (WindowSpec(16384, 256, win_type_t.TB), 8, 256, 8 * 65,
                jnp.add, True),
    # kcb's window and ring at its cell's size: 2 panes a window of 4,096
    # slots, where a row costs more than two element takes
    "kcb": (WindowSpec(1024, 512), 4, 4096, 64, jnp.add, False),
    # a combine that is not an add keeps its panes in pane order
    "maximum": (WindowSpec(16384, 256, win_type_t.TB), 8, 256, 8 * 65,
                jnp.maximum, False),
}


def make_op(name):
    spec, K, slots, W, combine, _ = CASES[name]
    op = Win_SeqFFAT(lambda t: t.v, combine, spec=spec, num_keys=K,
                     pane_capacity=slots, max_wins=W, global_time=False,
                     identity=np.iinfo(np.int32).min
                     if combine is jnp.maximum else 0)
    return op


def random_state(op, seed):
    """A ring a key: each slot -1 (never written), the id of a pane that has
    fired, or of one at or past the key's first unfired pane, as ``p % P``
    places it; key 0's first unfired window starts two panes before the ring
    wraps to slot 0. A key's due windows: fewer than the budget's share, so
    the step's list ends before the budget (the EOS flush's of the time-based
    windows runs past it); key ``K - 1`` has had no tuple."""
    rng = np.random.default_rng(seed)
    K, P, s = op.num_keys, op.P, op.spec
    st = op.init_state({"v": jax.ShapeDtypeStruct((), jnp.int32)})
    lo = rng.integers(0, 40, K)
    lo[0] = (P - 2) // op.spanes
    n_due = rng.integers(0, op.max_wins // K, K)
    n_due[0] = max(n_due[0], 1)                 # key 0 fires across the wrap
    # the clock a key needs for ``n_due`` windows to be due past ``lo``
    clock = (lo + n_due - 1) * s.slide + s.win_len + rng.integers(0, s.slide, K)
    first = lo * op.spanes
    live = first[:, None] + (np.arange(P)[None, :] - first[:, None]) % P
    pick = rng.random((K, P))
    pane_of = np.where(pick < 0.15, -1, np.where(pick < 0.35, live - P, live))
    pane_of = np.where(pane_of < -1, -1, pane_of)
    panes = rng.integers(-50, 51, (K, P))
    count = np.where(np.arange(K) == K - 1, 0, clock if s.is_cb else 1)
    return dataclasses.replace(
        st, panes=jnp.asarray(panes, jnp.int32),
        pane_of=jnp.asarray(pane_of, jnp.int32),
        count=jnp.asarray(count, jnp.int32),
        wm=jnp.asarray(np.where(count > 0, clock, -1), jnp.int32),
        next_win=jnp.asarray(lo, jnp.int32))


def emit(op, state, flush, row_lane_ns, monkeypatch):
    monkeypatch.setattr(engine, "ROW_LANE_NS", row_lane_ns)
    rows = op._emit_reads_rows()
    W = op.max_wins
    new, out = jax.jit(lambda st: op._emit(st, W, flush))(state)
    return rows, jax.tree.map(np.asarray, (new, out))


def numpy_fold(op, state, out):
    """Each listed window's result: the combine over the slots whose pane id
    lies in its range, in pane order."""
    combine = np.add if op.combine is jnp.add else np.maximum
    pane_of, panes = np.asarray(state.pane_of), np.asarray(state.panes)
    want = np.full(out.valid.shape, op.identity, np.int64)
    for r in np.flatnonzero(out.valid):
        k, p0 = out.key[r], out.id[r] * op.spanes
        for p in range(p0, p0 + op.wpanes):
            if pane_of[k, p % op.P] == p:
                want[r] = combine(want[r], panes[k, p % op.P])
    return want


@pytest.mark.parametrize("flush", [False, True], ids=["step", "eos_flush"])
@pytest.mark.parametrize("name", sorted(CASES))
def test_rows_and_element_takes_give_the_same_batch(name, flush,
                                                    monkeypatch):
    op = make_op(name)
    *_, by_rule = CASES[name]
    # the rule at the measured prices, and the gauge it publishes
    assert op._emit_reads_rows() == by_rule
    assert op.stage_counters()["ffat_emit_row_lanes"] == (
        op.max_wins * op.P if by_rule else 0)
    state = random_state(op, sorted(CASES).index(name) + 11 * flush)
    rows, (new_e, out_e) = emit(op, state, flush, float("inf"), monkeypatch)
    assert not rows
    forced, (new_r, out_r) = emit(op, state, flush, 0.0, monkeypatch)
    # rows are taken under jnp.add alone, whatever they cost
    assert forced == (op.combine is jnp.add)
    for a, b in zip(jax.tree.leaves((new_e, out_e)),
                    jax.tree.leaves((new_r, out_r))):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    valid = out_e.valid
    assert valid.any()
    # rows past the list, or (the time-based EOS flush) a list past the budget
    assert valid.all() == (flush and not op.spec.is_cb)
    # every wrap, fired id and empty slot handled as the fold has them
    assert np.array_equal(out_e.payload[valid],
                          numpy_fold(op, state, out_e)[valid])
