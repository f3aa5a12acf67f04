"""Unit tests for segmented ops and compaction — the device-side keyed-routing layer.

Oracle: plain numpy per-key loops (the reference checks result invariance against a
sequential run, src/graph_test/test_graph_1.cpp:77-87; same idea at the op level)."""

import inspect
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from windflow_tpu.ops import segment, compaction


def _random_batch(rng, c=257, k=7):
    keys = rng.integers(0, k, size=c).astype(np.int32)
    vals = rng.normal(size=c).astype(np.float32)
    valid = rng.random(c) < 0.8
    return keys, vals, valid


def test_segment_reduce_sum_matches_numpy():
    rng = np.random.default_rng(0)
    keys, vals, valid = _random_batch(rng)
    out = segment.segment_reduce(vals, jnp.asarray(keys), jnp.asarray(valid), 7)
    expect = np.zeros(7, np.float32)
    for k, v, ok in zip(keys, vals, valid):
        if ok:
            expect[k] += v
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_segment_reduce_custom_combine_max():
    rng = np.random.default_rng(1)
    keys, vals, valid = _random_batch(rng)
    out = segment.segment_reduce(vals, jnp.asarray(keys), jnp.asarray(valid), 7,
                                 combine=jnp.maximum, identity=-1e30)
    expect = np.full(7, -1e30, np.float32)
    for k, v, ok in zip(keys, vals, valid):
        if ok:
            expect[k] = max(expect[k], v)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-4, atol=1e-5)


def test_segment_prefix_scan_stream_order():
    rng = np.random.default_rng(2)
    keys, vals, valid = _random_batch(rng, c=101, k=5)
    out = segment.segment_prefix_scan(jnp.asarray(vals), jnp.asarray(keys),
                                      jnp.asarray(valid), jnp.add, 0)
    run = {}
    for i, (k, v, ok) in enumerate(zip(keys, vals, valid)):
        if ok:
            run[k] = run.get(k, 0.0) + v
            np.testing.assert_allclose(np.asarray(out)[i], run[k], rtol=1e-4, atol=1e-5)


def test_segment_prefix_scan_with_carry():
    rng = np.random.default_rng(3)
    keys, vals, valid = _random_batch(rng, c=64, k=4)
    carry = np.arange(4, dtype=np.float32) * 100
    out = segment.segment_prefix_scan(jnp.asarray(vals), jnp.asarray(keys),
                                      jnp.asarray(valid), jnp.add, 0,
                                      carry_in=jnp.asarray(carry))
    run = dict(enumerate(carry))
    for i, (k, v, ok) in enumerate(zip(keys, vals, valid)):
        if ok:
            run[k] = run[k] + v
            np.testing.assert_allclose(np.asarray(out)[i], run[k], rtol=1e-4, atol=1e-5)


def test_segment_rank():
    rng = np.random.default_rng(4)
    keys, _, valid = _random_batch(rng, c=50, k=3)
    rank = np.asarray(segment.segment_rank(jnp.asarray(keys), jnp.asarray(valid)))
    seen = {}
    for i, (k, ok) in enumerate(zip(keys, valid)):
        if ok:
            assert rank[i] == seen.get(k, 0)
            seen[k] = seen.get(k, 0) + 1


def test_scatter_compact():
    valid = jnp.asarray(np.array([1, 0, 1, 1, 0, 1], bool))
    vals = jnp.arange(6, dtype=jnp.float32)
    out, out_valid = compaction.scatter_compact(vals, valid)
    np.testing.assert_array_equal(np.asarray(out)[:4], [0, 2, 3, 5])
    np.testing.assert_array_equal(np.asarray(out_valid), [1, 1, 1, 1, 0, 0])


def test_partition_by_destination():
    dest = jnp.asarray(np.array([2, 0, 1, 0, 2, 2, 1], np.int32))
    valid = jnp.asarray(np.array([1, 1, 1, 1, 0, 1, 1], bool))
    vals = np.array([10, 20, 30, 40, 50, 60, 70], np.float32)
    idx, out_valid = compaction.partition_by_destination(dest, valid, 3, 4)
    got = np.asarray(jnp.take(jnp.asarray(vals), idx))
    ov = np.asarray(out_valid)
    assert sorted(got[0][ov[0]].tolist()) == [20, 40]
    assert sorted(got[1][ov[1]].tolist()) == [30, 70]
    assert sorted(got[2][ov[2]].tolist()) == [10, 60]


def test_compact_under_jit():
    @jax.jit
    def f(vals, valid):
        return compaction.scatter_compact(vals, valid)
    out, ov = f(jnp.arange(8, dtype=jnp.float32), jnp.arange(8) % 2 == 0)
    np.testing.assert_array_equal(np.asarray(out)[:4], [0, 2, 4, 6])


# ------------------------------------------ the owner of a listed row (PR 37)

def _zeros_between(K, every, n):
    counts = np.zeros(K, np.int32)
    counts[::every] = n
    return counts


#: (counts per key, budget): the shapes on both sides of the crossover
#: (``owner_compare_cells`` 0 from 131,072 keys on: the search stays)
OWNER_CASES = {
    "all_zero": (np.zeros(7, np.int32), 12),
    "zeros_between": (np.asarray([0, 3, 0, 0, 2, 1, 0], np.int32), 16),
    "total_above_the_budget": (np.asarray([5, 0, 9, 4], np.int32), 11),
    "total_is_the_budget": (np.asarray([5, 0, 9, 4], np.int32), 18),
    "empty_list_of_one_key": (np.zeros(1, np.int32), 5),
    "one_key_holds_every_row": (np.asarray([0, 0, 40, 0], np.int32), 24),
    "last_key_holds_every_row": (np.asarray([0, 0, 0, 24], np.int32), 24),
    "one_key": (np.asarray([6], np.int32), 9),
    "one_key_cut": (np.asarray([60], np.int32), 9),
    "kpf_fired_windows": (np.full(512, 17, np.int32), 8704),
    "kcb_runs": (_zeros_between(512, 3, 5), 3072),
    "ysb_wmr_fired_windows": (_zeros_between(100, 2, 3), 200),
    "short_of_the_crossover": (_zeros_between(1 << 16, 97, 2), 64),
    "past_the_crossover": (_zeros_between(1 << 17, 97, 2), 700),
    "past_the_crossover_cut": (_zeros_between(1 << 18, 5, 1), 64),
}


def _primitives(jaxpr):
    """Every equation's primitive, nested jaxprs included, in order."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _primitives(sub)


@pytest.mark.parametrize("case", sorted(OWNER_CASES))
def test_enumerate_runs_is_searchsorted_and_the_index_by_hand(case):
    counts, budget = OWNER_CASES[case]
    K = len(counts)
    n = jnp.asarray(counts)
    key, index, live = jax.jit(segment.enumerate_runs, static_argnums=1)(
        n, budget)
    # what the four sites wrote by hand until PR 37
    csum = jnp.cumsum(n)
    r = jnp.arange(budget, dtype=jnp.int32)
    k = jnp.minimum(jnp.searchsorted(csum, r, side="right"), K - 1)
    assert key.dtype == index.dtype == jnp.int32 and live.dtype == jnp.bool_
    assert np.array_equal(key, k)
    assert np.array_equal(index, r - jnp.take(csum - n, k))
    assert np.array_equal(live, r < csum[-1])
    # and a loop over the keys, for the live rows
    rows = [(k, i) for k, c in enumerate(counts) for i in range(c)][:budget]
    assert int(np.sum(live)) == len(rows)
    assert list(zip(np.asarray(key)[:len(rows)].tolist(),
                    np.asarray(index)[:len(rows)].tolist())) == rows
    # the form follows the shapes, and the gauge says which it was
    cells = segment.owner_compare_cells(budget, K)
    assert cells == (budget * K if K < 1 << 17 else 0)
    loops = [name for name in _primitives(jax.make_jaxpr(
        lambda n: segment.enumerate_runs(n, budget))(n).jaxpr)
        if name in ("while", "scan", "gather")]
    # (a loop of a fixed number of rounds traces as ``scan``, and XLA runs it
    # as a ``while``: one gather a round, and the take after it)
    assert loops == ([] if cells else ["scan", "gather", "gather"])


def test_the_crossover_is_where_the_two_prices_meet():
    """A row pays K cells or ``K.bit_length() + 1`` serialized rounds,
    whatever the budget: the form depends on the key space alone."""
    price = lambda K: (K * segment.COMPARE_CELL_NS,
                       (K.bit_length() + 1) * segment.SEARCH_ROUND_NS)
    for K in (1, 100, 512, 1024, 4096, 1 << 16):
        assert price(K)[0] <= price(K)[1]
        assert segment.owner_compare_cells(8704, K) == 8704 * K
    for K in (1 << 17, 1 << 18, 1 << 20):
        assert price(K)[0] > price(K)[1]
        assert segment.owner_compare_cells(8704, K) == 0
    assert segment.owner_compare_cells(0, 512) == 0


def test_the_four_sites_reach_the_one_owner_function(monkeypatch):
    """``enumerate_runs`` holds the three files' only ``side="right"``
    search, and ``Win_Seq``'s insert and emit, ``segment_run_fold`` and
    ``Win_SeqFFAT._emit`` all list their rows through it."""
    from windflow_tpu.basic import win_type_t
    from windflow_tpu.batch import Batch
    from windflow_tpu.operators import win_seq, win_seqffat
    from windflow_tpu.operators.window import WindowSpec

    sources = [inspect.getsource(m) for m in (segment, win_seq, win_seqffat)]
    assert sum(len(re.findall(r'side="right"', s)) for s in sources) == 2
    owner = inspect.getsource(segment.enumerate_runs)
    assert len(re.findall(r'side="right"', owner)) == 2   # docstring, call
    assert sum(s.count("searchsorted(") for s in sources) == (
        owner.count("searchsorted(") + 2)     # the two K + 1 key-edge searches

    calls = []
    real = segment.enumerate_runs

    def listed(n_runs, budget):
        calls.append((n_runs.shape[0], budget))
        return real(n_runs, budget)
    for mod in (segment, win_seq, win_seqffat):
        monkeypatch.setattr(mod, "enumerate_runs", listed)
    C, K = 64, 4
    batch = Batch.empty(C, {"v": jax.ShapeDtypeStruct((), jnp.int32)})
    spec = {"v": jax.ShapeDtypeStruct((), jnp.int32)}
    seq = win_seq.Win_Seq(lambda wid, it: it.sum("v"),
                          WindowSpec(8, 4, win_type_t.CB), num_keys=K)
    seq.bind_geometry(C)
    jax.make_jaxpr(seq.apply)(seq.init_state(spec), batch)
    assert calls == [(K, seq.run_rows), (K, seq._w)]
    del calls[:]
    ffat = win_seqffat.Win_SeqFFAT(lambda t: t.data["v"], jnp.add,
                                   spec=WindowSpec(8, 4, win_type_t.CB),
                                   num_keys=K)
    ffat.bind_geometry(C)
    jax.make_jaxpr(ffat.apply)(ffat.init_state(spec), batch)
    assert calls == [(K, segment.run_budget(C, K, 4)), (K, ffat._w)]
    assert ffat.stage_counters()["ffat_run_budget"] == calls[0][1]
