"""Ordering_Node modes and the DETERMINISTIC broadcast+renumbering case.

Reference: ``wf/ordering_node.hpp:47-287`` (ID/TS/TS_RENUMBERING release,
renumbering at ``:218,257``) and the count-based-windows-after-shuffle rule at
``wf/pipegraph.hpp:1954-1957`` — a CB windowed operator downstream of a
DETERMINISTIC merge must see tuples in deterministic (ts) arrival order with
progressive ids, or the per-key window contents depend on merge scheduling.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import Mode, ordering_mode_t, win_type_t
from windflow_tpu.batch import Batch, CTRL_DTYPE
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.parallel.ordering import Ordering_Node
from windflow_tpu.runtime.pipegraph import PipeGraph


def mk_batch(ids, ts=None, vals=None):
    ids = np.asarray(ids, np.int32)
    ts = ids if ts is None else np.asarray(ts, np.int32)
    vals = ids.astype(np.float32) if vals is None else np.asarray(vals, np.float32)
    return Batch(key=jnp.zeros(len(ids), CTRL_DTYPE), id=jnp.asarray(ids),
                 ts=jnp.asarray(ts), payload={"v": jnp.asarray(vals)},
                 valid=jnp.ones(len(ids), bool))


def drain(node, pushes):
    """Push (channel, batch) pairs then flush; return the released id sequence."""
    out = []

    def take(b):
        if b is None:
            return
        v = np.asarray(b.valid)
        out.extend(np.asarray(b.id)[v].tolist())

    for ch, b in pushes:
        take(node.push(ch, b))
    take(node.flush())
    return out


def test_ordering_node_id_mode_low_watermark():
    node = Ordering_Node(2, ordering_mode_t.ID)
    rel = node.push(0, mk_batch([3, 1, 5]))
    assert rel is None or not bool(np.asarray(rel.valid).any())  # ch1 has no wm yet
    rel = node.push(1, mk_batch([2, 4]))
    # low watermark = min(max ids) = min(5, 4) = 4 -> ids <= 4 release, sorted
    got = np.asarray(rel.id)[np.asarray(rel.valid)].tolist()
    assert got == [1, 2, 3, 4]
    final = drain(node, [])
    assert final == [5]


def test_ordering_node_ts_mode_interleave():
    node = Ordering_Node(2, ordering_mode_t.TS)
    got = drain(node, [(0, mk_batch([0, 1], ts=[0, 20])),
                       (1, mk_batch([10, 11], ts=[10, 30])),
                       (0, mk_batch([2], ts=[40])),
                       (1, mk_batch([12], ts=[50]))])
    # ids in ts order: ts 0,10,20,30,40,50 -> ids 0,10,1,11,2,12
    assert got == [0, 10, 1, 11, 2, 12]


def test_ordering_node_ts_renumbering_progressive_ids():
    node = Ordering_Node(2, ordering_mode_t.TS_RENUMBERING)
    got = drain(node, [(0, mk_batch([100, 200], ts=[5, 15])),
                       (1, mk_batch([300, 400], ts=[10, 20]))])
    # renumbered: progressive ids 0..n-1 in ts order regardless of original ids
    assert got == [0, 1, 2, 3]


def test_ordering_node_equal_ts_ties_are_deterministic():
    # equal (ts, id) pairs on both channels: channel index is the final tiebreak,
    # so release order never depends on push interleaving
    def payload_seq(pushes):
        node = Ordering_Node(2, ordering_mode_t.TS)
        out = []
        for ch, b in pushes:
            r = node.push(ch, b)
            if r is not None:
                out.extend(np.asarray(r.payload["v"])[np.asarray(r.valid)].tolist())
        r = node.flush()
        if r is not None:
            out.extend(np.asarray(r.payload["v"])[np.asarray(r.valid)].tolist())
        return out

    b0 = mk_batch([0, 1], ts=[5, 5], vals=[10.0, 11.0])
    b1 = mk_batch([0, 1], ts=[5, 5], vals=[20.0, 21.0])
    a = payload_seq([(0, b0), (1, b1)])
    b = payload_seq([(1, b1), (0, b0)])
    assert a == b == [10.0, 20.0, 11.0, 21.0]   # (ts, id, channel) total order


def test_unbalanced_merge_releases_early_in_push_driver():
    """A short source exhausting must stop gating (and hoarding) the long one."""
    g = PipeGraph("unbal", batch_size=16, mode=Mode.DETERMINISTIC)
    sa = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=16, num_keys=1,
                   ts_fn=lambda i: i, name="short")
    sb = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=512, num_keys=1,
                   ts_fn=lambda i: i, name="long")
    pa, pb = g.add_source(sa), g.add_source(sb)
    m = pa.merge(pb)
    seen = []
    m.add(wf.Map(lambda t: {"v": t.v})).add_sink(
        wf.Sink(lambda v: v is not None and seen.extend(
            np.asarray(v["payload"]["v"]).tolist())))
    g.run()
    # all 528 tuples arrive; the Ordering_Node did not hold the long tail hostage
    assert len(seen) == 528
    node = m._ordering
    assert node is not None and node._pending is None


def test_ordering_node_channel_eos_unblocks():
    node = Ordering_Node(2, ordering_mode_t.TS)
    held = node.push(0, mk_batch([1, 2], ts=[1, 2]))          # ch1 silent: held
    assert held is None or not bool(np.asarray(held.valid).any())
    assert node.last_release_count == 0
    rel = node.close_channel(1)                               # ch1 EOS: stops gating
    got = np.asarray(rel.id)[np.asarray(rel.valid)].tolist()
    # ts=1 < ch0's watermark (2) releases; ts=2 == the watermark is a potential
    # tie (ch0 may still deliver more ts=2) and stays held until ch0 closes
    assert got == [1]
    rel2 = node.close_channel(0)
    got2 = np.asarray(rel2.id)[np.asarray(rel2.valid)].tolist()
    assert got2 == [2]


def test_long_stream_backlog_stays_bounded():
    """Soak: 200 alternating pushes through one Ordering_Node. The retained
    pool's capacity must stay bounded by ~2x the held-back backlog (pow2 trim),
    NOT grow with stream length — the memory guarantee that makes DETERMINISTIC
    mode usable on unbounded streams."""
    from windflow_tpu.parallel.ordering import Ordering_Node
    B = 1024
    node = Ordering_Node(2, ordering_mode_t.TS)
    released = 0
    max_cap = 0
    for i in range(200):
        ch = i % 2
        ids = np.arange(i * B, (i + 1) * B, dtype=np.int32)
        b = Batch(key=jnp.zeros(B, jnp.int32), id=jnp.asarray(ids),
                  ts=jnp.asarray(2 * ids + ch),
                  payload={"v": jnp.zeros(B, jnp.float32)},
                  valid=jnp.ones(B, bool))
        out = node.push(ch, b)
        if out is not None:
            released += node.last_release_count
        if node._pending is not None:
            max_cap = max(max_cap, node._pending.capacity)
    tail = node.flush()
    if tail is not None:
        released += node.last_release_count
    assert released == 200 * B                  # nothing lost
    # the two channels interleave tightly: backlog is ~1 batch; the pool must
    # never have grown beyond a few batches' pow2 envelope
    assert max_cap <= 8 * B, max_cap


K = 2


def run_cb(batch_size, swap=False, threaded=False):
    """CB windows downstream of a DETERMINISTIC merge (renumbering case)."""
    g = PipeGraph("det_cb", batch_size=batch_size, mode=Mode.DETERMINISTIC)
    sa = wf.Source(lambda i: {"v": (i % 5).astype(jnp.float32)}, total=100,
                   num_keys=K, ts_fn=lambda i: 2 * i, name="even_ts")
    sb = wf.Source(lambda i: {"v": (i % 7).astype(jnp.float32)}, total=100,
                   num_keys=K, ts_fn=lambda i: 2 * i + 1, name="odd_ts")
    pa, pb = g.add_source(sa), g.add_source(sb)
    m = pb.merge(pa) if swap else pa.merge(pb)
    out = []

    def cb(view):
        if view is None:
            return
        out.extend((int(k), int(w), round(float(r), 4)) for k, w, r in
                   zip(view["key"].tolist(), view["id"].tolist(),
                       np.asarray(view["payload"]).tolist()))

    m.add(wf.Win_Seq(lambda wid, it: it.sum("v"),
                     WindowSpec(10, 10, win_type_t.CB),
                     num_keys=K)).add_sink(wf.Sink(cb))
    g.run(threaded=threaded)
    return sorted(out)


def cb_oracle():
    """Per-key ts-ordered arrival stream chunked into CB windows of 10."""
    per_key = {k: [] for k in range(K)}
    rows = []
    for i in range(100):
        rows.append((2 * i, i % K, i % 5))
        rows.append((2 * i + 1, i % K, i % 7))
    for ts, k, v in sorted(rows):
        per_key[k].append(v)
    want = []
    for k, vs in per_key.items():
        for w in range(0, -(-len(vs) // 10)):
            want.append((k, w, round(float(sum(vs[10 * w:10 * w + 10])), 4)))
    return sorted(want)


@pytest.mark.parametrize("batch_size", [32, 77, 200])
def test_deterministic_cb_windows_after_merge(batch_size):
    assert run_cb(batch_size) == cb_oracle()


def test_deterministic_cb_invariant_operand_order_and_driver():
    base = run_cb(50)
    assert run_cb(50, swap=True) == base
    assert run_cb(50, threaded=True) == base
    assert run_cb(80, swap=True, threaded=True) == base


# ------------------------------------------------- sync-free counts readback


def _mk_ord_batch(ids, ts):
    return mk_batch(ids, ts=ts)


def test_ordering_async_readback_identical_to_settled():
    """Deferred counts settle (the async hot path) releases EXACTLY what an
    eagerly-settled node releases, over a randomized two-channel sweep."""
    from windflow_tpu.parallel.ordering import Ordering_Node, ordering_mode_t

    def run(eager, seed):
        rng = np.random.default_rng(seed)
        node = Ordering_Node(2, ordering_mode_t.TS)
        out = []
        t = [0, 0]
        for _ in range(12):
            ch = int(rng.integers(0, 2))
            n = int(rng.integers(1, 5))
            ts = sorted(int(t[ch] + x) for x in rng.integers(0, 9, n))
            t[ch] = ts[-1]
            rel = node.push(ch, _mk_ord_batch(list(range(n)), ts))
            if eager:
                node.settle()         # the seed behavior: block every push
            cnt = node.last_release_count
            if rel is not None and cnt:
                v = np.asarray(rel.ts)[:cnt].tolist()
                out.extend(v)
        for ch in range(2):
            rel = node.close_channel(ch)
            if rel is not None and node.last_release_count:
                out.extend(np.asarray(rel.ts)[:node.last_release_count]
                           .tolist())
        rel = node.flush()
        if rel is not None and node.last_release_count:
            out.extend(np.asarray(rel.ts)[:node.last_release_count].tolist())
        return out

    for seed in range(3):
        assert run(False, seed) == run(True, seed), seed


def test_ordering_push_returns_empty_release_not_stale():
    from windflow_tpu.parallel.ordering import Ordering_Node, ordering_mode_t
    node = Ordering_Node(2, ordering_mode_t.TS)
    rel = node.push(0, _mk_ord_batch([1, 2], [1, 2]))
    # ch1 silent: nothing releasable — the async contract returns a batch
    # with zero valid lanes (or None), never stale data
    assert node.last_release_count == 0
    if rel is not None:
        assert int(np.asarray(jnp.sum(rel.valid))) >= 0
    rel2 = node.push(1, _mk_ord_batch([3], [5]))
    assert node.last_release_count > 0
    got = np.asarray(rel2.ts)[:node.last_release_count].tolist()
    # ch0's ts=1 sits strictly below the low watermark (min(2, 5) = 2);
    # ts=2 == the watermark is a potential duplicate and stays held
    assert got == [1]
