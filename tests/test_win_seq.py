"""Win_Seq tests: CB and TB sliding/tumbling windows, keyed, with EOS flush.

Oracle: pure-python window computation over the same stream (reference pattern:
result invariance vs a sequential run, src/mp_test_cpu suite semantics)."""

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.operators.win_seq import Win_Seq
from windflow_tpu.basic import win_type_t


def run_pipeline(total, K, spec, win_fn, batch_size, **kw):
    src = wf.Source(lambda i: {"v": (i // K).astype(jnp.float32)},
                    total=total, num_keys=K)
    ws = Win_Seq(win_fn, spec, num_keys=K, **kw)
    results = []

    def cb(view):
        if view is None:
            return
        for k, w, r in zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()):
            results.append((k, w, r))

    wf.Pipeline(src, [ws], wf.Sink(cb), batch_size=batch_size).run()
    return sorted(results)


def oracle_cb(total, K, L, S, agg=sum, flush=True):
    """Python oracle: key k receives values i//K for i = k, k+K, k+2K, ..."""
    per_key = {k: [] for k in range(K)}
    for i in range(total):
        per_key[i % K].append(float(i // K))
    out = []
    for k, vals in per_key.items():
        n = len(vals)
        hi = (n - 1) // S + 1 if (flush and n > 0) else max(0, (n - L) // S + 1)
        for w in range(hi):
            content = vals[w * S: w * S + L]
            if content:
                out.append((k, w, agg(content)))
    return sorted(out)


def test_cb_tumbling_sum():
    spec = WindowSpec(win_len=4, slide=4, wtype=win_type_t.CB)
    got = run_pipeline(160, 2, spec, lambda wid, it: it.sum("v"), batch_size=32)
    assert got == oracle_cb(160, 2, 4, 4)


def test_cb_sliding_sum():
    spec = WindowSpec(win_len=6, slide=2, wtype=win_type_t.CB)
    got = run_pipeline(200, 3, spec, lambda wid, it: it.sum("v"), batch_size=64)
    assert got == oracle_cb(200, 3, 6, 2)


def test_cb_invariance_under_batch_size():
    spec = WindowSpec(win_len=5, slide=3, wtype=win_type_t.CB)
    runs = [run_pipeline(121, 4, spec, lambda wid, it: it.sum("v"), bs)
            for bs in (16, 64, 121)]
    assert runs[0] == runs[1] == runs[2]
    assert runs[0] == oracle_cb(121, 4, 5, 3)


def test_cb_incremental_fold():
    spec = WindowSpec(win_len=4, slide=4, wtype=win_type_t.CB)
    fold = lambda wid, t, acc: acc + t.v
    got = run_pipeline(96, 2, spec, fold, batch_size=24,
                       incremental=True, init_acc=jnp.zeros((), jnp.float32))
    assert got == oracle_cb(96, 2, 4, 4)


def test_cb_max_window():
    spec = WindowSpec(win_len=8, slide=8, wtype=win_type_t.CB)
    got = run_pipeline(128, 2, spec, lambda wid, it: it.max("v"), batch_size=32)
    assert got == oracle_cb(128, 2, 8, 8, agg=max)


def test_tb_tumbling_sum():
    # ts = global index i; key = i % K; window [w*8, w*8+8) per key
    total, K, L, S = 160, 2, 8, 8
    spec = WindowSpec(win_len=L, slide=S, wtype=win_type_t.TB)
    got = run_pipeline(total, K, spec, lambda wid, it: it.sum("v"), batch_size=40)
    # oracle over timestamps
    per_key = {k: [] for k in range(K)}
    for i in range(total):
        per_key[i % K].append((i, float(i // K)))   # (ts, v)
    expect = []
    for k, tuples in per_key.items():
        max_ts = max(t for t, _ in tuples)
        for w in range(max_ts // S + 1):
            content = [v for t, v in tuples if w * S <= t < w * S + L]
            if content:
                expect.append((k, w, sum(content)))
    assert got == sorted(expect)


def test_tb_sliding_with_lateness():
    """Out-of-order timestamps within the lateness allowance land in their windows."""
    total, K, L, S, delay = 120, 1, 10, 5, 16
    spec = WindowSpec(win_len=L, slide=S, wtype=win_type_t.TB, delay=delay)
    # scramble ts mildly: ts = i + (3 - i%7 scaled) stays within lateness
    def src_fn(i):
        return {"v": i.astype(jnp.float32)}
    src = wf.Source(src_fn, total=total, num_keys=K,
                    ts_fn=lambda i: i + (i % 3) * 2 - 2)
    ws = Win_Seq(lambda wid, it: it.sum("v"), spec, num_keys=K,
                 archive_capacity=256)
    results = []

    def cb(view):
        if view is None:
            return
        for w, r in zip(view["id"].tolist(), np.asarray(view["payload"]).tolist()):
            results.append((w, r))

    wf.Pipeline(src, [ws], wf.Sink(cb), batch_size=30).run()
    ts_of = [i + (i % 3) * 2 - 2 for i in range(total)]
    max_ts = max(ts_of)
    expect = []
    for w in range(max_ts // S + 1):
        content = [float(i) for i in range(total) if w * S <= ts_of[i] < w * S + L]
        if content:
            expect.append((w, sum(content)))
    assert sorted(results) == sorted(expect)


def test_iterable_positional_access():
    """at/[]/first/last (reference wf/iterable.hpp begin/end/at/operator[])."""
    import windflow_tpu as wf
    from windflow_tpu.operators.win_seq import Win_Seq

    results = []

    def win_fn(wid, it):
        # span = last.v - first.v; mid = it[1].v (second live tuple)
        return it.last().v - it.first().v + 100.0 * it[1].v

    src = wf.Source(lambda i: {"v": i.astype(jnp.float32)}, total=40, num_keys=1)

    def cb(view):
        if view is None:
            return
        results.extend(zip(view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()))

    wf.Pipeline(src, [Win_Seq(win_fn, WindowSpec(8, 8, win_type_t.CB),
                              num_keys=1)], wf.Sink(cb), batch_size=16).run()
    got = dict(results)
    for w in range(5):
        base = w * 8.0
        want = (base + 7) - base + 100.0 * (base + 1)
        assert abs(got[w] - want) < 1e-3, (w, got[w], want)


def test_vector_payload_windows():
    """Tuples carrying vector payloads (e.g. embeddings): windowed reduction is
    element-wise over the trailing dims, both non-incremental and incremental."""
    import windflow_tpu as wf
    src = lambda: wf.Source(
        lambda i: {"emb": (i % 5).astype(jnp.float32) * jnp.ones(4)},
        total=96, num_keys=2)

    def run(op):
        out = []
        def cb(view):
            if view is None:
                return
            out.extend(map(tuple, np.asarray(view["payload"]).tolist()))
        wf.Pipeline(src(), [op], wf.Sink(cb), batch_size=32).run()
        return sorted(out)

    spec = WindowSpec(8, 8, win_type_t.CB)
    noninc = run(wf.Win_Seq(lambda wid, it: it.sum("emb"), spec, num_keys=2))
    inc = run(wf.Win_Seq(lambda wid, t, acc: acc + t.emb, spec,
                         init_acc=jnp.zeros(4), num_keys=2))
    assert noninc == inc and len(noninc) == 12
    per_key = {0: [], 1: []}
    for i in range(96):
        per_key[i % 2].append(float(i % 5))
    want = sorted(tuple([sum(xs[j:j + 8])] * 4)
                  for xs in per_key.values() for j in range(0, len(xs), 8))
    assert noninc == want


# ------------------------------------------- owner_compare_cells (PR 37)

def _engine(kind, K, **kw):
    from windflow_tpu.operators.win_seqffat import Win_SeqFFAT
    if kind == "win_seq":
        return Win_Seq(lambda wid, it: it.sum("v"),
                       WindowSpec(8, 4, win_type_t.CB), num_keys=K, **kw)
    return Win_SeqFFAT(lambda t: t.v, jnp.add,
                       spec=WindowSpec(8, 4, win_type_t.CB), num_keys=K, **kw)


@pytest.mark.parametrize("kind,K,batch,rows,fired", [
    # Win_Seq: the insert's listed body rows (run_rows: a key's whole ring of
    # 128 slots a row; rows of 32 slots) and the fired windows
    ("win_seq", 3, 64, 64 // 128 + 3, 80),
    ("win_seq", 1024, 4096, 4096 // 32 + 1024, 1088),
    # Win_SeqFFAT, count-based: the (key, pane) runs and the fired windows
    ("win_seqffat", 3, 64, 64 // 4 + 2 * 3, 80),
    ("win_seqffat", 1024, 4096, 4096 // 4 + 2 * 1024, 1088),
    # past the crossover the lists keep the binary search: no cell compared
    ("win_seq", 1 << 17, 4096, None, 1088),
    ("win_seqffat", 1 << 18, 4096, None, 1088),
])
def test_owner_compare_cells_is_rows_by_keys_at_the_engines_shapes(
        kind, K, batch, rows, fired):
    from windflow_tpu.observability.names import STAGE_GAUGES
    op = _engine(kind, K)
    op.bind_geometry(batch)
    # known with the fired-window budget: from the first apply, or max_wins=
    assert "owner_compare_cells" not in op.stage_counters()
    op = _engine(kind, K, max_wins=fired)
    op.bind_geometry(batch)
    counters = op.stage_counters()
    assert "owner_compare_cells" in STAGE_GAUGES
    if kind == "win_seq":
        assert counters["fired_window_budget"] == fired
        assert rows is None or op.run_rows == rows
    else:
        assert rows is None or counters["ffat_run_budget"] == rows
    assert counters["owner_compare_cells"] == (
        0 if rows is None else (rows + fired) * K)


def test_global_time_path_lists_no_rows_and_publishes_no_cells():
    from windflow_tpu.operators.win_seqffat import Win_SeqFFAT
    op = Win_SeqFFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(8, 4, win_type_t.TB), num_keys=4)
    op.bind_geometry(64)
    assert "fired_window_budget" in op.stage_counters()
    assert "owner_compare_cells" not in op.stage_counters()


@pytest.mark.parametrize("kind", ["win_seq", "win_seqffat"])
def test_results_past_the_crossover_are_the_oracles(kind):
    """An engine bound for 131,072 keys keeps the binary search (the gauge
    reads 0) and delivers what the same stream gives at 3."""
    out = {}
    for K in (3, 1 << 17):
        op = _engine(kind, K)
        src = wf.Source(lambda i: {"v": (i // 3).astype(jnp.float32)},
                        total=200, num_keys=3)
        got = []

        def cb(view):
            if view is not None:
                got.extend(zip(view["key"].tolist(), view["id"].tolist(),
                               np.asarray(view["payload"]).tolist()))
        wf.Pipeline(src, [op], wf.Sink(cb), batch_size=16).run()
        out[K] = sorted(got)
        cells = op.stage_counters()["owner_compare_cells"]
        assert (cells > 0) == (K == 3)
    assert out[3] == out[1 << 17] == oracle_cb(200, 3, 8, 4)
