"""Nexmark suite correctness: every query passes its dense oracle (exact
expected outputs, the ``test_ysb.py`` style) invariant under batch size; the
interval-join and session queries are byte-identical across the plain /
threaded / supervised drivers and under FaultPlan injection with mid-upsert
checkpoints (both supervised drivers); the join-table state replays byte-identically through a
restart that lands between an upsert's ingestion and its watermark
application."""

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.nexmark import QUERIES, make_query, oracles
from windflow_tpu.operators.join import StreamTableJoin
from windflow_tpu.runtime.faults import FaultPlan, FaultSpec

TOTAL = 400


def pay(v, f):
    return np.asarray(v["payload"][f]).tolist()


def ids(v, f):
    return np.asarray(v[f]).tolist()


ROW_FNS = {
    "q1_currency": lambda v: list(zip(ids(v, "id"), pay(v, "auction"),
                                      pay(v, "euro"))),
    "q2_selection": lambda v: list(zip(ids(v, "id"), pay(v, "auction"),
                                       pay(v, "price"))),
    "q3_enrich_join": lambda v: list(zip(ids(v, "id"), pay(v, "auction"),
                                         pay(v, "category"),
                                         pay(v, "price"))),
    "q4_interval_join": lambda v: list(zip(pay(v, "auction"),
                                           pay(v, "open_ts"),
                                           pay(v, "bid_ts"),
                                           pay(v, "price"))),
    "q5_session": lambda v: list(zip(
        ids(v, "key"), ids(v, "id"), pay(v, "start"), pay(v, "end"),
        pay(v, "n"),
        [int(x) for x in np.asarray(v["payload"]["agg"]["bids"])],
        [int(x) for x in np.asarray(v["payload"]["agg"]["spend"])])),
    "q7_distinct": lambda v: list(zip(ids(v, "id"), pay(v, "auction"))),
}


def run_query(name, batch, driver="plain", **kw):
    src, ops = make_query(name, TOTAL)
    rows = []
    rowfn = ROW_FNS[name]

    def cb(view):
        if view is None:
            return
        rows.extend(rowfn(view))
    sink = wf.Sink(cb)
    if driver == "plain":
        wf.Pipeline(src, ops, sink, batch_size=batch, **kw).run()
    elif driver == "threaded":
        wf.ThreadedPipeline(src, [ops], sink, batch_size=batch, **kw).run()
    elif driver == "supervised":
        wf.SupervisedPipeline(src, ops, sink, batch_size=batch,
                              backoff_base=0.001, backoff_cap=0.01,
                              **kw).run()
    elif driver == "graph-supervised":
        g = wf.PipeGraph(batch_size=batch)
        mp = g.add_source(src)
        for op in ops:
            mp.add(op)
        mp.add_sink(sink)
        g.run_supervised(checkpoint_every=2, backoff_base=0.001,
                         backoff_cap=0.01, **kw)
    return rows


# --------------------------------------------------------- dense oracles

@pytest.mark.parametrize("batch", [32, 64, 100, TOTAL])
@pytest.mark.parametrize("name", ["q1_currency", "q2_selection",
                                  "q3_enrich_join", "q4_interval_join",
                                  "q5_session", "q7_distinct"])
def test_query_matches_dense_oracle(name, batch):
    got = sorted(run_query(name, batch))
    want = oracles.ORACLES[name](TOTAL)
    assert got == want


@pytest.mark.parametrize("batch", [64, 100])
def test_topn_matches_dense_oracle(batch):
    src, ops = make_query("q6_topn", TOTAL)
    final = {}

    def cb(view):
        if view is None:
            return
        for k, r, i, s in zip(view["key"].tolist(),
                              np.asarray(view["payload"]["rank"]).tolist(),
                              view["id"].tolist(),
                              np.asarray(view["payload"]["score"]).tolist()):
            final[(k, r)] = (i, s)
    wf.Pipeline(src, ops, wf.Sink(cb), batch_size=batch).run()
    got = sorted((k, r, i, s) for (k, r), (i, s) in final.items())
    assert got == oracles.q6_topn(TOTAL)


def test_every_registered_query_has_oracle_and_rowfn_coverage():
    assert set(oracles.ORACLES) == set(QUERIES)
    assert set(ROW_FNS) | {"q6_topn"} == set(QUERIES)


def test_queries_match_names_registry():
    from windflow_tpu.observability.names import NEXMARK_QUERIES
    assert QUERIES == NEXMARK_QUERIES


# -------------------------------------- cross-driver / chaos byte-identity

@pytest.mark.parametrize("name", ["q4_interval_join", "q5_session"])
def test_join_and_session_byte_identical_across_drivers(name):
    base = run_query(name, 50)
    assert run_query(name, 50, "threaded") == base
    assert run_query(name, 50, "supervised") == base
    assert run_query(name, 50, "graph-supervised") == base


@pytest.mark.chaos
@pytest.mark.parametrize("name", ["q4_interval_join", "q5_session",
                                  "q3_enrich_join"])
def test_join_session_byte_identical_under_faultplan(name):
    base = run_query(name, 50)
    plan = FaultPlan([FaultSpec("chain.step", at=[3, 5])], seed=3)
    got = run_query(name, 50, "supervised", checkpoint_every=2, faults=plan)
    assert got == base
    got_g = run_query(name, 50, "graph-supervised", faults=plan)
    assert got_g == base


@pytest.mark.chaos
def test_join_table_replay_with_mid_upsert_checkpoint():
    """A restart landing while upserts are still parked in the pending ring
    (delay > 0) must replay the join-table state byte-identically: the
    checkpoint carries the ring, the watermark, and the arrival-seq stamp."""
    def gen(i):
        is_def = (i % 4) == 0
        return {"side": jnp.where(is_def, 1, 0).astype(jnp.int32),
                "k": ((i // 4) % 8).astype(jnp.int32),
                "val": (i * 10).astype(jnp.int32)}
    mk = lambda: wf.Source(gen, total=160, num_keys=8,
                           key_fn=lambda i: (i // 4) % 8,
                           ts_fn=lambda i: i // 4)
    op = lambda: StreamTableJoin(
        lambda t: t.side == 1, lambda t: t.k, lambda t: {"jv": t.val},
        num_slots=16, delay=3, emit_misses=True)

    def run(faults=None):
        rows = []

        def cb(view):
            if view is None:
                return
            rows.extend(zip(view["id"].tolist(),
                            np.asarray(view["payload"]["jv"]).tolist()))
        wf.SupervisedPipeline(mk(), [op()], wf.Sink(cb), batch_size=16,
                              checkpoint_every=2, backoff_base=0.001,
                              backoff_cap=0.01, faults=faults).run()
        return rows

    base = run()
    # fault after the 3rd chain step: checkpoint at step 2 holds a pending
    # ring mid-flight (delay=3 keeps recent upserts unapplied)
    got = run(FaultPlan([FaultSpec("chain.step", at=[3])], seed=11))
    assert got == base


# ------------------------------------------------------------- wiring

def test_sweep_run_nexmark_rows():
    from windflow_tpu.benchmarks.sweep import run_nexmark
    rows = run_nexmark(batches=(64,), steps=2)
    assert len(rows) == len(QUERIES)
    assert all(tps > 0 for _, _, _, tps in rows)
    names = {n for n, _, _, _ in rows}
    assert names == {f"nexmark:{q}" for q in QUERIES}


def test_validate_clean_on_every_query():
    from windflow_tpu.analysis import validate
    for name in QUERIES:
        src, ops = make_query(name, TOTAL)
        rep = validate(wf.Pipeline(src, ops, wf.Sink(lambda v: None),
                                   batch_size=64))
        assert rep.ok, f"{name}: {rep}"
