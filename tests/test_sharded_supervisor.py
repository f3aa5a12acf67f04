"""Shard-local supervision (``runtime/supervisor.py`` ``ShardSupervisor`` /
``ShardedSupervisor``): shard-count invariance (1 vs 4 vs a mid-run 4 -> 8
live reshard) across both supervised drivers and the Nexmark query set,
kill-one-of-4 chaos with the no-global-restart journal pin, sharded-and-
parallel checkpoints (per-shard lineage + per-shard fallback), deterministic
re-sharding under torn-handoff / mid-handoff-checkpoint chaos, the governor's
reshard planner, per-shard health reporting + host-tagged fleet folding, and
the WF115 validator pins."""

import glob
import os

import numpy as np
import jax.numpy as jnp
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import Mode, win_type_t
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.parallel.sharding import (ReshardPlan, ShardAssignment,
                                            affected_shards, make_splitter,
                                            resolve_shards)
from windflow_tpu.runtime import checkpoint as ckpt
from windflow_tpu.runtime.faults import FaultInjector, FaultPlan, FaultSpec
from windflow_tpu.runtime.supervisor import (ShardedSupervisor,
                                             SupervisedPipeline,
                                             _fresh_states)

TOTAL, K = 400, 4


def build(sink_cb, **kw):
    src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                    total=TOTAL, num_keys=K)
    op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                    WindowSpec(10, 10, win_type_t.TB), num_keys=K)
    return SupervisedPipeline(src, [op], wf.Sink(sink_cb), batch_size=50,
                              backoff_base=0.0, **kw)


def collect(results):
    def cb(view):
        if view is None:
            return
        results.extend(zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()))
    return cb


def run_build(**kw):
    got = []
    p = build(collect(got), **kw)
    p.run()
    return sorted(got), p


# ------------------------------------------------------------- assignment


def test_assignment_owner_and_moves():
    a = ShardAssignment(4)
    assert [a.owner(k) for k in range(8)] == [0, 1, 2, 3, 0, 1, 2, 3]
    m = ShardAssignment(4, ((5, 0), (2, 3)))
    assert m.owner(5) == 0 and m.owner(2) == 3 and m.owner(6) == 2
    rt = ShardAssignment.from_meta(m.to_meta())
    assert rt == m
    with pytest.raises(ValueError, match="nonexistent shard"):
        ShardAssignment(4, ((1, 7),))
    # duplicate key slots would make owner() and the traced owner_of()
    # disagree — rejected at construction
    with pytest.raises(ValueError, match="more than one move"):
        ShardAssignment(4, ((3, 1), (3, 2)))


def test_doubling_splits_each_shard_in_two():
    # key % 2N is congruent to key % N (mod N): a 4 -> 8 reshard only ever
    # SPLITS a shard — no key moves between surviving pairs
    a4, a8 = ShardAssignment(4), ShardAssignment(8)
    for k in range(64):
        assert a8.owner(k) % 4 == a4.owner(k)


def test_split_covers_input_exactly():
    a = ShardAssignment(3)
    b = wf.Batch.of({"v": jnp.arange(32, dtype=jnp.float32)},
                    key=jnp.arange(32, dtype=jnp.int32) * 7 % 11,
                    valid=jnp.arange(32) % 5 != 0)
    subs = a.split(b)
    masks = np.stack([np.asarray(s.valid) for s in subs])
    # disjoint and complete: each live input lane lives in EXACTLY one shard
    assert (masks.sum(axis=0) == np.asarray(b.valid).astype(int)).all()
    for s in subs:
        np.testing.assert_array_equal(np.asarray(s.key), np.asarray(b.key))


def test_affected_shards():
    a4 = ShardAssignment(4)
    assert affected_shards(a4, ShardAssignment(8)) == set(range(8))
    moved = ShardAssignment(4, ((5, 0),))
    assert affected_shards(a4, moved) == {0, 1}      # donor 1, recipient 0
    assert affected_shards(moved, moved) == set()


def test_resolve_shards_and_plan(monkeypatch):
    assert resolve_shards(None) == 1
    monkeypatch.setenv("WF_SHARDS", "4")
    assert resolve_shards(None) == 4
    # '0' means OFF (the documented ENV_FLAGS contract), never an error
    monkeypatch.setenv("WF_SHARDS", "0")
    assert resolve_shards(None) == 1
    assert resolve_shards(0) == 1
    with pytest.raises(ValueError):
        resolve_shards(-2)
    monkeypatch.setenv("WF_SHARDS", "4")
    monkeypatch.setenv("WF_RESHARD", "8")
    plan = ReshardPlan.resolve(None)
    assert plan.new_shards == 8
    assert ReshardPlan.resolve('{"at_pos": 3, "moves": [[5, 0]]}').moves \
        == ((5, 0),)
    assert ReshardPlan.resolve("auto") == "auto"
    assert ReshardPlan.resolve(False) is None


# ------------------------------------------------- off-path / invariance


def test_off_path_is_single_domain():
    got, p = run_build()
    assert p._shards == 1 and p._sharded is None
    assert p.shard_report() == {}


def test_shard_count_invariance_1_vs_4_vs_live_reshard():
    oracle, _ = run_build()
    got4, p4 = run_build(shards=4, checkpoint_every=3)
    assert got4 == oracle
    rep = p4.shard_report()
    assert sorted(rep) == [0, 1, 2, 3]
    assert sum(r["occupancy_tuples"] for r in rep.values()) == TOTAL
    # mid-run live 4 -> 8 reshard: byte-identical result multiset, zero
    # dropped/duplicated keys, every unit re-admitted once
    got8, p8 = run_build(shards=4, checkpoint_every=3,
                         reshard={"new_shards": 8, "at_pos": 4})
    assert got8 == oracle
    rep8 = p8.shard_report()
    assert sorted(rep8) == list(range(8))
    assert all(r["reshard_moves"] == 1 for r in rep8.values())
    assert p8._sharded.reshard_count == 1


def test_targeted_move_rebuilds_only_donor_and_recipient():
    oracle, _ = run_build()
    got, p = run_build(shards=4, checkpoint_every=3,
                       reshard={"moves": [[3, 0]], "at_pos": 4})
    assert got == oracle
    rep = p.shard_report()
    # key 3 moved from shard 3 to shard 0: only those two units re-admitted
    assert rep[0]["reshard_moves"] == 1 and rep[3]["reshard_moves"] == 1
    assert rep[1]["reshard_moves"] == 0 and rep[2]["reshard_moves"] == 0


# ------------------------------------------------------- chaos / recovery


def test_kill_one_of_four_journal_timeline(tmp_path):
    """THE acceptance drill: kill one shard's step; surviving shards emit
    continuously (journal shows shard_restore for the killed shard and NO
    global restore span), the failed shard replays only its own extent, and
    the output is byte-identical to the fault-free run."""
    from windflow_tpu.observability import (EventJournal, read_journal,
                                            set_journal)
    oracle, _ = run_build()
    path = str(tmp_path / "events.jsonl")
    j = EventJournal(path)
    set_journal(j)
    try:
        got, p = run_build(
            shards=4, checkpoint_every=3, max_restarts=4,
            faults=FaultInjector(FaultPlan(
                [FaultSpec("shard.kill", where={"shard": 2}, max_fires=2)],
                seed=1)))
    finally:
        set_journal(None)
        j.close()
    assert got == oracle
    rep = p.shard_report()
    assert rep[2]["restarts"] == 2
    assert all(rep[k]["restarts"] == 0 for k in (0, 1, 3))
    assert rep[2]["last_recovery_s"] > 0.0
    events = read_journal(path)
    restores = [e for e in events if e.get("event") == "shard_restore"]
    assert len(restores) == 2
    assert all(e["shard"] == 2 for e in restores)
    assert all("replay_from" in e for e in restores)
    # NO whole-domain restore: the "restore" span never opened (global
    # restarts would journal it), and commits continued across the kills
    assert not [e for e in events if e.get("event") == "restore"]
    ckpts = [e for e in events if e.get("event") == "checkpoint"
             and e.get("phase") == "begin"]
    assert ckpts and all(c.get("shards") == 4 for c in ckpts)


def test_plan_past_eos_is_journaled_not_silent(tmp_path):
    """A reshard plan whose barrier never arrives (at_pos past the stream)
    must leave an aborted journal record — a silently dropped re-layout
    would look like a healthy run."""
    from windflow_tpu.observability import (EventJournal, read_journal,
                                            set_journal)
    path = str(tmp_path / "e.jsonl")
    j = EventJournal(path)
    set_journal(j)
    try:
        got, p = run_build(shards=2, checkpoint_every=3,
                           reshard={"new_shards": 4, "at_pos": 10_000})
    finally:
        set_journal(None)
        j.close()
    assert len(p.shard_report()) == 2        # never applied
    ev = [e for e in read_journal(path) if e.get("event") == "reshard"]
    assert ev and ev[-1].get("aborted") and "stream ended" in ev[-1]["error"]


def test_shard_restart_budget_exhausts_locally():
    with pytest.raises(wf.RestartExhausted, match="shard 1"):
        run_build(shards=2, checkpoint_every=4, max_restarts=1,
                  faults=FaultInjector(FaultPlan(
                      [FaultSpec("shard.kill", where={"shard": 1})],
                      seed=0)))


def test_shard_poison_quarantine_dead_letters_exact_sub_batch():
    from windflow_tpu.runtime.faults import DeadLetterQueue
    oracle, _ = run_build()
    dlq = DeadLetterQueue()
    got, p = run_build(
        shards=4, checkpoint_every=3, max_restarts=6, dead_letter=dlq,
        poison_threshold=2,
        faults=FaultInjector(FaultPlan(
            [FaultSpec("shard.kill", where={"shard": 1, "pos": 3})],
            seed=0)))
    # shard 1's sub-batch at pos 3 was quarantined; every other (shard,
    # pos) cell — including the OTHER shards' slices of pos 3 — delivered
    assert len(dlq) == 1
    entry = dlq.entries[0]
    assert entry["pos"] == 3 and entry["driver"].endswith("shard1")
    assert p.shard_report()[1]["dead_letters"] == 1
    lost = set(oracle) - set(got)
    assert lost and not set(got) - set(oracle)
    # lost results all belong to shard 1's key range (key % 4 == 1)
    assert {k % 4 for k, _i, _v in lost} == {1}


def test_global_fault_falls_back_to_whole_domain_restore():
    oracle, _ = run_build()
    got, p = run_build(shards=4, checkpoint_every=3, max_restarts=3,
                       faults=FaultInjector(FaultPlan(
                           [FaultSpec("source.next", at=[5])], seed=0)))
    assert got == oracle
    assert p.restarts >= 1


def test_torn_handoff_discarded_and_rederived(tmp_path):
    oracle, _ = run_build()
    path = str(tmp_path / "ck.npz")
    got, p = run_build(
        shards=4, checkpoint_every=2, spill_path=path, max_restarts=4,
        reshard={"new_shards": 8, "at_pos": 3},
        faults=FaultInjector(FaultPlan(
            [FaultSpec("reshard.handoff", kind="torn", max_fires=1)],
            seed=5)))
    assert got == oracle
    assert len(p.shard_report()) == 8
    assert not glob.glob(str(tmp_path / "ck.handoff*")), "seal debris left"


def test_checkpoint_lands_mid_handoff_rederives_move(tmp_path):
    """A checkpoint.save fault during the post-reshard barrier commit: the
    restore discards the in-flight handoff manifests, replay re-derives the
    move at the same barrier, results stay byte-identical."""
    oracle, _ = run_build()
    path = str(tmp_path / "ck.npz")
    shard5 = ckpt.shard_stem(path, 5) + ".npz"
    got, p = run_build(
        shards=4, checkpoint_every=2, spill_path=path, max_restarts=4,
        reshard={"new_shards": 8, "at_pos": 3},
        faults=FaultInjector(FaultPlan(
            [FaultSpec("checkpoint.save", where={"path": shard5},
                       max_fires=1)], seed=6)))
    assert got == oracle
    assert len(p.shard_report()) == 8
    assert not glob.glob(str(tmp_path / "ck.handoff*"))


# ------------------------------------------------- sharded checkpoints


def test_sharded_checkpoint_files_and_roundtrip(tmp_path):
    path = str(tmp_path / "ck.npz")
    got, p = run_build(shards=4, checkpoint_every=2, spill_path=path,
                       checkpoint_keep=3)
    # one lineage per shard + the shards manifest
    for k in range(4):
        assert os.path.exists(ckpt.manifest_path(ckpt.shard_stem(path, k)))
    states, layout, meta = ckpt.load_sharded(_fresh_states(p.chain), path)
    assert sorted(states) == [0, 1, 2, 3]
    assert layout == {"num_shards": 4, "moves": []}
    assert meta["batches_done"] == TOTAL // 50
    # the restored per-shard states match the final supervised snapshots
    import jax
    for k, s in enumerate(p._sharded.shards):
        got_leaves = [np.asarray(x) for st in states[k]
                      for x in jax.tree.leaves(st)]
        want_leaves = [np.asarray(x) for st in s.snap
                       for x in jax.tree.leaves(st)]
        assert len(got_leaves) == len(want_leaves)
        for ga, wa in zip(got_leaves, want_leaves):
            np.testing.assert_array_equal(ga, wa)


def test_per_shard_lineage_fallback(tmp_path):
    """Corrupting ONE shard's newest lineage file degrades THAT shard to
    its previous commit (checkpoint_fallback) without touching peers."""
    path = str(tmp_path / "ck.npz")
    _got, p = run_build(shards=4, checkpoint_every=2, spill_path=path,
                        checkpoint_keep=3)
    man = ckpt._read_manifest(ckpt.manifest_path(ckpt.shard_stem(path, 2)))
    newest = os.path.join(str(tmp_path), man["entries"][-1]["file"])
    with open(newest, "wb") as f:
        f.write(b"torn!")
    states, _layout, _meta = ckpt.load_sharded(_fresh_states(p.chain), path)
    assert sorted(states) == [0, 1, 2, 3]    # shard 2 fell back, peers fine


def test_save_sharded_is_committed_by_manifest(tmp_path):
    """Shard files not named by a fully-written shards manifest are
    invisible to load_sharded (the crash-mid-fan-out rule)."""
    path = str(tmp_path / "ck.npz")
    with pytest.raises(ckpt.CheckpointCorrupt, match="manifest"):
        ckpt.load_sharded([], path)


# ------------------------------------------------------- nexmark + graph


from test_nexmark import ROW_FNS, run_query  # noqa: E402


def _run_nexmark_sharded(name, shards, reshard=None, total=400):
    from windflow_tpu.nexmark import make_query
    src, ops = make_query(name, total)
    rows = []
    rowfn = ROW_FNS[name]

    def cb(view):
        if view is None:
            return
        rows.extend(rowfn(view))
    # q5 re-keys by bidder (KeyBy): ownership must follow the session key
    key_fn = (lambda t: t.bidder) if name == "q5_session" else None
    wf.SupervisedPipeline(src, ops, wf.Sink(cb), batch_size=50,
                          checkpoint_every=3, backoff_base=0.0,
                          shards=shards, reshard=reshard,
                          shard_key=key_fn).run()
    return sorted(rows)


@pytest.mark.parametrize("name", sorted(ROW_FNS))
def test_nexmark_shard_count_invariance(name):
    base = sorted(run_query(name, 50, "supervised"))
    assert _run_nexmark_sharded(name, 4) == base


@pytest.mark.parametrize("name", ["q3_enrich_join", "q5_session"])
def test_nexmark_live_reshard_4_to_8(name):
    base = sorted(run_query(name, 50, "supervised"))
    got = _run_nexmark_sharded(name, 4,
                               reshard={"new_shards": 8, "at_pos": 4})
    assert got == base


def test_topn_shard_invariance():
    from windflow_tpu.nexmark import make_query

    def run(shards):
        src, ops = make_query("q6_topn", TOTAL)
        final = {}

        def cb(view):
            if view is None:
                return
            for k, r, i, s in zip(
                    view["key"].tolist(),
                    np.asarray(view["payload"]["rank"]).tolist(),
                    view["id"].tolist(),
                    np.asarray(view["payload"]["score"]).tolist()):
                final[(k, r)] = (i, s)
        wf.SupervisedPipeline(src, ops, wf.Sink(cb), batch_size=50,
                              checkpoint_every=3, backoff_base=0.0,
                              shards=shards).run()
        return sorted((k, r, i, s) for (k, r), (i, s) in final.items())
    assert run(4) == run(1)


def _graph_run(shards=1, faults=None, reshard=None, mode=Mode.DEFAULT):
    got = []
    g = wf.PipeGraph("shtest", batch_size=20, mode=mode)
    a = g.add_source(wf.Source(lambda i: {"v": (i % 9).astype(jnp.float32)},
                               total=200, num_keys=3, name="a"))
    b = g.add_source(wf.Source(lambda i: {"v": (i % 7).astype(jnp.float32)},
                               total=100, num_keys=3, name="b"))
    (a.merge(b)
     .add(wf.Win_Seq(lambda wid, it: it.sum("v"),
                     WindowSpec(12, 12, win_type_t.CB), num_keys=3))
     .add_sink(wf.Sink(collect(got))))
    g.run_supervised(checkpoint_every=3, max_restarts=6, backoff_base=0.0,
                     backoff_cap=0.01, faults=faults, shards=shards,
                     reshard=reshard)
    return sorted(got), g


def test_graph_shard_invariance_and_kill():
    base, _ = _graph_run()
    got, g = _graph_run(shards=3)
    assert got == base
    assert sorted(g._shard_report) == [0, 1, 2]
    killed, g2 = _graph_run(
        shards=3,
        faults=FaultInjector(FaultPlan(
            [FaultSpec("shard.kill", where={"shard": 1}, max_fires=2)],
            seed=3)))
    assert killed == base
    assert g2._shard_report[1]["restarts"] == 2
    assert g2._shard_report[0]["restarts"] == 0


def test_graph_deterministic_merge_sharded():
    base, _ = _graph_run(mode=Mode.DETERMINISTIC)
    got, _g = _graph_run(shards=2, mode=Mode.DETERMINISTIC)
    assert got == base


def test_graph_live_reshard():
    base, _ = _graph_run()
    got, g = _graph_run(shards=2, reshard={"new_shards": 4, "at_pos": 3})
    assert got == base
    assert sorted(g._shard_report) == [0, 1, 2, 3]


# ------------------------------------------------- multi-host slice


def test_process_shard_slice_union_is_exact():
    from windflow_tpu.parallel import multihost
    lo, hi = multihost.process_shard_slice(4)
    assert (lo, hi) == (0, 4)                # single-process: all shards
    oracle, _ = run_build()
    a, _pa = run_build(shards=4, shard_range=(0, 2))
    b, _pb = run_build(shards=4, shard_range=(2, 4))
    merged = sorted(a + b)
    assert merged == oracle                  # no key lost, none duplicated
    assert a and b


def test_shard_range_requires_sharding_on():
    """shard_range= with shards resolving to 1 must be LOUD: a host that
    silently supervised the whole stream would duplicate every output
    across the fleet (the graph-driver rejection, mirrored)."""
    with pytest.raises(ValueError, match="shard_range"):
        build(lambda v: None, shard_range=(0, 1))


def test_shard_range_rejects_reshard():
    with pytest.raises(ValueError, match="shard_range"):
        run_build(shards=4, shard_range=(0, 2),
                  reshard={"new_shards": 8, "at_pos": 2})


# ------------------------------------------------- composition guards


def test_shard_key_follows_rekeyed_stream():
    """A KeyBy re-key under sharding: ownership must follow the KeyBy's
    key (shard_key=), and the validator errors without it."""
    from windflow_tpu.analysis import validate

    def mk(**kw):
        src = wf.Source(lambda i: {"u": (i * 3 % 7).astype(jnp.int32),
                                   "v": (i % 13).astype(jnp.float32)},
                        total=TOTAL, num_keys=16)
        ops = [wf.KeyBy(lambda t: t.u, 7),
               wf.Win_Seq(lambda wid, it: it.sum("v"),
                          WindowSpec(10, 10, win_type_t.TB), num_keys=7)]
        got = []
        p = SupervisedPipeline(src, ops, wf.Sink(collect(got)),
                               batch_size=50, backoff_base=0.0, **kw)
        return p, got
    p1, got1 = mk()
    p1.run()
    p4, got4 = mk(shards=4, shard_key=lambda t: t.u)
    p4.run()
    assert sorted(got4) == sorted(got1)
    bad, _ = mk(shards=4)                    # no shard_key: WF115 error
    r = validate(bad)
    assert any(d.code == "WF115" and "KeyBy" in d.message for d in r.errors)


# --------------------------------------------- governor / auto-reshard


def test_recommend_reshard_planner():
    from windflow_tpu.control.governor import recommend_reshard
    a = ShardAssignment(4)
    assert recommend_reshard({0: 10, 1: 10, 2: 10, 3: 10}, a) is None
    plan = recommend_reshard({0: 100, 1: 5, 2: 5, 3: 5}, a)
    assert plan is not None and plan.new_shards == 8
    assert recommend_reshard({0: 100, 1: 5}, a, max_shards=4) is None
    assert recommend_reshard({}, a) is None
    assert recommend_reshard({0: 0.0, 1: 0.0}, a) is None
    # scale-free trigger: two active keys spread over 8 shards is NOT skew
    # (a max/mean ratio of 4 would have mis-fired here)
    assert recommend_reshard({i: (50 if i in (1, 5) else 0)
                              for i in range(8)}, ShardAssignment(8)) is None


def test_auto_reshard_doubles_under_skew():
    """reshard='auto': the governor's planner sees the committed per-shard
    load (shard 0 carries ~85% of traffic under a hot key) and doubles the
    layout at a barrier — results stay exact."""
    def mk(**kw):
        # key 0 carries ~85% of traffic; under shards=4 shard 0's load is
        # > 2x the mean, which trips the planner's doubling rule
        src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                        total=TOTAL, num_keys=4,
                        key_fn=lambda i: ((i % 20 >= 17) *
                                          (1 + i % 3)).astype(jnp.int32))
        op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                        WindowSpec(10, 10, win_type_t.TB), num_keys=4)
        got = []
        p = SupervisedPipeline(src, [op], wf.Sink(collect(got)),
                               batch_size=50, checkpoint_every=2,
                               backoff_base=0.0, **kw)
        p.run()
        return sorted(got), p
    base, _ = mk()
    got, p = mk(shards=4, reshard="auto")
    assert got == base
    assert p._sharded.reshard_count >= 1
    assert len(p.shard_report()) >= 8


def test_auto_reshard_stops_when_doubling_cannot_help():
    """A single hot key slot: ``key % 2N`` cannot split it, so after one
    futile doubling the governor's per-epoch skew ratio does not improve
    and auto-resharding STOPS instead of cascading to max_shards."""
    def mk(**kw):
        src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                        total=2 * TOTAL, num_keys=4,
                        key_fn=lambda i: (i * 0).astype(jnp.int32))
        op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                        WindowSpec(10, 10, win_type_t.TB), num_keys=4)
        got = []
        p = SupervisedPipeline(src, [op], wf.Sink(collect(got)),
                               batch_size=50, checkpoint_every=2,
                               backoff_base=0.0, **kw)
        p.run()
        return sorted(got), p
    base, _ = mk()
    got, p = mk(shards=4, reshard="auto")
    assert got == base
    assert p._sharded.reshard_count == 1     # one doubling, then damped
    assert len(p.shard_report()) == 8
    assert p._sharded._auto_stopped


def test_graph_drain_failure_recovers_without_double_apply():
    """A fault during the EOS drain: the shard restores to its last commit
    and replays its buffer — the replayed state must NOT stack on top of
    the stale pre-drain capture (the double-apply bug: uncommitted batches
    counted twice in a ReduceSink)."""
    from windflow_tpu.operators.sink import ReduceSink

    def run(shards, fail_drain=False):
        g = wf.PipeGraph("drain", batch_size=20)
        mp = g.add_source(wf.Source(
            lambda i: {"v": (i % 13).astype(jnp.float32)},
            total=190, num_keys=4, name="s"))
        mp.add(wf.Map(lambda t: {"v": t.v * 2.0}))
        mp.add(ReduceSink(lambda t: t.v, name="total"))
        if fail_drain:
            orig = g._topo_order
            hits = {"n": 0}

            def flaky():
                hits["n"] += 1
                if hits["n"] == 1:        # first drain call only
                    raise RuntimeError("injected drain fault")
                return orig()
            g._topo_order = flaky
        res = g.run_supervised(checkpoint_every=3, max_restarts=4,
                               backoff_base=0.0, shards=shards)
        return float(np.asarray(res["total"]))
    oracle = run(1)
    assert run(2) == oracle
    assert run(2, fail_drain=True) == oracle


def test_surplus_host_empty_slice_idles():
    """A fleet larger than the shard count: the surplus host's empty slice
    supervises zero shards (idles through the stream) instead of crashing;
    the owning hosts' union is still exact."""
    oracle, _ = run_build()
    a, _pa = run_build(shards=2, shard_range=(0, 1))
    b, _pb = run_build(shards=2, shard_range=(1, 2))
    c, pc = run_build(shards=2, shard_range=(2, 2))     # surplus host
    assert c == [] and pc.shard_report() == {}
    assert sorted(a + b) == oracle


def test_multihost_slice_manifests_do_not_clobber(tmp_path):
    """Two hosts spilling slices of one layout to a shared stem: per-slice
    manifests coexist (no last-writer-wins), load_sharded merges them, and
    a missing slice is a LOUD CheckpointCorrupt, never a silent partial
    restore."""
    path = str(tmp_path / "fleet.npz")
    _a, pa = run_build(shards=4, shard_range=(0, 2), checkpoint_every=2,
                       spill_path=path)
    _b, pb = run_build(shards=4, shard_range=(2, 4), checkpoint_every=2,
                       spill_path=path)
    tmpl = _fresh_states(pa.chain)
    states, layout, _meta = ckpt.load_sharded(tmpl, path)
    assert sorted(states) == [0, 1, 2, 3] and layout["num_shards"] == 4
    # drop host B's slice manifest: the restore must refuse, naming the gap
    os.unlink(str(tmp_path / "fleet.shards.s2-3.json"))
    for f in glob.glob(str(tmp_path / "fleet.shard2*")) \
            + glob.glob(str(tmp_path / "fleet.shard3*")):
        os.unlink(f)
    with pytest.raises(ckpt.CheckpointCorrupt, match=r"\[2, 3\] missing"):
        ckpt.load_sharded(tmpl, path)


def test_stale_slice_manifest_never_overrides_newer_full_save(tmp_path):
    """Deployment-shape switch: per-slice manifests left behind must not
    override a NEWER full save's entries (per shard, the newest generation
    wins the merge)."""
    path = str(tmp_path / "sw.npz")
    # phase 1: two-host slices at batches_done=8
    _a, pa = run_build(shards=4, shard_range=(0, 2), checkpoint_every=4,
                       spill_path=path)
    _b, _pb = run_build(shards=4, shard_range=(2, 4), checkpoint_every=4,
                        spill_path=path)
    # phase 2: single-host full save of a LONGER run (batches_done bumped
    # by hand to model a later generation under the same layout)
    import json as _json
    _c, pc = run_build(shards=4, checkpoint_every=4, spill_path=path)
    mf = str(tmp_path / "sw.shards.json")
    man = _json.loads(open(mf).read())
    man["meta"]["batches_done"] = 16
    for k in range(4):
        smf = ckpt.manifest_path(ckpt.shard_stem(path, k))
        # keep=1: no per-stem lineage; rewrite the shard files' meta via a
        # fresh save_states at the newer generation
        ckpt.save_states(pc._sharded.shards[k].snap, ckpt.shard_stem(path, k),
                         meta={"batches_done": 16, "shard": k,
                               "num_shards": 4})
        assert not ckpt._read_manifest(smf)
    open(mf, "w").write(_json.dumps(man))
    _states, _layout, meta = ckpt.load_sharded(_fresh_states(pa.chain), path)
    # the full (newest) manifest won for every shard despite the stale
    # slice manifests sorting first lexicographically
    assert all(m["batches_done"] == 16 for m in meta["shard_meta"].values())


def test_wf115_env_reshard_parity(monkeypatch):
    """WF_RESHARD alone must get the same WF115 legality checks as an
    explicit reshard= (the drivers resolve the env; so must the gate)."""
    from windflow_tpu.analysis import validate
    monkeypatch.setenv("WF_RESHARD", '{"moves": [[3, 99]]}')
    p = build(lambda v: None, shards=4)
    r = validate(p)
    assert any(d.code == "WF115" and "does not exist" in d.message
               for d in r.errors), r
    monkeypatch.setenv("WF_RESHARD", "not-json{")
    assert any(d.code == "WF115" for d in validate(
        build(lambda v: None, shards=4)).errors)
    monkeypatch.setenv("WF_RESHARD", "8")
    p1 = build(lambda v: None)               # shards off: can-never-apply
    assert any(d.code == "WF115" for d in validate(p1).warnings)


def test_empty_slice_reduce_sink_returns_identity():
    from windflow_tpu.operators.sink import ReduceSink
    src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                    total=100, num_keys=4)
    ops = [ReduceSink(lambda t: t.v, name="total")]
    p = SupervisedPipeline(src, ops, None, batch_size=50, backoff_base=0.0,
                           shards=2, shard_range=(2, 2))
    res = p.run()
    assert float(np.asarray(res["total"])) == 0.0    # identity, never None


def test_wf115_graph_env_shards_and_shard_key_passthrough(monkeypatch):
    """WF_SHARDS alone must give a supervised graph the WF115 coverage
    (the run resolves the env, so must the validator), and validate's
    shard_key= passthrough silences the KeyBy error for a correctly
    configured run."""
    from windflow_tpu.analysis import validate

    def mk_graph():
        g = wf.PipeGraph("env", batch_size=20)
        mp = g.add_source(wf.Source(
            lambda i: {"u": (i * 3 % 7).astype(jnp.int32),
                       "v": (i % 13).astype(jnp.float32)},
            total=100, num_keys=16))
        mp.add(wf.KeyBy(lambda t: t.u, 7))
        mp.add(wf.Win_Seq(lambda wid, it: it.sum("v"),
                          WindowSpec(10, 10, win_type_t.TB), num_keys=7))
        mp.add_sink(wf.Sink(lambda v: None))
        return g
    monkeypatch.setenv("WF_SHARDS", "4")
    r = validate(mk_graph(), supervised=True)
    assert any(d.code == "WF115" and "KeyBy" in d.message for d in r.errors)
    r = validate(mk_graph(), supervised=True, shard_key=lambda t: t.u)
    assert "WF115" not in [d.code for d in r.errors]
    monkeypatch.delenv("WF_SHARDS")
    # env off: no WF115 findings on the same graph
    assert "WF115" not in validate(mk_graph(), supervised=True).codes()


def test_graph_driver_rejects_shard_range():
    g = wf.PipeGraph("r", batch_size=20)
    g.add_source(wf.Source(lambda i: {"v": (i % 9).astype(jnp.float32)},
                           total=40, num_keys=3)).add_sink(
        wf.Sink(lambda v: None))
    with pytest.raises(ValueError, match="shard_range"):
        g.run_supervised(shards=2, shard_range=(0, 1))


def test_auto_reshard_replans_after_real_improvement():
    """The damping guard compares only the FIRST post-reshard epoch: a
    doubling that genuinely splits the hot pair keeps auto mode alive, and
    a NEW hot spot later in the stream triggers a second reshard (the
    stale-ratio bug permanently disabled auto after any first success)."""
    def mk(**kw):
        # phase 1: keys {1, 5} hot (both -> shard 1 of 4; a doubling
        # splits them); phase 2: keys {2, 10} hot (both -> shard 2 of 8;
        # a second doubling splits them)
        src = wf.Source(
            lambda i: {"v": (i % 13).astype(jnp.float32)},
            total=800, num_keys=16,
            key_fn=lambda i: jnp.where(
                i < 400,
                jnp.where(i % 2 == 0, 1, 5),
                jnp.where(i % 2 == 0, 2, 10)).astype(jnp.int32))
        op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                        WindowSpec(10, 10, win_type_t.TB), num_keys=16)
        got = []
        p = SupervisedPipeline(src, [op], wf.Sink(collect(got)),
                               batch_size=50, checkpoint_every=2,
                               backoff_base=0.0, **kw)
        p.run()
        return sorted(got), p
    base, _ = mk()
    got, p = mk(shards=4, reshard="auto")
    assert got == base
    assert p._sharded.reshard_count == 2, p._sharded.reshard_count
    assert not p._sharded._auto_stopped
    assert len(p.shard_report()) == 16


def test_poison_batch_survives_a_reshard():
    """A sub-batch the live run already quarantined must not kill the
    reshard's prefix replay: the rebuild dead-letters it inline and the
    run completes (previously: RestartExhausted at the barrier)."""
    from windflow_tpu.runtime.faults import DeadLetterQueue
    oracle, _ = run_build()
    dlq = DeadLetterQueue()
    got, p = run_build(
        shards=4, checkpoint_every=3, max_restarts=6, dead_letter=dlq,
        poison_threshold=2, reshard={"new_shards": 8, "at_pos": 5},
        # shard 1's slice of pos 3 is deterministically poison: it fails
        # in the live run (quarantined) AND in the rebuild replay
        faults=FaultInjector(FaultPlan(
            [FaultSpec("shard.kill", where={"shard": 1, "pos": 3})],
            seed=0)))
    assert len(p.shard_report()) == 8        # the reshard went through
    lost = set(oracle) - set(got)
    assert lost and not set(got) - set(oracle)
    assert {k % 4 for k, _i, _v in lost} == {1}


def test_sharded_manifest_detects_torn_keep1_fanout(tmp_path):
    """keep=1 + crash between the shard fan-out and the manifest rewrite:
    shard files are one generation AHEAD of the manifest (the committed
    bytes were overwritten in place) — load_sharded must refuse loudly and
    point at checkpoint_keep >= 2, never mix generations silently."""
    path = str(tmp_path / "g1.npz")
    _got, p = run_build(shards=2, checkpoint_every=4, spill_path=path)
    man_file = str(tmp_path / "g1.shards.json")
    stale = open(man_file).read().replace(
        '"batches_done": 8', '"batches_done": 4')
    open(man_file, "w").write(stale)         # manifest one commit behind
    with pytest.raises(ckpt.CheckpointCorrupt, match="AHEAD"):
        ckpt.load_sharded(_fresh_states(p.chain), path)


# ------------------------------------------------- health / reporting


def test_shard_report_gauges_registered():
    from windflow_tpu.observability.names import SHARD_GAUGES
    _got, p = run_build(shards=2, checkpoint_every=3)
    for row in p.shard_report().values():
        assert set(row) == set(SHARD_GAUGES)


def test_metrics_snapshot_shards_section_and_fleet_merge():
    from windflow_tpu.observability.device_health import merge_snapshots
    from windflow_tpu.observability.metrics import MetricsRegistry
    reg = MetricsRegistry("shtest")
    reg.attach_shards(lambda: {0: {"occupancy_tuples": 5, "restarts": 1},
                               1: {"occupancy_tuples": 9, "restarts": 0}})
    snap = reg.snapshot()
    assert snap["shards"]["1"]["occupancy_tuples"] == 9
    other = dict(snap)
    other["shards"] = {"0": {"occupancy_tuples": 50, "restarts": 2}}
    merged = merge_snapshots([snap, other], hosts=["hostA", "hostB"])
    # host-tagged, never summed: the fleet view names WHICH shard is hot
    assert merged["shards"]["hostA/1"]["occupancy_tuples"] == 9
    assert merged["shards"]["hostB/0"]["occupancy_tuples"] == 50
    assert len(merged["shards"]) == 3


def test_wf_state_and_wf_health_render_shards(tmp_path, capsys):
    import importlib.util
    import json as _json
    mon = tmp_path / "mon"
    mon.mkdir()
    snap = {"graph": "g", "shards": {
        "0": {"occupancy_tuples": 5, "restarts": 1, "last_recovery_s": 0.01,
              "dead_letters": 0, "reshard_moves": 0, "committed_pos": 8},
        "1": {"occupancy_tuples": 99, "restarts": 0, "last_recovery_s": 0.0,
              "dead_letters": 0, "reshard_moves": 1, "committed_pos": 8}}}
    (mon / "snapshot.json").write_text(_json.dumps(snap))
    (mon / "events.jsonl").write_text(
        _json.dumps({"event": "shard_restore", "shard": 0, "at_batch": 3,
                     "replay_from": 2, "error": "InjectedFault"}) + "\n"
        # a reshard SPAN: begin+end records — the CLIs must count/print it
        # once, not twice
        + _json.dumps({"event": "reshard", "phase": "begin",
                       "from_shards": 2, "to_shards": 4, "at_pos": 6,
                       "moves": 0}) + "\n"
        + _json.dumps({"event": "reshard", "phase": "end",
                       "from_shards": 2, "to_shards": 4, "at_pos": 6,
                       "moves": 0}) + "\n")
    for script in ("wf_state", "wf_health"):
        spec = importlib.util.spec_from_file_location(
            f"{script}_t", os.path.join(
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                "scripts", f"{script}.py"))
        m = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(m)
        rc = m.main(["--monitoring-dir", str(mon), "--report", "shards"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "shard" in out and "[HOT]" in out, (script, out)
        # one reshard rendered once (span begin+end != two events)
        assert out.count("2->4") <= 1, (script, out)
        rc = m.main(["--monitoring-dir", str(mon), "--json"])
        out = capsys.readouterr().out
        assert rc == 0 and _json.loads(out)["shards"]["1"]["reshard_moves"] \
            == 1


# --------------------------------------------------------- WF115 pins


def test_wf115_pins():
    from windflow_tpu.analysis import validate
    from windflow_tpu.control import ControlConfig

    def mk(**kw):
        src = wf.Source(lambda i: {"v": (i % 13).astype(jnp.float32)},
                        total=100, num_keys=K)
        op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                        WindowSpec(10, 10, win_type_t.TB), num_keys=K)
        return SupervisedPipeline(src, [op], wf.Sink(lambda v: None),
                                  batch_size=50, **kw)
    assert "WF115" not in validate(mk(shards=4)).codes()
    # shards > key space: empty shards, error
    errs = validate(mk(shards=8)).errors
    assert any(d.code == "WF115" and "key space" in d.message for d in errs)
    # indivisible: warning
    assert any(d.code == "WF115"
               for d in validate(mk(shards=3)).warnings)
    # reshard to a nonexistent shard: error
    errs = validate(mk(shards=4),
                    reshard={"new_shards": 4, "moves": [[2, 9]]}).errors
    assert any(d.code == "WF115" and "does not exist" in d.message
               for d in errs)
    # wall-clock admission under shards: error (the WF105 mirror)
    errs = validate(mk(shards=4),
                    control=ControlConfig(autotune=False, admission=True,
                                          rate_tps=100.0)).errors
    assert any(d.code == "WF115" and "wall-clock" in d.message
               for d in errs)
    # shard fault sites while shards resolve to 1: can-never-fire warning
    warns = validate(mk(), faults=FaultPlan(
        [FaultSpec("shard.kill")])).warnings
    assert any(d.code == "WF115" for d in warns)
    # reshard plan with shards=1: can-never-apply warning
    warns = validate(mk(), reshard=8).warnings
    assert any(d.code == "WF115" for d in warns)
    # graph form: pass shards/reshard explicitly
    g = wf.PipeGraph("v", batch_size=20)
    g.add_source(wf.Source(lambda i: {"v": (i % 9).astype(jnp.float32)},
                           total=100, num_keys=3)).add_sink(
        wf.Sink(lambda v: None))
    r = validate(g, supervised=True, shards=4,
                 reshard={"new_shards": 4, "moves": [[1, 7]]})
    assert any(d.code == "WF115" and "does not exist" in d.message
               for d in r.errors)


def test_shards_site_map_in_wf103():
    """The new sites are registered for the supervised driver (WF103 stays
    accurate): scheduling them under 'supervised' produces no WF103."""
    from windflow_tpu.analysis import validate
    p = build(lambda v: None, shards=4)
    r = validate(p, faults=FaultPlan([FaultSpec("shard.kill"),
                                      FaultSpec("reshard.handoff")]))
    assert "WF103" not in r.codes()
