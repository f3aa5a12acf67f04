"""Configuration ``ysb_wmr`` (the Yahoo Streaming Benchmark with its window
stage as ``Win_MapReduce`` over the archive engine ``Win_Seq``) at rehearsal
sizes on the CPU, and what the published size made the engine grow: budgets
from the deployment, a count of live ring slots overwritten, an EOS flush that
delivers every open window, and the ``insert`` / ``emit`` scopes the
benchmark's readers find the engine by."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.basic import win_type_t
from windflow_tpu.batch import Batch
from windflow_tpu.benchmarks import ysb
from windflow_tpu.operators.win_patterns import Win_MapReduce
from windflow_tpu.operators.win_seq import Win_Seq
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.runtime.pipeline import CompiledChain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import span_reduce  # noqa: E402

BATCH = 16384
N_BATCHES = 9           # 147,456 events: one whole 100,000-event window, one open


def load_config(name):
    spec = importlib.util.spec_from_file_location(
        "wmr_cfg_" + name, os.path.join(BENCH, "configs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg.get("rehearsal", {}))
    return mod, cfg


def make_pool(seed, n_batches=N_BATCHES):
    mod, cfg = load_config("ysb_wmr")
    return mod.make_pool(cfg, np.random.default_rng(seed), BATCH, n_batches)


def run_config(name, pool, ops=None):
    """The served path over ``pool``: (ops, every (key, id, value) the sink
    got, in delivery order)."""
    mod, cfg = load_config(name)

    def records():
        for j, recs in enumerate(pool):
            mod.stamp(cfg, recs, j * BATCH)
            yield recs

    got = []

    def deliver(view):
        if view is not None:
            got.extend(zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()))
    src = wf.RecordSource(records, mod.RECORD, key_field=mod.KEY_FIELD,
                          ts_field=mod.TS_FIELD, name="wmr_records")
    ops = ops or mod.build_ops(cfg, BATCH)
    wf.Pipeline(src, ops, wf.Sink(deliver), batch_size=BATCH,
                prefetch=2).run()
    return ops, got


def as_grid(got, shape):
    grid = np.zeros(shape, np.int64)
    for k, w, v in got:
        grid[k, w] += v
    return grid


@pytest.mark.parametrize("seed", [11, 2_700_000_019, 3_000_000_007])
def test_served_path_equals_the_reference_eos_flush_included(seed):
    mod, cfg = load_config("ysb_wmr")
    pool = make_pool(seed)
    ops, got = run_config("ysb_wmr", pool)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    cells = [(k, w) for k, w, _ in got]
    assert len(set(cells)) == len(cells)                    # each once
    assert set(cells) == set(zip(*np.nonzero(want["must_deliver"])))
    assert want["value"].shape[1] == 2                      # one of them open
    assert np.array_equal(as_grid(got, want["value"].shape), want["value"])
    # per key in ascending window order, across the EOS flush
    last = {}
    for k, w in cells:
        assert w > last.get(k, -1)
        last[k] = w
    checks = mod.program_checks(cfg, ops)
    assert set(mod.ENGINE_COUNTERS) < set(checks)
    assert all(v == 0 and limit == 0 for v, limit in checks.values()), checks
    # the repo's own reference, from the stamped records' three columns
    recs = np.concatenate(pool)
    assert np.array_equal(
        ysb.window_counts(recs["ad_id"], recs["event_type"], recs["ts"],
                          win_len=cfg["win_len_ms"]), want["value"])


def test_reference_in_bfloat16_is_not_the_reference():
    import ml_dtypes
    mod, cfg = load_config("ysb_wmr")
    pool = make_pool(5)
    exact = mod.reference(cfg, pool, N_BATCHES, BATCH)
    low = mod.reference(cfg, pool, N_BATCHES, BATCH,
                        acc_dtype=ml_dtypes.bfloat16)
    assert exact["value"].max() > 256                # beyond bfloat16's 8 bits
    assert np.count_nonzero(low["value"] != exact["value"]) > 50
    other, other_cfg = load_config("ysb")
    assert np.array_equal(
        other.reference(other_cfg, pool, N_BATCHES, BATCH)["value"],
        exact["value"])
    assert (mod.min_bytes_per_batch(cfg, 1 << 20)
            == other.min_bytes_per_batch(other_cfg, 1 << 20))


def test_both_ysb_engines_deliver_the_same_results():
    pool = make_pool(23)
    _, kf = run_config("ysb", pool)
    _, wmr = run_config("ysb_wmr", pool)
    assert len(kf) == len(wmr) == len(set(wmr))
    assert set(kf) == set(wmr)


def test_budgets_come_from_the_deployment():
    mod, cfg = load_config("ysb_wmr")
    with open(os.path.join(BENCH, "configs", "ysb_wmr.json")) as f:
        published = json.load(f)
    slots, max_wins = mod.engine_budgets(published, 1 << 20)
    # a window's 3,333 views and a batch's 3,495, with room: not 2 x batch
    assert 6829 < slots <= 8192 and max_wins == 200
    window = mod.build_ops(published, 1 << 20)[-1]
    assert isinstance(window, Win_MapReduce) and window.M == 4
    # the insert moves the rings as 712 rows of 2,048 slots a table a batch
    # (100 head rows, 512 + 100 body rows) in place of 1,048,576 lanes
    window.bind_geometry(1 << 20)               # as the compiled chain does
    assert window.stage_counters() == {
        "archive_slots": 8192, "fired_window_budget": 200,
        "archive_run_len": 2048, "archive_run_rows": 712,
        # the 612 body rows and the 200 fired windows find their campaign by
        # comparison with all 100 (PR 37)
        "owner_compare_cells": (612 + 200) * 100}
    assert window.engine.A * window.num_keys * 4 * 4 < 14e6   # four tables


def test_a_ring_too_small_is_counted_and_fails_the_checks(monkeypatch):
    mod, cfg = load_config("ysb_wmr")
    pool = make_pool(31)
    monkeypatch.setattr(mod, "engine_budgets", lambda cfg, batch: (64, 100))
    ops = mod.build_ops(cfg, BATCH)
    assert ops[-1].engine.A == 64           # a window holds about 333 views
    ops, got = run_config("ysb_wmr", pool, ops=ops)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    grid = as_grid(got, want["value"].shape)
    lost = int((want["value"] - grid).sum())
    assert lost > 0 and (grid <= want["value"]).all()       # it did miscount
    checks = mod.program_checks(cfg, ops)
    assert checks["archive_overwrites"] == (lost, 0)        # and says so
    assert checks["old_drops"] == (0, 0)


def run_engine(op, keys, ts, batch):
    """``op`` alone behind a host source of (key, ts) tuples with ``v`` = 1."""
    keys, ts = np.asarray(keys), np.asarray(ts)
    src = wf.Source(lambda i: {"v": jnp.ones_like(i)}, total=len(keys),
                    num_keys=int(keys.max()) + 1,
                    key_fn=lambda i: jnp.asarray(keys)[i],
                    ts_fn=lambda i: jnp.asarray(ts)[i])
    got = []

    def deliver(view):
        if view is not None:
            got.extend(zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()))
    wf.Pipeline(src, [op], wf.Sink(deliver), batch_size=batch).run()
    return got


@pytest.mark.parametrize("pattern", ["win_seq", "win_mapreduce"])
def test_eos_flush_delivers_more_open_windows_than_the_budget(pattern):
    """16 keys, each with windows 0 and 9 open at EOS and eight empty ones
    between them (a lateness that keeps everything open), a budget of 4: the
    flush goes on past batches of empty windows and delivers all 32."""
    K, L = 16, 10
    keys = np.tile(np.arange(K), 4)
    ts = np.concatenate([np.full(2 * K, 3), np.full(2 * K, 95)])
    spec = WindowSpec(L, L, win_type_t.TB, delay=1000)
    kw = dict(num_keys=K, max_wins=4, tb_capacity=8)
    if pattern == "win_seq":
        op = Win_Seq(lambda wid, it: it.size(), spec, **kw)
    else:
        op = Win_MapReduce(lambda wid, it: it.size(), lambda wid, it: it.sum(),
                           spec, map_parallelism=2, **kw)
    got = run_engine(op, keys, ts, batch=32)
    assert got == [(k, w, 2) for k in range(K) for w in (0, 9)]
    # two batches of 32 lanes, two a key: a head row a key a batch, a key's
    # whole ring of 8 slots (20 rows a pass cost less than 32 of 2 slots),
    # the payload, id and ts of a row in one gather
    assert op.stage_counters() == {
        "archive_slots": 8, "fired_window_budget": 4, "archive_overwrites": 0,
        "old_drops": 0, "windows_undelivered_at_eos": 0,
        "archive_run_len": 8, "archive_run_rows": 36, "archive_run_groups": 1,
        "archive_runs_written": 32, "owner_compare_cells": (20 + 4) * K}


def test_old_drops_and_overwrites_are_counted_on_the_device():
    # key 0: ts 0..39 in order, window 10; then three stragglers behind the
    # horizon. The ring of 16 holds a window (10) and a batch of 5.
    ts = list(range(40)) + [1, 2, 3] + [40, 41]
    op = Win_Seq(lambda wid, it: it.size(), WindowSpec(10, 10, win_type_t.TB),
                 num_keys=1, max_wins=8, tb_capacity=16)
    got = run_engine(op, np.zeros(len(ts), np.int64), ts, batch=5)
    assert got == [(0, w, 10) for w in range(4)] + [(0, 4, 2)]
    counters = op.stage_counters()
    assert counters["old_drops"] == 3 and counters["archive_overwrites"] == 0
    # the same stream into a ring of 8: a window no longer fits
    small = Win_Seq(lambda wid, it: it.size(),
                    WindowSpec(10, 10, win_type_t.TB),
                    num_keys=1, max_wins=8, tb_capacity=8)
    got = run_engine(small, np.zeros(len(ts), np.int64), ts, batch=5)
    lost = 42 - sum(v for _, _, v in got)
    assert lost > 0
    assert small.stage_counters()["archive_overwrites"] == lost


def chain_step(cfg, mod, batch_capacity):
    ops = mod.build_ops(cfg, batch_capacity)
    src = wf.RecordSource(lambda: iter(()), mod.RECORD,
                          key_field=mod.KEY_FIELD, ts_field=mod.TS_FIELD)
    chain = CompiledChain(ops, src.payload_spec(),
                          batch_capacity=batch_capacity)
    batch = Batch.empty(batch_capacity, chain.specs[0])
    return ops, chain._step_fn(0), (tuple(chain.states), batch)


def test_lowered_step_carries_the_engine_phases():
    mod, cfg = load_config("ysb_wmr")
    ops, step, args = chain_step(cfg, mod, BATCH)
    hlo = step.lower(*args).as_text(debug_info=True)
    window = ops[-1].scope_name()
    assert window == "Win_MapReduce:ysb_window_wmr"
    # (a scope opened under ``vmap`` is recorded as ``vmap(<scope>)``)
    for sub in ("insert/rank", "insert/rank/sort", "insert/rank/runs",
                "insert/count", "insert/write", "emit/range",
                "emit/gather", "emit/vmap(map)", "emit/vmap(reduce)"):
        assert f"/{window}/{sub}/" in hlo, sub
    # the inner engine opens no operator scope of its own: the readers take
    # the phase from the element right after the first ``Class:name``
    assert "Win_Seq:" not in hlo
    path = f"jit(step)/{window}/insert/write/scatter"
    assert span_reduce.scope_of(path + ":scatter") == (path, window, "insert")
    path = f"jit(step)/{window}/emit/vmap(map)/vmap()/reduce_sum"
    assert span_reduce.scope_of(path + ":reduce")[1:] == (window, "emit")


@pytest.mark.parametrize("name", ["ysb", "kcb"])
def test_the_other_cells_step_programs_do_not_reach_the_archive_engine(name):
    """``ysb`` and ``kcb`` window through ``Key_FFAT``: nothing in their
    lowered step comes from ``win_seq.py`` or from ``ops/segment.py`` at or
    below ``take_windows`` and its prices, so a change there leaves their
    programs, and the compile cache's keys for them (which take each
    operation's source line), as they were."""
    import inspect
    import re
    from windflow_tpu.ops import segment
    # (a library function jitted on its own, ``jnp.searchsorted`` say, keeps
    # the call site it was first traced from: start as the cell's process does)
    jax.clear_caches()
    mod, cfg = load_config(name)
    _, step, args = chain_step(cfg, mod, 8192)
    text = step.lower(*args).as_text(debug_info=True)
    assert "win_seqffat.py" in text                    # the locations are there
    assert "win_seq.py" not in text and "take_windows" not in text
    above, first = inspect.getsourcelines(segment.range_max)
    fence = first + len(above)              # what follows range_max moved
    lines = [int(n) for n in re.findall(r'ops/segment\.py":(\d+)', text)]
    assert (name == "kcb") == bool(lines)        # its run folds live above
    assert all(n < fence for n in lines), (max(lines), fence)


def equations(jaxpr, scope=""):
    """(equation, its whole scope path) through every nested jaxpr."""
    for eqn in jaxpr.eqns:
        own = str(eqn.source_info.name_stack)
        path = "/".join(p for p in (scope, own) if p)
        yield eqn, path
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from equations(sub, path)


def test_the_insert_at_the_published_size_moves_rows_not_lanes():
    """C = 1,048,576, K = 100, A = 8,192 as the cell builds them: under
    ``insert`` no scatter and no gather has an index for every lane (the
    largest has one for each of the 612 body rows), the sort is the only
    operation of its kind, and the phase still sits right under the pattern's
    scope."""
    mod, _ = load_config("ysb_wmr")
    with open(os.path.join(BENCH, "configs", "ysb_wmr.json")) as f:
        published = json.load(f)
    C = 1 << 20
    ops, step, args = chain_step(published, mod, C)
    window = ops[-1].scope_name()
    assert (ops[-1].num_keys, ops[-1].engine.A) == (100, 8192)
    insert = [(eqn, path) for eqn, path in
              equations(jax.make_jaxpr(step)(*args).jaxpr)
              if f"{window}/insert" in path]
    assert all(path.startswith(f"{window}/insert") for _, path in insert)
    moves = [(eqn.primitive.name, eqn.invars[1].aval.shape[0], path)
             for eqn, path in insert
             if eqn.primitive.name.startswith(("scatter", "gather"))]
    assert len(moves) > 20
    assert max(n for _, n, _ in moves) == 612, sorted(moves)[-3:]
    assert {name for name, _, path in moves if "/write" in path} == {
        "gather", "scatter"}
    # cmp, id and ts ride one buffer: a gather of row windows a pass, not one
    # a column, and the engine says so
    takes = [eqn.invars[1].aval.shape[0] for eqn, path in insert
             if eqn.primitive.name == "gather"
             and eqn.invars[0].aval.shape == (3, C + 2 * 2048)]
    assert sorted(takes) == [100, 612], sorted(moves)
    assert ops[-1].engine._budget_gauges() == {
        "archive_slots": 8192, "fired_window_budget": 200,
        "archive_run_len": 2048, "archive_run_rows": 712,
        "archive_run_groups": 1, "owner_compare_cells": (612 + 200) * 100}
    sorts = [path for eqn, path in insert if eqn.primitive.name == "sort"]
    assert sorts == [f"{window}/insert/rank/sort"]
    assert not [eqn.primitive.name for eqn, _ in insert
                if eqn.primitive.name.startswith("cum")
                and eqn.invars[0].aval.shape[0] >= C]


@pytest.mark.parametrize("name,phase", [("archive_insert_device_ms", "insert"),
                                        ("window_fire_device_ms", "emit")])
def test_new_readers_read_the_phases_and_nothing_without_scopes(name, phase):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    testdata = os.path.join(BENCH, "testdata")
    with open(os.path.join(testdata, "expected_spans.json")) as f:
        slice_batches = json.load(f)["slice_batches"]
    scoped = {"trace_path": os.path.join(testdata, "kcb_spans.xplane.pb"),
              "slice_batches": slice_batches}
    assert reader.read(scoped) == span_reduce.window_ms(dict(scoped), phase) > 0
    unscoped = {"trace_path": os.path.join(testdata, "ysb_slice.xplane.pb"),
                "slice_batches": slice_batches}
    assert reader.read(unscoped) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert declared[name]["workloads"] == ["ysb_wmr.backlog"]
    assert declared[name]["moves"] == "tuples_per_s"


def test_rehearsal_of_the_cell_exits_zero(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearsal",
         "--workload", "ysb_wmr.backlog", "--seed", "2700000033",
         "--seconds", "1", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""                        # a rehearsal prints no metric
    last = json.loads(proc.stderr.splitlines()[-1].split(" ", 1)[1])
    assert last["correct"] is True and last["failed"] == 0
    assert {"archive_overwrites", "old_drops", "windows_undelivered_at_eos",
            "window_not_wmr_over_win_seq"} < set(last["compared"])
