"""Configuration ``kpf`` (the keyed time-based sliding-window sum through
``Pane_Farm``: a PLQ ``Win_Seq`` over panes, a WLQ ``Win_Seq`` over their
results) at rehearsal sizes on the CPU, and what the published size made the
pattern grow: a budget for each stage, both engines' counters under stage
names, the ``plq`` / ``wlq`` scopes the benchmark's two readers find the
stages by, and a WLQ that sizes itself from the pane results a batch brings."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from test_ysb_wmr_config import (BATCH, BENCH, ROOT, as_grid, chain_step,
                                 load_config, run_config, run_engine)
from windflow_tpu.basic import win_type_t
from windflow_tpu.observability.names import STAGE_COUNTERS, STAGE_GAUGES
from windflow_tpu.operators.win_patterns import Pane_Farm
from windflow_tpu.operators.win_seq import Win_Seq
from windflow_tpu.operators.window import WindowSpec

import span_reduce  # noqa: E402 - test_ysb_wmr_config put benchmark/ on the path

N_BATCHES = 9           # 147,456 ticks: 144 windows a key, the last 16 partial


def published_config():
    """(module, kpf.json without its rehearsal overrides)."""
    mod, _ = load_config("kpf")
    with open(os.path.join(BENCH, "configs", "kpf.json")) as f:
        return mod, json.load(f)


def make_pool(seed, n_batches=N_BATCHES):
    mod, cfg = load_config("kpf")
    return mod.make_pool(cfg, np.random.default_rng(seed), BATCH, n_batches)


@pytest.mark.parametrize("seed", [11, 2_700_000_019, 3_000_000_007])
def test_served_path_equals_the_reference_eos_flush_included(seed):
    mod, cfg = load_config("kpf")
    pool = make_pool(seed)
    ops, got = run_config("kpf", pool)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    cells = [(k, w) for k, w, _ in got]
    assert len(set(cells)) == len(cells)                    # each once
    assert set(cells) == set(zip(*np.nonzero(want["must_deliver"])))
    assert want["value"].shape == (8, 144)
    assert np.array_equal(as_grid(got, want["value"].shape), want["value"])
    # the sixteen windows a key that the stream's end cuts short came with the
    # flush, smaller each than the one before
    tail = want["value"][:, -16:]
    assert (np.diff(tail, axis=1) <= 0).all() and (tail[:, -1] > 0).all()
    # per key in ascending window order, across the EOS flush
    last = {}
    for k, w in cells:
        assert w > last.get(k, -1)
        last[k] = w
    checks = mod.program_checks(cfg, ops)
    assert {f"{s}_{c}" for s in mod.STAGES
            for c in mod.ENGINE_COUNTERS} < set(checks)
    assert all(v == 0 and limit == 0 for v, limit in checks.values()), checks
    # the checks read what they read before PR 37: the six drop and EOS
    # counters and the two of the structure, not the new gauge, which the
    # stage publishes for both engines beside them (rehearsal: 8 keys)
    assert len(checks) == 8 and not any("owner" in c for c in checks)
    counters = ops[-1].stage_counters()
    assert counters["plq_owner_compare_cells"] == (24 + 136) * 8
    assert counters["wlq_owner_compare_cells"] == (10 + 136) * 8
    # straight from the stamped records: the last whole window of key 3
    recs = np.concatenate(pool)
    w = 144 - 17
    inside = ((recs["key"] == 3) & (recs["ts"] >= w * cfg["slide"])
              & (recs["ts"] < w * cfg["slide"] + cfg["win_len"]))
    assert want["value"][3, w] == recs["value"][inside].sum()
    assert want["last_batch"][3, w] == recs["ts"][inside].max() // BATCH


def test_reference_in_bfloat16_is_not_the_reference():
    import ml_dtypes
    mod, cfg = load_config("kpf")
    pool = make_pool(5)
    exact = mod.reference(cfg, pool, N_BATCHES, BATCH)
    low = mod.reference(cfg, pool, N_BATCHES, BATCH,
                        acc_dtype=ml_dtypes.bfloat16)
    assert exact["value"].max() > 256                # beyond bfloat16's 8 bits
    assert np.count_nonzero(low["value"] != exact["value"]) > 1000
    # the needed bytes at the published size: three columns of a batch, 8,192
    # pane results out and in, 8,192 window results out
    _, published = published_config()
    assert mod.min_bytes_per_batch(published, 1 << 20) == (
        3 * 4 * (1 << 20) + 2 * 8192 * 16 + 8192 * 16)


def test_pane_farm_and_a_plain_win_seq_deliver_the_same_results():
    mod, cfg = load_config("kpf")
    pool = make_pool(23)
    _, panes = run_config("kpf", pool)
    per_key = BATCH // cfg["n_keys"]
    plain = Win_Seq(lambda wid, it: it.sum("value"),
                    WindowSpec(cfg["win_len"], cfg["slide"], win_type_t.TB),
                    num_keys=cfg["n_keys"], name="kpf_plain",
                    tb_capacity=2 * per_key,            # a window and a batch
                    max_wins=cfg["n_keys"] * (BATCH // cfg["slide"] + 1))
    project = mod.build_ops(cfg, BATCH)[0]
    ops, whole = run_config("kpf", pool, ops=[project, plain])
    assert plain.stage_counters()["archive_overwrites"] == 0
    assert len(panes) == len(whole) == len(set(whole)) == 8 * 144
    assert set(panes) == set(whole)


def test_budgets_come_from_the_deployment():
    mod, published = published_config()
    assert published["reduced"] == [] and "rehearsal" in published
    assert (published["n_keys"], published["win_len"], published["slide"]) \
        == (512, 1 << 20, 1 << 16)
    budgets = mod.engine_budgets(published, 1 << 20)
    # an open pane's 128 tuples and a batch's 2,048; 17 panes a key; a
    # window's 16 pane results and a batch's 17; 17 windows a key
    assert budgets == (128 + 2048, 512 * 17, 16 + 17, 512 * 17)
    window = mod.build_ops(published, 1 << 20)[-1]
    assert isinstance(window, Pane_Farm)
    assert (window.pane_len, window.wpanes, window.spanes) == (1 << 16, 16, 1)
    window.bind_geometry(1 << 20)               # as the compiled chain does
    counters = window.stage_counters()
    assert {k: counters[k] for k in (
        "plq_archive_slots", "plq_fired_window_budget",
        "wlq_archive_slots", "wlq_fired_window_budget")} == {
        "plq_archive_slots": 4096, "plq_fired_window_budget": 8704,
        "wlq_archive_slots": 64, "wlq_fired_window_budget": 8704}
    assert window.out_capacity(1 << 20) == 8704
    # how a batch is cut: the PLQ moves 2,048 ring rows of 1,024 slots a table
    # (512 head rows, 1,024 + 512 body rows); the WLQ moves a key's whole ring
    # of 64 slots, 1,160 rows (512 + 136 + 512) where rows of 8 were 2,112
    assert {k: counters[k] for k in (
        "plq_archive_run_len", "plq_archive_run_rows",
        "wlq_archive_run_len", "wlq_archive_run_rows")} == {
        "plq_archive_run_len": 1024, "plq_archive_run_rows": 2048,
        "wlq_archive_run_len": 64, "wlq_archive_run_rows": 1160}
    # how a listed row finds its key: by comparison with all 512 (PR 37), the
    # insert's body rows and the fired windows of each engine
    assert {k: counters[k] for k in (
        "plq_owner_compare_cells", "wlq_owner_compare_cells")} == {
        "plq_owner_compare_cells": (1536 + 8704) * 512,
        "wlq_owner_compare_cells": (648 + 8704) * 512}
    # and once the step is traced: payload, id and ts of a row in one gather
    ops, step, args = chain_step(published, mod, 1 << 20)
    jax.eval_shape(step, *args)
    for _, engine in ops[-1].engines():
        assert engine._budget_gauges()["archive_run_groups"] == 1
    # four int32 tables a stage: 33.5 MB and 0.5 MB, not 17 GB
    assert window.plq.A * 512 * 4 * 4 == 33_554_432
    assert window.wlq.A * 512 * 4 * 4 == 524_288
    assert set(counters) <= set(STAGE_COUNTERS) | set(STAGE_GAUGES)
    assert mod.structure_checks(published, window) == {
        "engine_budgets_not_the_deployments": (0, 0),
        "window_not_pane_farm_over_two_win_seq": (0, 0)}
    other = Pane_Farm(lambda p, it: it.sum("value"), lambda w, it: it.sum(),
                      WindowSpec(1 << 20, 1 << 16, win_type_t.TB),
                      num_keys=512, plq_slots=4096, plq_max_wins=8704,
                      wlq_slots=128, wlq_max_wins=8704)
    other.bind_geometry(1 << 20)
    assert mod.structure_checks(published, other) == {
        "engine_budgets_not_the_deployments": (1, 0),
        "window_not_pane_farm_over_two_win_seq": (0, 0)}


@pytest.mark.parametrize("stage,budgets", [("plq", (64, 136, 33, 136)),
                                           ("wlq", (4096, 136, 8, 136))])
def test_a_ring_too_small_is_counted_and_fails_the_checks(monkeypatch, stage,
                                                          budgets):
    """A PLQ ring of 64 slots (a batch brings a key 2,048 tuples) and a WLQ
    ring of 8 (a window has 16 panes): the sums come out short, and the
    stage's own counter says by how much was lost."""
    mod, cfg = load_config("kpf")
    pool = make_pool(31)
    assert mod.engine_budgets(cfg, BATCH) == (2176, 136, 33, 136)
    monkeypatch.setattr(mod, "engine_budgets", lambda cfg, batch: budgets)
    ops = mod.build_ops(cfg, BATCH)
    assert (ops[-1].plq.A, ops[-1].wlq.A) == (budgets[0], 64 if stage == "plq"
                                              else 8)
    ops, got = run_config("kpf", pool, ops=ops)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    grid = as_grid(got, want["value"].shape)
    assert (grid <= want["value"]).all() and (grid < want["value"]).any()
    checks = mod.program_checks(cfg, ops)
    other = "wlq" if stage == "plq" else "plq"
    assert checks[f"{stage}_archive_overwrites"][0] > 0
    assert checks[f"{other}_archive_overwrites"] == (0, 0)
    assert checks["plq_old_drops"] == checks["wlq_old_drops"] == (0, 0)
    if stage == "plq":
        # a batch's 2,048 tuples a key but the last 64, and from the second
        # batch on the 64 slots of the pane the batch before left open
        assert checks["plq_archive_overwrites"][0] == (
            N_BATCHES * 8 * (2048 - 64) + (N_BATCHES - 1) * 8 * 64)


def test_eos_flush_delivers_more_open_panes_and_windows_than_the_budgets():
    """16 keys, each with panes 0 and 9 open at EOS and eight empty ones
    between (a lateness that keeps every pane open), four panes a window, both
    fired budgets 4: the PLQ's flush goes on past batches of empty panes, each
    of its batches passes through the WLQ, and the WLQ's flush delivers the
    rest: window 0 (pane 0) and windows 6 to 9 (pane 9) of every key."""
    K = 16
    keys = np.tile(np.arange(K), 4)
    ts = np.concatenate([np.full(2 * K, 3), np.full(2 * K, 95)])
    op = Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                   WindowSpec(40, 10, win_type_t.TB, delay=1000), num_keys=K,
                   plq_slots=8, plq_max_wins=4, wlq_slots=8, wlq_max_wins=4)
    got = run_engine(op, keys, ts, batch=32)
    assert sorted(got) == [(k, w, 2) for k in range(K)
                           for w in (0, 6, 7, 8, 9)]
    last = {}
    for k, w, _ in got:
        assert w > last.get(k, -1)
        last[k] = w
    counters = op.stage_counters()
    assert set(counters) <= set(STAGE_COUNTERS) | set(STAGE_GAUGES)
    for stage in ("plq", "wlq"):
        assert {n: counters[f"{stage}_{n}"] for n in (
            "archive_slots", "fired_window_budget", "archive_overwrites",
            "old_drops", "windows_undelivered_at_eos")} == {
            "archive_slots": 8, "fired_window_budget": 4,
            "archive_overwrites": 0, "old_drops": 0,
            "windows_undelivered_at_eos": 0}, stage
    assert op.drop_counters() == {}
    assert op.get_StatsRecords()[0].tuples_dropped_old == 0


def test_old_drops_are_counted_for_the_stage_they_happen_in():
    # key 0: ts 0..39 in order, pane 5, window 10; then three stragglers
    # behind the PLQ's horizon
    ts = list(range(40)) + [1, 2, 3] + [40, 41]
    op = Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                   WindowSpec(10, 5, win_type_t.TB), num_keys=1,
                   plq_slots=16, plq_max_wins=8, wlq_slots=8, wlq_max_wins=8)
    got = run_engine(op, np.zeros(len(ts), np.int64), ts, batch=5)
    assert got == [(0, w, 10) for w in range(7)] + [(0, 7, 7), (0, 8, 2)]
    counters = op.stage_counters()
    assert (counters["plq_old_drops"], counters["wlq_old_drops"]) == (3, 0)
    assert op.get_StatsRecords()[0].tuples_dropped_old == 3


@pytest.mark.parametrize("pattern", ["win_seq", "pane_farm"])
def test_windows_that_end_past_int32_hold_their_tuples(pattern):
    """The stream's last 64 ticks before 2^31, the published window: the 16
    windows (and the pane) over them end past int32's last tick. Their tuples
    are in them all the same, and a result's ts stops at that tick."""
    spec = WindowSpec(1 << 20, 1 << 16, win_type_t.TB)
    if pattern == "win_seq":
        op = Win_Seq(lambda wid, it: it.sum("v"), spec, num_keys=1,
                     tb_capacity=128, max_wins=32)
    else:
        op = Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                       spec, num_keys=1, plq_slots=128, plq_max_wins=32,
                       wlq_slots=64, wlq_max_wins=32)
    ts = np.arange(2 ** 31 - 64, 2 ** 31)
    got = run_engine(op, np.zeros(64, np.int64), ts, batch=16)
    assert got == [(0, w, 64) for w in range(2 ** 15 - 16, 2 ** 15)]


def test_default_budgets_follow_the_pane_results_a_batch_brings():
    """Without budgets the WLQ sizes itself from what the PLQ can emit a
    batch: the panes of a window plus all of a batch's pane results on one
    key (not twice the batch), and a fired window for every slide's worth of
    pane results; a count-based pane farm keeps the sizes it had."""
    fns = (lambda pid, it: it.sum("v"), lambda wid, it: it.sum())
    tb = Pane_Farm(*fns, WindowSpec(64, 16, win_type_t.TB), num_keys=4)
    tb.bind_geometry(4096)
    panes = tb.plq.out_capacity(4096)
    assert panes == 4096 // 16 + 64 == 320
    assert tb.wlq.A == 512                      # 4 + 320, not 2 x 320 -> 1,024
    assert tb.out_capacity(4096) == 320 + 64    # one window a pane result
    cb = Pane_Farm(*fns, WindowSpec(64, 16), num_keys=4)
    cb.bind_geometry(4096)
    assert (cb.plq.A, cb.wlq.A, cb.out_capacity(4096)) == (8192, 512, 384)
    # the engine's own keywords still reach the PLQ, but not beside the stage's
    assert Pane_Farm(*fns, WindowSpec(64, 16), max_wins=7).plq.max_wins == 7
    with pytest.raises(TypeError, match="given twice"):
        Pane_Farm(*fns, WindowSpec(64, 16), max_wins=7, plq_max_wins=7)
    # at the published size the defaults are refused, stage by stage, by name
    big = Pane_Farm(*fns, WindowSpec(1 << 20, 1 << 16, win_type_t.TB),
                    num_keys=512)
    with pytest.raises(ValueError, match="plq_max_wins="):
        big.bind_geometry(1 << 20)
    big = Pane_Farm(*fns, WindowSpec(1 << 20, 1 << 16, win_type_t.TB),
                    num_keys=512, plq_slots=2176, plq_max_wins=8704)
    big.bind_geometry(1 << 20)
    with pytest.raises(ValueError, match="wlq_max_wins="):
        big.out_capacity(1 << 20)


def test_lowered_step_and_flush_carry_both_stages_and_their_phases():
    mod, cfg = load_config("kpf")
    ops, step, args = chain_step(cfg, mod, BATCH)
    hlo = step.lower(*args).as_text(debug_info=True)
    window = ops[-1].scope_name()
    assert window == "Pane_Farm:kpf_window"
    for stage in ("plq", "wlq"):
        for sub in ("insert/rank/sort", "insert/rank/runs", "insert/count",
                    "insert/write", "emit/range", "emit/gather"):
            assert f"/{window}/{stage}/{sub}/" in hlo, (stage, sub)
    # the inner engines open no operator scope of their own
    assert "Win_Seq:" not in hlo
    path = f"jit(step)/{window}/plq/insert/rank/sort/sort"
    assert span_reduce.scope_of(path + ":sort")[:2] == (path, window)
    # the EOS flush: the PLQ's open panes under plq/emit, their pass through
    # the WLQ a compiled step under wlq, the WLQ's open windows under wlq/emit
    state = args[0][-1]
    ops[-1].flush(state)
    for stage, engine in ops[-1].engines():
        text = engine._flush_jit.lower(state[stage]).as_text(debug_info=True)
        assert f"/{window}/{stage}/emit/gather/" in text, stage
    panes = ops[-1].plq._flush_jit(state["plq"])[1]
    cascade = ops[-1]._cascade.lower(state["wlq"], panes).as_text(
        debug_info=True)
    assert f"/{window}/wlq/insert/write/" in cascade
    assert f"/{window}/wlq/emit/range/" in cascade


@pytest.mark.parametrize("name,stage", [("pane_plq_device_ms", "plq"),
                                        ("pane_wlq_device_ms", "wlq")])
def test_new_readers_read_their_stage_and_nothing_without_it(name, stage):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    window = "jit(step)/Pane_Farm:kpf_window"
    ops = [{"scope": f"{window}/plq/insert/rank/sort/sort", "ns": 8e6},
           {"scope": f"{window}/plq/emit/gather/gather", "ns": 2e6},
           {"scope": f"{window}/wlq/insert/write/scatter", "ns": 1e6},
           {"scope": "jit(step)/BatchMap:kpf_project/plq/mul", "ns": 64e6},
           {"scope": f"{window}/emit/gather", "ns": 32e6},
           {"scope": None, "ns": 16e6}]
    run = {"trace_path": "no file is read", "slice_batches": 4,
           "span_reduce": {"device_ops": ops}}
    assert reader.read(run) == {"plq": 2.5 + 16.0, "wlq": 0.25}[stage]
    # traces of programs with scopes but no such stage, and with no scopes
    testdata = os.path.join(BENCH, "testdata")
    with open(os.path.join(testdata, "expected_spans.json")) as f:
        slice_batches = json.load(f)["slice_batches"]
    for trace in ("kcb_spans.xplane.pb", "ysb_slice.xplane.pb"):
        assert reader.read({"trace_path": os.path.join(testdata, trace),
                            "slice_batches": slice_batches}) is None
    assert reader.read({"trace_path": None, "slice_batches": 0}) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert declared[name]["workloads"] == ["kpf.backlog"]
    assert declared[name]["moves"] == "tuples_per_s"
    assert declared[name]["layer"] == "compiled chain + operators"


def test_rehearsal_of_the_new_cell_exits_zero(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearsal",
         "--workload", "kpf.backlog", "--seed", "2700000033",
         "--seconds", "1", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""                        # a rehearsal prints no metric
    last = json.loads(proc.stderr.splitlines()[-1].split(" ", 1)[1])
    assert last["correct"] is True and last["failed"] == 0
    assert {"plq_archive_overwrites", "wlq_archive_overwrites",
            "plq_old_drops", "wlq_windows_undelivered_at_eos",
            "window_not_pane_farm_over_two_win_seq",
            "engine_budgets_not_the_deployments"} < set(last["compared"])
