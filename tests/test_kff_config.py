"""Configuration ``kff`` (the keyed time-based sliding-window sum through
``Key_FFAT``: each value lifted and folded into a ``[K, P]`` ring of pane
partials, a fired window the sum of its 64 panes) at rehearsal sizes on the
CPU, and what the published size made the global-time path of
``Win_SeqFFAT`` grow: budgets and counters published for time-based specs, a
count of the lanes that overran the ring, an EOS flush that goes on until no
window is open, the scopes below ``insert`` / ``emit`` that the benchmark's
three readers find the phases by (the cells' step programs are fenced in
``test_step_programs.py``)."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_keyed_pane_fold import scatters
from test_ysb_wmr_config import (BATCH, BENCH, ROOT, as_grid, chain_step,
                                 equations, load_config, run_config,
                                 run_engine)
from windflow_tpu.basic import win_type_t
from windflow_tpu.observability import names
from windflow_tpu.observability.names import STAGE_COUNTERS, STAGE_GAUGES
from windflow_tpu.operators.win_patterns import Key_FFAT
from windflow_tpu.operators.win_seqffat import Win_SeqFFAT
from windflow_tpu.operators.window import WindowSpec

import span_reduce  # noqa: E402 - test_ysb_wmr_config put benchmark/ on the path

N_BATCHES = 9           # 147,456 ticks: 576 windows a key, the last 64 partial


def published_config():
    """(module, kff.json without its rehearsal overrides)."""
    mod, _ = load_config("kff")
    with open(os.path.join(BENCH, "configs", "kff.json")) as f:
        return mod, json.load(f)


def make_pool(seed, n_batches=N_BATCHES, name="kff"):
    mod, cfg = load_config(name)
    return mod.make_pool(cfg, np.random.default_rng(seed), BATCH, n_batches)


@pytest.mark.parametrize("seed", [11, 2_700_000_019, 3_400_000_007])
def test_served_path_equals_the_reference_eos_flush_included(seed):
    mod, cfg = load_config("kff")
    pool = make_pool(seed)
    ops, got = run_config("kff", pool)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    cells = [(k, w) for k, w, _ in got]
    assert len(set(cells)) == len(cells)                    # each once
    assert set(cells) == set(zip(*np.nonzero(want["must_deliver"])))
    assert want["value"].shape == (8, 576)
    assert np.array_equal(as_grid(got, want["value"].shape), want["value"])
    # the 64 windows a key that the stream's end cuts short came with the
    # flush, smaller each than the one before
    tail = want["value"][:, -64:]
    assert (np.diff(tail, axis=1) <= 0).all() and (tail[:, -1] > 0).all()
    # per key in ascending window order, across the EOS flush
    last = {}
    for k, w in cells:
        assert w > last.get(k, -1)
        last[k] = w
    checks = mod.program_checks(cfg, ops)
    assert set(mod.ENGINE_COUNTERS) < set(checks)
    assert all(v == 0 and limit == 0 for v, limit in checks.values()), checks
    # straight from the stamped records: the last whole window of key 3
    recs = np.concatenate(pool)
    w = 576 - 65
    inside = ((recs["key"] == 3) & (recs["ts"] >= w * cfg["slide"])
              & (recs["ts"] < w * cfg["slide"] + cfg["win_len"]))
    assert want["value"][3, w] == recs["value"][inside].sum()
    assert want["last_batch"][3, w] == recs["ts"][inside].max() // BATCH


def test_reference_in_bfloat16_is_not_the_reference():
    import ml_dtypes
    mod, cfg = load_config("kff")
    pool = make_pool(5)
    exact = mod.reference(cfg, pool, N_BATCHES, BATCH)
    low = mod.reference(cfg, pool, N_BATCHES, BATCH,
                        acc_dtype=ml_dtypes.bfloat16)
    assert exact["value"].max() > 256                # beyond bfloat16's 8 bits
    assert np.count_nonzero(low["value"] != exact["value"]) > 4000
    # the needed bytes at the published size: three columns of a batch, 32,768
    # pane partials (sum and count) out and in, 32,768 window results out
    _, published = published_config()
    assert mod.min_bytes_per_batch(published, 1 << 20) == (
        3 * 4 * (1 << 20) + 2 * 32768 * 8 + 32768 * 16)


def test_the_two_references_agree_at_kpfs_window():
    """``kff.py::reference`` and ``kpf.py::reference`` were written apart;
    asked ``kpf``'s window (16 panes) they give the same grid cell for cell."""
    kff, _ = load_config("kff")
    kpf, cfg = load_config("kpf")
    pool = make_pool(17, name="kpf")
    ours = kff.reference(cfg, pool, N_BATCHES, BATCH)
    theirs = kpf.reference(cfg, pool, N_BATCHES, BATCH)
    assert ours["value"].shape == (8, 144)
    for part in ("value", "last_batch", "must_deliver"):
        assert np.array_equal(ours[part], theirs[part]), part


def test_key_ffat_and_pane_farm_deliver_the_same_results():
    """The same stream and window through the system's two window engines:
    pane partials in a ring (``Win_SeqFFAT``), and tuple archives under
    ``Pane_Farm`` (two ``Win_Seq``)."""
    _, cfg = load_config("kff")
    kpf, _ = load_config("kpf")
    pool = make_pool(23)
    _, ffat = run_config("kff", pool)
    ops, panes = run_config("kpf", pool, ops=kpf.build_ops(cfg, BATCH))
    assert (ops[-1].wpanes, ops[-1].spanes) == (64, 1)
    assert len(ffat) == len(panes) == len(set(panes)) == 8 * 576
    assert set(ffat) == set(panes)


def test_budgets_come_from_the_deployment():
    mod, published = published_config()
    assert published["reduced"] == [] and "rehearsal" in published
    assert (published["n_keys"], published["win_len"], published["slide"]) \
        == (512, 1 << 20, 1 << 14)
    # a window's 64 panes, a batch's 64 and the one left open; 65 windows a key
    assert mod.engine_budgets(published, 1 << 20) == (64 + 64 + 1, 65)
    window = mod.build_ops(published, 1 << 20)[-1]
    assert type(window) is Key_FFAT and window.global_time
    assert (window.pane_len, window.wpanes, window.spanes) == (1 << 14, 64, 1)
    window.bind_geometry(1 << 20)               # as the compiled chain does
    assert window.stage_counters() == {
        "ffat_keys": 512, "ffat_pane_slots": 256, "fired_window_budget": 65}
    assert set(window.stage_counters()) <= set(STAGE_GAUGES)
    assert window.out_capacity(1 << 20) == 512 * 65 == 33_280
    # two int32 tables: 1 MB, and the windows' panes come out of them by a
    # roll and a static take (the fired windows' span fits the ring)
    assert 2 * 512 * window.P * 4 == 1 << 20
    assert (window._resolve_w(1 << 20) - 1) * window.spanes + window.wpanes \
        <= window.P
    # without budgets the ring allows 192 windows a key: 98,304 lanes out
    default = Key_FFAT(lambda t: t.value, jnp.add,
                       spec=WindowSpec(1 << 20, 1 << 14, win_type_t.TB),
                       num_keys=512)
    default.bind_geometry(1 << 20)
    assert default.stage_counters()["fired_window_budget"] == 192
    assert default.out_capacity(1 << 20) == 98_304
    # the checks want the lift seen (the value fold, not the count histogram)
    # and the deployment's two budgets
    ops, step, args = chain_step(published, mod, 1 << 20)
    jax.eval_shape(step, *args)
    assert ops[-1].count_lift is False
    assert mod.structure_checks(published, ops[-1]) == {
        "window_not_key_ffat_value_fold_on_global_time": (0, 0),
        "engine_budgets_not_the_deployments": (0, 0)}
    for other in (
            Key_FFAT(lambda t: t.value, jnp.add, num_keys=512,
                     spec=WindowSpec(1 << 20, 1 << 14, win_type_t.TB),
                     pane_capacity=512, max_wins=65),
            Key_FFAT(lambda t: t.value, jnp.add, num_keys=512,
                     spec=WindowSpec(1 << 20, 1 << 14, win_type_t.TB),
                     pane_capacity=129, max_wins=64)):
        other.bind_geometry(1 << 20)
        other.count_lift = False
        assert mod.structure_checks(published, other) == {
            "window_not_key_ffat_value_fold_on_global_time": (0, 0),
            "engine_budgets_not_the_deployments": (1, 0)}
    per_key = Key_FFAT(lambda t: t.value, jnp.add, num_keys=512,
                       spec=WindowSpec(1 << 20, 1 << 14, win_type_t.TB),
                       pane_capacity=129, max_wins=65, global_time=False)
    per_key.bind_geometry(1 << 20)
    assert mod.structure_checks(published, per_key)[
        "window_not_key_ffat_value_fold_on_global_time"] == (1, 0)


def test_a_program_without_the_counters_is_refused_at_import(monkeypatch):
    """What the parent commit gives this PR's benchmark files: ``kff.py``
    raises while it is imported, before the runtime starts."""
    monkeypatch.setattr(names, "STAGE_COUNTERS", tuple(
        c for c in STAGE_COUNTERS if c != "ffat_ring_overruns"))
    with pytest.raises(RuntimeError, match="ffat_ring_overruns"):
        load_config("kff")


def test_a_ring_too_small_is_counted_and_fails_the_checks(monkeypatch):
    """A ring of 64 slots where a window's 64 panes and a batch's 64 need 128:
    from the second batch on every lane lands in the slot of a pane that has
    not fired, the sums come out wrong, and the counter says how many."""
    mod, cfg = load_config("kff")
    pool = make_pool(31)
    assert mod.engine_budgets(cfg, BATCH) == (129, 65)
    monkeypatch.setattr(mod, "engine_budgets", lambda cfg, batch: (64, 65))
    ops = mod.build_ops(cfg, BATCH)
    assert ops[-1].P == 64
    ops, got = run_config("kff", pool, ops=ops)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    assert not np.array_equal(as_grid(got, want["value"].shape),
                              want["value"])
    checks = mod.program_checks(cfg, ops)
    assert checks["ffat_ring_overruns"] == ((N_BATCHES - 1) * BATCH, 0)
    assert checks["old_drops"] == (0, 0)
    # a ring of exactly the 128 is not overrun
    monkeypatch.setattr(mod, "engine_budgets", lambda cfg, batch: (128, 65))
    ops, got = run_config("kff", pool, ops=mod.build_ops(cfg, BATCH))
    assert ops[-1].stage_counters()["ffat_ring_overruns"] == 0
    assert np.array_equal(as_grid(got, want["value"].shape), want["value"])


@pytest.mark.parametrize("global_time", [True, False])
def test_eos_flush_goes_on_past_empty_windows_until_none_is_open(global_time):
    """16 keys, each with window 0 (tick 3) and windows 6 to 9 (tick 95) open
    at EOS and five empty ones between (a lateness that keeps every window
    open), a fired budget of 2 a key (32 in all on the per-key path): the
    second pass of the global-time flush holds windows 2 and 3 alone, which
    have no tuple; it is passed over, not taken for the end, and the flush
    says that it left nothing."""
    K = 16
    keys = np.tile(np.arange(K), 4)
    ts = np.concatenate([np.full(2 * K, 3), np.full(2 * K, 95)])
    op = Key_FFAT(lambda t: t.v, jnp.add,
                  spec=WindowSpec(40, 10, win_type_t.TB, delay=1000),
                  num_keys=K, pane_capacity=16, global_time=global_time,
                  max_wins=2 if global_time else 2 * K)
    got = [r for r in run_engine(op, keys, ts, batch=32) if r[2]]
    assert sorted(got) == [(k, w, 2) for k in range(K)
                           for w in (0, 6, 7, 8, 9)]
    last = {}
    for k, w, _ in got:
        assert w > last.get(k, -1)
        last[k] = w
    counters = op.stage_counters()
    assert set(counters) <= set(STAGE_COUNTERS) | set(STAGE_GAUGES)
    assert counters == {
        "ffat_keys": K, "ffat_pane_slots": 16,
        "fired_window_budget": 2 if global_time else 2 * K, "old_drops": 0,
        "windows_undelivered_at_eos": 0,
        # the global-time path lists no rows; the per-key one finds the key
        # of its 32 fired windows by comparison with all 16 (PR 37)
        # (a delay publishes the late lanes, none here; every batch's counts
        # take keyed_pane_fold, and a batch of 32 lanes, no whole chunk, its
        # scatters); the per-key path counts its ring overruns too, and how
        # far the keys' clocks lie apart (every key's last tick is 95)
        **({"ffat_ring_overruns": 0, "ffat_late_lanes": 0,
            "ffat_fold_fallbacks": 2, "ffat_fold_partials": 0,
            "ffat_fold_spill_lanes": 0} if global_time
           else {"owner_compare_cells": 2 * K * K, "ffat_ring_overruns": 0,
                 "ffat_key_clock_spread": 0,
                 # 4 panes a window in a ring of 16: whole rows
                 "ffat_emit_row_lanes": 2 * K * 16})}
    assert op.get_StatsRecords()[0].tuples_dropped_old == 0


def test_a_key_with_a_gap_in_its_ticks_does_not_end_the_flush_early():
    """One key of two falls silent for six windows' worth of ticks while the
    other goes on: the silent key's late windows and the gap's empty ones
    share the flush's passes, and every window with a tuple arrives."""
    ts = np.concatenate([np.arange(0, 20), np.arange(80, 100)])
    keys = np.concatenate([np.zeros(20, np.int64), np.ones(20, np.int64)])
    op = Win_SeqFFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(10, 10, win_type_t.TB, delay=1000),
                     num_keys=2, pane_capacity=16, max_wins=4)
    got = run_engine(op, keys, ts, batch=8)
    assert sorted(got) == [(0, 0, 10), (0, 1, 10), (1, 8, 10), (1, 9, 10)]
    assert op.stage_counters()["windows_undelivered_at_eos"] == 0


def test_counters_of_the_count_lift_and_of_count_based_windows():
    """A windowed count folds no value by slot: the overrun count stays out
    of what it publishes (absent, not 0), and its counts take
    ``keyed_pane_fold``, whose branches it counts (batches of 8 lanes, no
    whole chunk: the scatters); a count-based window publishes its run
    budget and, after the flush, what the flush left."""
    count = Key_FFAT(lambda t: 1, jnp.add,
                     spec=WindowSpec(10, 10, win_type_t.TB), num_keys=2,
                     pane_capacity=16, max_wins=4)
    got = run_engine(count, [0, 1] * 20, np.repeat(np.arange(20), 2), batch=8)
    assert sorted(got) == [(k, w, 10) for k in (0, 1) for w in (0, 1)]
    assert count.count_lift is True
    assert count.stage_counters() == {
        "ffat_keys": 2, "ffat_pane_slots": 16, "fired_window_budget": 4,
        "old_drops": 0, "windows_undelivered_at_eos": 0,
        "ffat_fold_fallbacks": 5, "ffat_fold_partials": 0,
        "ffat_fold_spill_lanes": 0}
    cb = Key_FFAT(lambda t: t.v, jnp.add, spec=WindowSpec(8, 4), num_keys=2)
    got = run_engine(cb, [0, 1] * 20, np.arange(40), batch=8)
    assert len(got) == 2 * 5 and {v for _, _, v in got} == {8, 4}
    counters = cb.stage_counters()
    assert counters["windows_undelivered_at_eos"] == 0
    assert "fired_window_budget" not in counters
    assert {"ffat_run_budget", "ffat_keys", "ffat_pane_slots",
            "old_drops"} < set(counters)


NEW_SCOPES = ("insert/hist", "insert/fold", "emit/gather", "emit/reduce",
              "emit/clear")


def test_lowered_step_and_flush_carry_the_five_new_scopes():
    mod, cfg = load_config("kff")
    ops, step, args = chain_step(cfg, mod, BATCH)
    hlo = step.lower(*args).as_text(debug_info=True)
    window = ops[-1].scope_name()
    assert window == "Key_FFAT:kff_window"
    for sub in NEW_SCOPES:
        assert f"/{window}/{sub}/" in hlo, sub
    # the segment fold's scatter lies under insert/fold (since PR 35 in the
    # fallback branch of the cond that the contraction's dots share with it,
    # the counts' included: ISSUE 35 puts the one contraction under fold and
    # leaves insert/hist the add into ``cnt``, so the dots this line looked
    # for under insert/hist are under insert/fold now), and nothing of the
    # engine under a phase alone but index arithmetic and the out batch
    assert re.search(rf'/{window}/insert/fold/[^"]*scatter', hlo)
    assert re.search(rf'/{window}/insert/fold/[^"]*dot_general', hlo)
    assert re.search(rf'/{window}/insert/hist/add"', hlo)
    assert not re.search(rf'/{window}/insert/hist/[^"]*(dot_general|cond)', hlo)
    assert not re.search(
        rf'/{window}/(insert|emit)/(scatter[\w-]*|gather|dot_general)"', hlo)
    path = f"jit(step)/{window}/insert/fold/scatter-add"
    assert span_reduce.scope_of(path + ":scatter") == (path, window, "insert")
    # the EOS flush program: the same three scopes under emit
    state = args[0][-1]
    ops[-1].flush(state)
    text = ops[-1]._flush_jit.lower(state).as_text(debug_info=True)
    for sub in NEW_SCOPES[2:]:
        assert f"/{window}/{sub}/" in text, sub


@pytest.mark.parametrize("name,want", [("ffat_insert_device_ms", 2.75),
                                       ("ffat_fold_device_ms", 2.5),
                                       ("ffat_emit_device_ms", 0.375)])
def test_new_readers_read_their_scope_and_nothing_without_it(name, want):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    window = "jit(step)/Key_FFAT:kff_window"
    ops = [{"scope": f"{window}/insert/fold/scatter-add", "ns": 8e6},
           {"scope": f"{window}/insert/fold/jit(lift)/convert", "ns": 2e6},
           {"scope": f"{window}/insert/hist/dot_general", "ns": 1e6},
           {"scope": f"{window}/emit/gather/gather", "ns": 1e6},
           {"scope": f"{window}/emit/clear/select_n", "ns": 0.5e6},
           {"scope": "jit(step)/BatchMap:m/fold/insert/mul", "ns": 64e6},
           {"scope": "jit(step)/fold/insert/mul", "ns": 32e6},
           {"scope": None, "ns": 16e6}]
    run = {"trace_path": "no file is read", "slice_batches": 4,
           "span_reduce": {"device_ops": ops}}
    assert reader.read(run) == want
    # traces of programs with no such scope (kcb's insert has a fold, which
    # the fold reader finds: the testdata's kcb trace predates it), and with
    # no scopes at all
    testdata = os.path.join(BENCH, "testdata")
    with open(os.path.join(testdata, "expected_spans.json")) as f:
        slice_batches = json.load(f)["slice_batches"]
    assert reader.read({
        "trace_path": os.path.join(testdata, "ysb_slice.xplane.pb"),
        "slice_batches": slice_batches}) is None
    assert reader.read({"trace_path": None, "slice_batches": 0}) is None
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert declared[name] == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "compiled chain + operators",
        "moves": "tuples_per_s", "workloads": ["kff.backlog"]}


def test_rehearsal_of_the_new_cell_exits_zero(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearsal",
         "--workload", "kff.backlog", "--seed", "3400000033",
         "--seconds", "1", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""                        # a rehearsal prints no metric
    last = json.loads(proc.stderr.splitlines()[-1].split(" ", 1)[1])
    assert last["correct"] is True and last["failed"] == 0
    assert {"ffat_ring_overruns", "old_drops", "windows_undelivered_at_eos",
            "window_not_key_ffat_value_fold_on_global_time",
            "engine_budgets_not_the_deployments"} < set(last["compared"])


@pytest.mark.parametrize("name", ["kcb", "kpf"])
def test_no_loop_is_left_over_the_rows_a_step_lists(name):
    """At rehearsal size: where the engines list their rows (``Win_Seq``'s
    ``insert/rank/runs`` and ``emit/range``, ``Win_SeqFFAT``'s
    ``insert/rank/runs`` and ``emit``) no ``while`` and no ``scan`` carries
    an operand as long as a list (the binary search's rounds over R runs or
    W fired windows); what loops there still is the search of the K + 1 key
    edges among the sorted lanes."""
    mod, cfg = load_config(name)
    ops, step, args = chain_step(cfg, mod, BATCH)
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    window = ops[-1]
    if name == "kpf":
        lists = {n for _, e in window.engines() for n in (e.run_rows, e._w)}
        # fired panes and windows, the PLQ's body rows, the WLQ's
        assert lists == {136, 16 + 8, 2 + 8}, lists
    else:
        lists = {window._run_budget, window._w}
        assert lists == {BATCH // cfg["slide"] + 2 * cfg["n_keys"],
                         BATCH // cfg["slide"] + 64}, lists
    K = cfg["n_keys"]
    loops = [(path, sorted({v.aval.shape[0] for v in eqn.invars
                            if v.aval.shape}))
             for eqn, path in equations(jaxpr)
             if eqn.primitive.name in ("while", "scan")
             and re.search(r"/(insert/rank/runs|emit)(/|$)", path)]
    assert all(not lists & set(sizes) for _, sizes in loops), loops
    # kcb's key edges are searched under insert/rank/runs (kpf's under
    # insert/rank/sort, in sort_segments): K + 1 queries into the lanes
    assert [sizes for _, sizes in loops] == (
        [[K + 1, BATCH]] if name == "kcb" else []), loops
    window.collect_stats(args[0][-1])
    cells = {k: v for k, v in window.stage_counters().items()
             if k.endswith("owner_compare_cells")}
    assert len(cells) == (2 if name == "kpf" else 1) and all(cells.values())


def test_kffs_step_scatters_nowhere_but_in_the_fallback_branch():
    """At rehearsal size: the step's only scatters are those of
    ``keyed_pane_fold``'s fallbacks (the whole batch's two, counts and
    values, and the partial branch's one of its compacted stragglers), all
    inside the first branch of the window insert's first ``cond``; the branch
    an in-order stream takes has the two dots and no scatter, and the served
    path never left it."""
    mod, cfg = load_config("kff")
    _, step, args = chain_step(cfg, mod, BATCH)
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    assert sum(e.primitive.name == "cond" for e, _ in equations(jaxpr)) == 2
    # branch 0 of the outer cond, its false side: the locality test failed
    assert list(scatters(jaxpr)) == [("scatter-add", 0)] * 3
    ops, _ = run_config("kff", make_pool(41, n_batches=3))
    counters = ops[-1].stage_counters()
    assert counters["ffat_fold_fallbacks"] == 0
    assert counters["ffat_fold_partials"] == 0
    assert counters["ffat_fold_spill_lanes"] == 0
    assert counters["ffat_ring_overruns"] == 0
