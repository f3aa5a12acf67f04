"""Configuration ``kff_drawdown`` (each symbol's trailing maximum drawdown
through ``Key_FFAT``: a ``(hi, lo, dd)`` struct, a non-commutative combine
whose third field reads the other two, an identity of its own type, then ``Map(dd)``) at
rehearsal sizes on the CPU: the reference against brute force, the served path
against the reference, both program controls and the bfloat16 control failing,
the checks on the chain, the import refusal, the scopes of the ordered fold and
reduction and their readers (the cells' step programs are fenced in
``test_step_programs.py``)."""

import importlib.util
import json
import os
import subprocess
import sys

import jax
import ml_dtypes
import numpy as np
import pytest

import windflow_tpu as wf
from test_ysb_wmr_config import (BATCH, BENCH, ROOT, chain_step, load_config,
                                 run_config)
from windflow_tpu.observability import names
from windflow_tpu.observability.names import STAGE_COUNTERS, STAGE_GAUGES
from windflow_tpu.operators.win_patterns import Key_FFAT

import judge  # noqa: E402 - test_ysb_wmr_config put benchmark/ on the path

N_BATCHES = 9           # 147,456 ticks: 576 windows a key, the last 64 partial


def published_config():
    mod, _ = load_config("kff_drawdown")
    with open(os.path.join(BENCH, "configs", "kff_drawdown.json")) as f:
        return mod, json.load(f)


def make_pool(seed, n_batches=N_BATCHES):
    mod, cfg = load_config("kff_drawdown")
    return mod.make_pool(cfg, np.random.default_rng(seed), BATCH, n_batches)


def judged(mod, cfg, pool, got):
    """``judge.compare`` of what the sink got against the reference."""
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    key, wid, val = (np.asarray(c) for c in zip(*got))
    return judge.compare(want, key.astype(np.int64), wid.astype(np.int64),
                         val.astype(np.float64), True)


def brute_force(cfg, pool, n_batches):
    """Per (key, window): ``max over i <= j of p_i - p_j`` over the key's
    stamped tuples in the window, in stream order, written apart from the
    reference."""
    mod, _ = load_config("kff_drawdown")
    recs = []
    for j in range(n_batches):
        r = pool[j % len(pool)].copy()
        mod.stamp(cfg, r, j * BATCH)
        recs.append(r)
    recs = np.concatenate(recs)
    win, slide = cfg["win_len"], cfg["slide"]
    n_win = (len(recs) - 1) // slide + 1
    out = np.zeros((cfg["n_keys"], n_win), np.int64)
    for k in range(cfg["n_keys"]):
        mine = recs[recs["key"] == k]
        ts, p = mine["ts"].astype(np.int64), mine["value"].astype(np.int64)
        for w in range(n_win):
            inside = p[(ts >= w * slide) & (ts < w * slide + win)]
            out[k, w] = np.max(np.maximum.accumulate(inside) - inside)
    return out


def test_the_reference_is_brute_force_eos_partials_included():
    mod, cfg = load_config("kff_drawdown")
    pool = make_pool(5)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    assert want["value"].shape == (8, 576)
    assert np.array_equal(want["value"], brute_force(cfg, pool, N_BATCHES))
    # the prices walk: falls within a window, and the pool's wrap jumps
    assert (want["value"] > 0).all()
    assert want["must_deliver"].all()
    assert want["last_batch"][0, 0] == (cfg["win_len"] - 1) // BATCH
    assert want["last_batch"][0, -1] == N_BATCHES - 1


@pytest.mark.parametrize("seed", [11, 2_700_000_019, 3_400_000_007])
def test_served_path_equals_the_reference_eos_flush_included(seed):
    mod, cfg = load_config("kff_drawdown")
    pool = make_pool(seed)
    ops, got = run_config("kff_drawdown", pool)
    numbers = judged(mod, cfg, pool, got)
    assert all(v == 0 for v, _ in numbers.values()), numbers
    assert len(got) == 8 * 576
    checks = mod.program_checks(cfg, ops)
    assert set(mod.ENGINE_COUNTERS) < set(checks)
    assert all(v == 0 and limit == 0 for v, limit in checks.values()), checks
    # every (key, pane) of the stream is a run of the ordered fold: 64 panes
    # a batch of 8 keys, a pane's 32 tuples of a key in one batch
    counters = ops[0].stage_counters()
    assert counters["ffat_ordered_runs"] == N_BATCHES * 64 * 8
    assert set(counters) <= set(STAGE_COUNTERS) | set(STAGE_GAUGES)


def test_the_program_controls_fail_the_comparison(monkeypatch):
    """The cross term dropped (a leaf-by-leaf fold's result), and a window's
    panes reduced pairing pane ``i`` with pane ``i + 32``: each delivers
    every cell once, and wrong ones."""
    mod, cfg = load_config("kff_drawdown")
    pool = make_pool(13)
    for ops in (mod.leafwise_ops(cfg, BATCH), None):
        if ops is None:
            import windflow_tpu.operators.win_seqffat as engine
            monkeypatch.setattr(engine, "ordered_reduce", mod.halves_reduce)
        _, got = run_config("kff_drawdown", pool, ops=ops)
        numbers = judged(mod, cfg, pool, got)
        assert numbers["results_wrong"][0] > 1000, numbers
        assert numbers["results_missing"][0] == numbers["results_twice"][0] \
            == 0


def test_the_bfloat16_control_fails_the_comparison():
    mod, cfg = load_config("kff_drawdown")
    pool = make_pool(17)
    exact = mod.reference(cfg, pool, N_BATCHES, BATCH)
    low = mod.reference(cfg, pool, N_BATCHES, BATCH,
                        acc_dtype=ml_dtypes.bfloat16)
    assert np.count_nonzero(low["value"] != exact["value"]) > 4000


def test_the_checks_refuse_an_additive_stage_and_no_ordered_runs():
    mod, published = published_config()
    ops = mod.build_ops(published, 1 << 20)
    ops[0].bind_geometry(1 << 20)               # as the compiled chain does
    assert mod.structure_checks(published, ops) == {
        "window_not_key_ffat_ordered_fold_on_global_time": (0, 0),
        "engine_budgets_not_the_deployments": (0, 0),
        "last_stage_not_the_map": (0, 0)}
    assert ops[0].stage_counters() == {
        "ffat_keys": 512, "ffat_pane_slots": 256, "fired_window_budget": 65}
    additive = [Key_FFAT(lambda t: t.value, jax.numpy.add,
                         spec=ops[0].spec, num_keys=512, pane_capacity=129,
                         max_wins=65), ops[1]]
    additive[0].bind_geometry(1 << 20)
    assert mod.structure_checks(published, additive)[
        "window_not_key_ffat_ordered_fold_on_global_time"] == (1, 0)
    assert mod.structure_checks(published, ops[:1])[
        "last_stage_not_the_map"] == (1, 0)
    # an additive stage that ran (kff's, on its own stream) publishes no
    # ordered runs
    kff, kff_cfg = load_config("kff")
    kff_ops, _ = run_config("kff", kff.make_pool(
        kff_cfg, np.random.default_rng(3), BATCH, 2))
    assert "ffat_ordered_runs" not in kff_ops[0].stage_counters()
    _, cfg = load_config("kff_drawdown")
    checks = mod.program_checks(cfg, kff_ops + ops[1:])
    assert checks["ordered_fold_absent"] == (1, 0)
    assert checks["window_not_key_ffat_ordered_fold_on_global_time"] == (1, 0)


def test_a_program_without_the_ordered_counter_is_refused_at_import(
        monkeypatch):
    """What the parent commit gives this PR's benchmark files: the module
    raises while it is imported, before the runtime starts."""
    monkeypatch.setattr(names, "STAGE_COUNTERS", tuple(
        c for c in STAGE_COUNTERS if c != "ffat_ordered_runs"))
    with pytest.raises(RuntimeError, match="ffat_ordered_runs"):
        load_config("kff_drawdown")


def test_the_pool_walks_on_across_batches():
    mod, published = published_config()
    pool = mod.make_pool(published, np.random.default_rng(2 ** 31 + 5),
                         1 << 14, 4)
    n_k = published["n_keys"]
    walk = np.concatenate([r["value"].reshape(-1, n_k) for r in pool])
    steps = np.diff(walk, axis=0)
    assert np.abs(steps).max() <= published["price_step"]
    assert walk.min() >= 1 and walk.max() <= published["price_max"]
    assert (walk[0] >= 100_000).all() and (walk[0] < 10_000_000).all()
    assert (pool[0]["key"] == np.arange(1 << 14) % n_k).all()
    # the needed bytes at the published size: three columns of a batch,
    # 32,768 pane partials (hi, lo, dd, count) out and in, 32,768 results
    assert mod.min_bytes_per_batch(published, 1 << 20) == (
        3 * 4 * (1 << 20) + 2 * 32768 * 16 + 32768 * 16)


ORDERED_SCOPES = ("insert/fold/ordered/sort", "insert/fold/ordered/scan",
                  "insert/fold/ordered/place", "emit/reduce/ordered")


def test_lowered_step_and_flush_carry_the_ordered_scopes():
    mod, cfg = load_config("kff_drawdown")
    ops, step, args = chain_step(cfg, mod, BATCH)
    hlo = step.lower(*args).as_text(debug_info=True)
    window = ops[0].scope_name()
    assert window == "Key_FFAT:kff_drawdown_window"
    for sub in ORDERED_SCOPES:
        assert f"/{window}/{sub}/" in hlo, sub
    state = args[0][0]
    ops[0].flush(state)
    text = ops[0]._flush_jit.lower(state).as_text(debug_info=True)
    assert f"/{window}/emit/reduce/ordered/" in text


def test_the_flush_compiles_no_step_of_its_own():
    """The ``Map`` behind the window is traced for the flush's batches at
    the stream's first batch: the EOS flush, inside a measured window,
    traces nothing."""
    mod, cfg = load_config("kff_drawdown")
    ops = mod.build_ops(cfg, BATCH)
    pool = make_pool(7, n_batches=2)
    src = wf.RecordSource(lambda: iter(pool), mod.RECORD,
                          key_field=mod.KEY_FIELD, ts_field=mod.TS_FIELD)
    pipe = wf.Pipeline(src, ops, wf.Sink(lambda view: None),
                       batch_size=BATCH)
    steps = []
    push = pipe.chain.push

    def spy(b, from_op=0):
        steps.append((from_op, sorted(pipe.chain._steps)))
        return push(b, from_op=from_op)
    pipe.chain.push = spy
    pipe.run()
    flushed = [seen for from_op, seen in steps if from_op == 1]
    assert flushed and all(seen == [0, 1] for seen in flushed)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


WINDOW = "jit(step)/Key_FFAT:kff_drawdown_window"
OPS = [{"scope": f"{WINDOW}/insert/fold/ordered/sort/sort", "ns": 8e6},
       {"scope": f"{WINDOW}/insert/fold/ordered/scan/max", "ns": 4e6},
       {"scope": f"{WINDOW}/insert/fold/ordered/place/scatter", "ns": 12e6},
       {"scope": f"{WINDOW}/insert/fold/ordered/max", "ns": 1e6},
       {"scope": f"{WINDOW}/insert/fold/dot_general", "ns": 2e6},
       {"scope": f"{WINDOW}/emit/reduce/ordered/max", "ns": 3e6},
       {"scope": f"{WINDOW}/emit/reduce/reduce_sum", "ns": 1e6},
       {"scope": "jit(step)/Map:kff_drawdown_dd/select_n", "ns": 64e6},
       {"scope": "jit(step)/insert/fold/ordered/mul", "ns": 32e6},
       {"scope": None, "ns": 16e6}]


@pytest.mark.parametrize("name,want", [("ffat_ordered_fold_device_ms", 6.25),
                                       ("ffat_ordered_emit_device_ms", 0.75)])
def test_the_readers_take_the_ordered_scopes(name, want):
    read = reader(name).read
    run = {"trace_path": "no file is read", "slice_batches": 4,
           "span_reduce": {"device_ops": OPS}}
    assert read(run) == want
    run["span_reduce"] = {"device_ops": OPS[4:5] + OPS[6:7]}
    assert read(run) is None                # no ordered scope
    assert read({"trace_path": None, "slice_batches": 0}) is None


def test_the_roofline_reader_counts_the_ordered_folds_least_bytes(tmp_path):
    mod = reader("ffat_ordered_fold_roofline")
    cfg_mod, published = published_config()
    need = mod.ordered_fold_min_bytes(cfg_mod, published, 1 << 20)
    # key, ts and value of a batch, and the [512, 256] hi, lo and dd rings in
    # and out
    assert need == 3 * 4 * (1 << 20) + 2 * 3 * 512 * 256 * 4
    trace = os.path.join(ROOT, ".bench_trace", "kff_drawdown.backlog",
                         "plugins", "profile", "x", "host.xplane.pb")
    run = {"trace_path": trace, "slice_batches": 4,
           "span_reduce": {"device_ops": OPS[:4]},
           "peaks": {"hbm_bytes_per_s": 819.0e9}}
    fold_ms = (8 + 4 + 12 + 1) / 4
    assert mod.read(run) == pytest.approx(
        100 * need / 819.0e9 / (fold_ms / 1e3))
    assert mod.read(dict(run, peaks=None)) is None
    assert mod.read(dict(run, span_reduce={"device_ops": OPS[4:5]})) is None
    assert mod.read(dict(run, trace_path=str(tmp_path / "x.pb"))) is None


def test_the_benchmark_declares_the_cell_and_its_three_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "kff_drawdown"
    assert bench["configs"][-1]["reduced"] == []
    assert bench["configs"][-1]["file"] == \
        "benchmark/configs/kff_drawdown.json"
    cell = bench["workloads"][-1]
    assert cell == {"name": "kff_drawdown.backlog", "config": "kff_drawdown",
                    "traffic": "backlog", "chips": 1, "why": cell["why"]}
    declared = {m["name"]: m for m in bench["per_layer"][-3:]}
    assert declared == {
        name: {"name": name, "unit": unit, "better": better,
               "source": "device_trace", "layer": layer,
               "moves": "tuples_per_s",
               "workloads": ["kff_drawdown.backlog"]}
        for name, unit, better, layer in (
            ("ffat_ordered_fold_device_ms", "ms", "lower",
             "compiled chain + operators"),
            ("ffat_ordered_emit_device_ms", "ms", "lower",
             "compiled chain + operators"),
            ("ffat_ordered_fold_roofline", "%", "higher", "kernels"))}
    # kff.backlog's traffic letter for letter
    with open(os.path.join(BENCH, "workloads", "kff.backlog.json")) as f:
        kff = json.load(f)
    with open(os.path.join(BENCH, "workloads",
                           "kff_drawdown.backlog.json")) as f:
        dd = json.load(f)
    assert (dd["traffic"], dd["rehearsal"]) == (kff["traffic"],
                                                kff["rehearsal"])
    # kff's window, keys and record
    with open(os.path.join(BENCH, "configs", "kff.json")) as f:
        kff_cfg = json.load(f)
    _, published = published_config()
    for k in ("record", "n_keys", "win_len", "slide", "rehearsal"):
        assert published[k] == kff_cfg[k], k


def test_rehearsal_of_the_new_cell_exits_zero(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearsal",
         "--workload", "kff_drawdown.backlog", "--seed", "4400000033",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""                        # a rehearsal prints no metric
    last = json.loads(proc.stderr.splitlines()[-1].split(" ", 1)[1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"]["ordered_fold_absent"]["value"] == 0
    assert last["compared"]["chain_traces_in_window"]["value"] == 0
