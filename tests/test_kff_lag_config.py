"""Configuration ``kff_lag`` (``kff``'s keyed sliding sum through ``Key_FFAT``
on per-key time, over a stream in which a quarter of the keys run behind the
rest by a lag of their own) at rehearsal sizes on the CPU: its reference
against a tuple-by-tuple simulation of per-key ``Triggerer_TB``, the served
path against the reference, both controls failing, the per-key arm's ring
overruns and key-clock spread counted, the checks on the stage, the scopes
and readers of the per-key phases (the cells' step programs are fenced in
``test_step_programs.py``)."""

import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest

from test_ysb_wmr_config import (BATCH, BENCH, ROOT, as_grid, chain_step,
                                 equations, load_config, run_config,
                                 run_engine)
from windflow_tpu.basic import win_type_t
from windflow_tpu.observability import names
from windflow_tpu.observability.names import STAGE_COUNTERS
from windflow_tpu.operators.win_patterns import Key_FFAT
from windflow_tpu.operators.window import WindowSpec

import judge  # noqa: E402 - test_ysb_wmr_config put benchmark/ on the path

N_BATCHES = 9           # 147,456 ticks: 576 windows, the lagging keys fewer


def published_config():
    mod, _ = load_config("kff_lag")
    with open(os.path.join(BENCH, "configs", "kff_lag.json")) as f:
        return mod, json.load(f)


def make_pool(seed, n_batches=N_BATCHES, cfg=None, batch=BATCH):
    mod, rehearsal = load_config("kff_lag")
    return mod.make_pool(cfg or rehearsal, np.random.default_rng(seed), batch,
                         n_batches)


def stamped(cfg, pool, j, batch):
    """numpy: batch ``j``'s keys and stamps, written apart from ``stamp``."""
    pos = j * batch + np.arange(batch)
    key = pool[j % len(pool)]["key"].astype(np.int64)
    return key, np.maximum(pos - pool[0].lag[key], 0)


def per_key_triggerer(cfg, pool, n_batches, batch):
    """Per-key ``Triggerer_TB`` tuple by tuple at batch granularity, written
    apart from the reference: a tuple goes to every window of its key that
    holds its ``ts`` and has not fired; after the batch each key fires the
    windows whose end its own largest ``ts`` reaches; at the end every window
    of a key that starts by its largest ``ts`` is flushed. -> (sums, last
    batch) by (key, window), and the tuples dropped."""
    win, slide = cfg["win_len"], cfg["slide"]
    sums, last = {}, {}
    fired, wm, dropped = {}, {}, 0
    for j in range(n_batches):
        key, ts = stamped(cfg, pool, j, batch)
        for k, t, v in zip(key.tolist(), ts.tolist(),
                           pool[j % len(pool)]["value"].tolist()):
            counted = [w for w in range(max(0, (t - win) // slide + 1),
                                        t // slide + 1)
                       if w >= fired.get(k, 0)]
            dropped += not counted
            for w in counted:
                sums[k, w] = sums.get((k, w), 0) + v
                last[k, w] = j
            wm[k] = max(wm.get(k, -1), t)
        for k, m in wm.items():
            fired[k] = max(fired.get(k, 0), (m - win) // slide + 1)
    return sums, last, dropped


def as_dicts(want):
    k, w = np.nonzero(want["must_deliver"])
    return ({(a, b): int(want["value"][a, b]) for a, b in zip(k, w)},
            {(a, b): int(want["last_batch"][a, b]) for a, b in zip(k, w)})


SMALL = {
    # name: overrides of the rehearsal configuration, batch, batches
    "slide_of_two_panes": (dict(win_len=96, slide=32, max_lag=300), 256, 12),
    "lag_past_the_stream": (dict(win_len=64, slide=16, max_lag=4000), 256,
                            10),
    "every_key_behind": (dict(win_len=64, slide=16, max_lag=200,
                              lag_share=1.0), 128, 14),
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_equals_a_tuple_by_tuple_per_key_triggerer(name):
    mod, cfg = load_config("kff_lag")
    over, batch, n = SMALL[name]
    cfg = dict(cfg, n_keys=4, **over)
    pool = make_pool(sorted(SMALL).index(name), n_batches=n, cfg=cfg,
                     batch=batch)
    want = mod.reference(cfg, pool, n, batch)
    sums, last, dropped = per_key_triggerer(cfg, pool, n, batch)
    assert as_dicts(want) == (sums, last)
    assert dropped == 0                     # every key is in order
    assert pool[0].lag.max() > 0
    # the control's precision moves sums, not which windows are due
    low = mod.reference(cfg, pool, n, batch, acc_dtype=ml_dtypes.bfloat16)
    assert np.array_equal(low["must_deliver"], want["must_deliver"])


def test_the_lagging_keys_last_batches_lie_later():
    mod, cfg = load_config("kff_lag")
    pool = make_pool(13)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    lag = pool[0].lag
    behind = np.flatnonzero(lag > cfg["win_len"])
    even = np.flatnonzero(lag == 0)
    assert len(behind) and len(even)
    w = 10                                  # a window every key holds whole
    assert (want["last_batch"][behind, w].min()
            >= want["last_batch"][even, w].max())
    # a lagging key holds fewer windows: its clock ends behind the others'
    n_even = want["must_deliver"][even].sum(axis=1)
    assert (want["must_deliver"][behind].sum(axis=1) < n_even.min()).all()


def serve(pool, ops=None):
    mod, cfg = load_config("kff_lag")
    ops, got = run_config("kff_lag", pool, ops=ops)
    return mod, cfg, ops, got


@pytest.mark.parametrize("seed", [11, 4_000_000_019])
def test_served_path_equals_the_reference_eos_flush_included(seed):
    pool = make_pool(seed)
    mod, cfg, ops, got = serve(pool)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    cells = [(k, w) for k, w, _ in got]
    assert len(set(cells)) == len(cells)                    # each once
    assert set(cells) == set(zip(*np.nonzero(want["must_deliver"])))
    assert np.array_equal(as_grid(got, want["value"].shape), want["value"])
    last = {}
    for k, w in cells:                                      # in window order
        assert w > last.get(k, -1)
        last[k] = w
    # the EOS flush left no key's window open: each key's last window is the
    # one that starts at or before its last tick
    last_ts = np.maximum(N_BATCHES * BATCH - cfg["n_keys"]
                         + np.arange(cfg["n_keys"]) - pool[0].lag, 0)
    assert [last[k] for k in range(cfg["n_keys"])] == (
        last_ts // cfg["slide"]).tolist()
    counters = ops[-1].stage_counters()
    assert counters["windows_undelivered_at_eos"] == 0
    assert counters["ffat_key_clock_spread"] == last_ts.max() - last_ts.min()
    checks = mod.program_checks(cfg, ops)
    assert set(checks) == {
        "window_not_key_ffat_on_per_key_time",
        "engine_budgets_not_the_deployments", "ffat_ring_overruns",
        "old_drops", "windows_undelivered_at_eos", "key_clocks_not_skewed"}
    assert all(v == 0 and limit == 0 for v, limit in checks.values()), checks


def in_step_pool(seed):
    """The rehearsal pool with no key lagging, and ``kff``'s from the same
    seed."""
    mod, cfg = load_config("kff_lag")
    kff, kff_cfg = load_config("kff")
    ours = mod.make_pool(dict(cfg, lag_share=0.0),
                         np.random.default_rng(seed), BATCH, N_BATCHES)
    theirs = kff.make_pool(kff_cfg, np.random.default_rng(seed), BATCH,
                           N_BATCHES)
    return mod, cfg, ours, kff, kff_cfg, theirs


def test_without_a_lag_it_is_kffs_reference_cell_for_cell():
    mod, cfg, ours, kff, kff_cfg, theirs = in_step_pool(17)
    assert all(np.array_equal(a["value"], b["value"])
               for a, b in zip(ours, theirs))
    want = mod.reference(cfg, ours, N_BATCHES, BATCH)
    other = kff.reference(kff_cfg, theirs, N_BATCHES, BATCH)
    for part in ("value", "last_batch", "must_deliver"):
        assert np.array_equal(want[part], other[part]), part


def test_without_a_lag_per_key_time_delivers_what_the_global_clock_does():
    """The same in-step stream through both programs: the same results, in
    the same order per key; the key clocks lie a round of the keys apart,
    and ``key_clocks_not_skewed`` fails the run."""
    mod, cfg, ours, _, _, _ = in_step_pool(19)
    ops, per_key = run_config("kff_lag", ours)
    _, glob = run_config("kff_lag", ours,
                         ops=mod.global_time_ops(cfg, BATCH))
    assert sorted(per_key) == sorted(glob)
    for k in range(cfg["n_keys"]):
        assert ([w for kk, w, _ in per_key if kk == k]
                == [w for kk, w, _ in glob if kk == k])
    counters = ops[-1].stage_counters()
    assert counters["ffat_key_clock_spread"] == cfg["n_keys"] - 1
    checks = mod.program_checks(cfg, ops)
    assert checks["key_clocks_not_skewed"] == (1, 0)
    assert all(v == 0 for c, (v, _) in checks.items()
               if c != "key_clocks_not_skewed")


def test_the_global_time_control_drops_tuples_and_fails_the_comparison():
    """The lagging stream through ``kff``'s program, one clock for every key:
    the fastest keys' clock fires the lagging keys' windows before their
    tuples come, which are dropped as OLD, and their results are missing."""
    mod, cfg = load_config("kff_lag")
    pool = make_pool(23)
    ops, got = run_config("kff_lag", pool,
                          ops=mod.global_time_ops(cfg, BATCH))
    counters = ops[-1].stage_counters()
    assert counters["old_drops"] > 0
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    numbers = judge.compare(want, *map(np.asarray, zip(*got)), True)
    assert numbers["results_missing"][0] > 0
    assert numbers["results_wrong"][0] > 0
    checks = mod.program_checks(cfg, ops)
    assert checks["window_not_key_ffat_on_per_key_time"] == (1, 0)
    assert checks["old_drops"] == (counters["old_drops"], 0)


def test_the_bfloat16_control_fails_the_comparison():
    mod, cfg = load_config("kff_lag")
    pool = make_pool(5)
    exact = mod.reference(cfg, pool, N_BATCHES, BATCH)
    low = mod.reference(cfg, pool, N_BATCHES, BATCH,
                        acc_dtype=ml_dtypes.bfloat16)
    key, wid = np.nonzero(low["must_deliver"])
    numbers = judge.compare(exact, key, wid, low["value"][key, wid], True)
    assert numbers["results_wrong"][0] > 1000
    assert numbers["results_missing"] == numbers["results_twice"] == (0, 0)
    key, wid = np.nonzero(exact["must_deliver"])
    assert all(v == 0 for v, _ in judge.compare(
        exact, key, wid, exact["value"][key, wid], True).values())


def overruns(cfg, pool, P, n_batches=N_BATCHES, batch=BATCH):
    """numpy: the lanes whose pane lies ``P`` or more past their key's first
    unfired pane when their batch comes (each key's clock its own largest
    ``ts``, a window fired once its end is at or behind it)."""
    pane = cfg["slide"]                     # the rehearsal window's pane
    first = np.zeros(cfg["n_keys"], np.int64)
    total = 0
    for j in range(n_batches):
        key, ts = stamped(cfg, pool, j, batch)
        total += int(np.count_nonzero(ts // pane >= first[key] + P))
        wm = np.full(cfg["n_keys"], -1)
        np.maximum.at(wm, key, ts)
        first = np.maximum(first, (wm - cfg["win_len"]) // cfg["slide"] + 1)
    return total


def test_a_ring_too_small_is_counted_and_fails_the_checks(monkeypatch):
    """A key's oldest open window and a batch span 128 panes: a ring of 64
    slots (the power of two below) is overrun from the second batch on, as
    numpy counts it, and the sums come out wrong; a ring of 128 is not."""
    mod, cfg = load_config("kff_lag")
    pool = make_pool(31)
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    assert overruns(cfg, pool, 128) == 0
    for slots, n in ((64, overruns(cfg, pool, 64)), (128, 0)):
        monkeypatch.setattr(mod, "engine_budgets",
                            lambda cfg, batch: (slots, 8 * 65))
        ops, got = run_config("kff_lag", pool, ops=mod.build_ops(cfg, BATCH))
        assert ops[-1].P == slots
        counters = ops[-1].stage_counters()
        assert counters["ffat_ring_overruns"] == n
        assert counters["old_drops"] == 0
        assert np.array_equal(as_grid(got, want["value"].shape),
                              want["value"]) == (n == 0)
    assert n == 0 and overruns(cfg, pool, 64) > BATCH


def test_a_lane_a_ring_past_its_keys_first_unfired_pane_is_counted():
    """One key, tumbling windows of 4 ticks, a ring of 4 panes: after ticks
    0-3 the first unfired pane is 0, so tick 12 (pane 3) fits and ticks 16,
    16 and 17 (pane 4, a ring past it) overrun; a ring of 8 holds them. The
    other key's clock stays at 0."""
    keys = [0, 0, 0, 1, 0, 0, 0, 0]
    ts = [0, 1, 3, 0, 12, 16, 16, 17]
    for capacity, want in ((4, 3), (8, 0)):
        op = Key_FFAT(lambda t: t.v, jnp.add,
                      spec=WindowSpec(4, 4, win_type_t.TB), num_keys=2,
                      pane_capacity=capacity, global_time=False, max_wins=8)
        run_engine(op, keys, ts, batch=4)
        counters = op.stage_counters()
        assert counters["ffat_ring_overruns"] == want
        assert counters["ffat_key_clock_spread"] == 17


def test_budgets_and_checks_come_from_the_deployment():
    mod, published = published_config()
    assert published["reduced"] == []
    assert (published["lag_share"], published["max_lag"],
            published["delay"]) == (0.25, 4 * published["win_len"], 0)
    # kff's ring, and one list of 65 windows a key for all 512 keys
    assert mod.engine_budgets(published, 1 << 20) == (64 + 64 + 1,
                                                      512 * 65)
    window = mod.build_ops(published, 1 << 20)[-1]
    assert type(window) is Key_FFAT and not window.global_time
    assert window.P == 256 and window.max_wins == 33_280
    # the per-key emit reads each fired window's key as one ring row
    assert (window.stage_counters()["ffat_emit_row_lanes"]
            == 33_280 * 256 == 8_519_680)
    ops, step, args = chain_step(published, mod, 1 << 20)
    jax.eval_shape(step, *args)
    assert mod.structure_checks(published, ops[-1]) == {
        "window_not_key_ffat_on_per_key_time": (0, 0),
        "engine_budgets_not_the_deployments": (0, 0)}
    # kff's global-time stage is not this deployment's
    other = mod.global_time_ops(published, 1 << 20)[-1]
    other.bind_geometry(1 << 20)
    other.count_lift = False
    assert mod.structure_checks(published, other)[
        "window_not_key_ffat_on_per_key_time"] == (1, 0)
    # nor a per-key stage at a budget a key, or at half the ring
    spec = WindowSpec(1 << 20, 1 << 14, win_type_t.TB)
    for slots, wins in ((129, 65), (64, 512 * 65), (129, 512 * 65 + 1)):
        wrong = Key_FFAT(lambda t: t.value, jnp.add, spec=spec, num_keys=512,
                         pane_capacity=slots, max_wins=wins,
                         global_time=False)
        wrong.bind_geometry(1 << 20)
        assert mod.structure_checks(published, wrong) == {
            "window_not_key_ffat_on_per_key_time": (0, 0),
            "engine_budgets_not_the_deployments": (1, 0)}, (slots, wins)
    # the stream's bytes are kff's
    kff, kff_published = load_config("kff")[0], dict(published)
    assert mod.min_bytes_per_batch(published, 1 << 20) == \
        kff.min_bytes_per_batch(kff_published, 1 << 20)
    # kcb's emit at its cell's shapes (2 panes a window, 4,096 slots, 2,112
    # windows) keeps the element takes, and says so once its budget is known
    kcb = load_config("kcb")[0]
    with open(os.path.join(BENCH, "configs", "kcb.json")) as f:
        kcb_published = json.load(f)
    ops, step, args = chain_step(kcb_published, kcb, 1 << 20)
    jax.eval_shape(step, *args)
    ops[-1].collect_stats(args[0][-1])
    assert (ops[-1].P, ops[-1].wpanes, ops[-1]._w) == (4096, 2, 2112)
    assert ops[-1].stage_counters()["ffat_emit_row_lanes"] == 0


def test_the_published_draw_lags_a_quarter_of_the_keys_past_a_window():
    mod, published = published_config()
    lag = mod.draw_lags(published, np.random.default_rng(4_000_000_001))
    assert np.count_nonzero(lag) == 128
    assert lag.max() <= 4 * published["win_len"]
    assert lag.max() - lag.min() >= published["win_len"]


def test_a_program_without_the_spread_counter_is_refused_at_import(
        monkeypatch):
    monkeypatch.setattr(names, "STAGE_COUNTERS", tuple(
        c for c in STAGE_COUNTERS if c != "ffat_key_clock_spread"))
    with pytest.raises(RuntimeError, match="ffat_key_clock_spread"):
        load_config("kff_lag")


# ---- tracing: the per-key phases' scopes --------------------------------

def emit_gathers(jaxpr):
    """The result shapes of the gathers under ``emit/gather``."""
    return [eqn.outvars[0].aval.shape for eqn, path in equations(jaxpr)
            if eqn.primitive.name == "gather" and "/emit/gather" in path]


PER_KEY_SCOPES = ("insert/lookup", "insert/fold", "insert/keys",
                  "emit/range", "emit/gather", "emit/reduce")


def test_lowered_step_and_flush_carry_the_per_key_scopes():
    mod, cfg = load_config("kff_lag")
    ops, step, args = chain_step(cfg, mod, BATCH)
    hlo = step.lower(*args).as_text(debug_info=True)
    window = ops[-1].scope_name()
    assert window == "Key_FFAT:kff_lag_window"
    for sub in PER_KEY_SCOPES:
        assert f"/{window}/{sub}/" in hlo, sub
    # the values and counts in the contraction under fold, the watermark's
    # select-reduce under keys (the count comes from the table's rows), the
    # [W, P] row takes under emit/gather
    for sub, op in (("insert/fold", r"rck,rcl->rkl/dot_general\""),
                    ("insert/keys", "reduce_max\""),
                    ("emit/gather", r"jit\(_take\)")):
        assert re.search(rf'/{window}/{sub}/[^"]*{op}', hlo), (sub, op)
    assert not re.search(rf'/{window}/insert/keys/[^"]*scatter', hlo)
    # the scatters over the lanes lie in the fold's fallbacks alone:
    # keyed_pane_fold's whole batch (count and value into the [K*P]
    # tables) and partial branch (the stragglers' two-wide rows) under
    # scatter, the pane ids of a batch that overran the ring under overrun
    jaxpr = jax.make_jaxpr(step)(*args).jaxpr
    KP = cfg["n_keys"] * ops[-1].P
    assert sorted((eqn.outvars[0].aval.shape, path.split(f"{window}/")[1])
                  for eqn, path in equations(jaxpr)
                  if eqn.primitive.name.startswith("scatter")) == [
        ((KP,), "insert/fold/overrun"), ((KP,), "insert/fold/scatter"),
        ((KP,), "insert/fold/scatter"), ((KP, 2), "insert/fold/scatter")]
    # emit/gather takes the whole ring row of each fired window's key out of
    # pane_of and the partials, and no window's [wpanes] slots one by one
    assert emit_gathers(jaxpr) == [(ops[-1]._w, ops[-1].P)] * 2
    # kcb's program at its cell's ring (4,096 slots, 2 panes a window) keeps
    # the [W, 2] element takes
    kcb = load_config("kcb")[0]
    with open(os.path.join(BENCH, "configs", "kcb.json")) as f:
        kcb_published = json.load(f)
    kcb_ops, kcb_step, kcb_args = chain_step(kcb_published, kcb, 1 << 20)
    kcb_jaxpr = jax.make_jaxpr(kcb_step)(*kcb_args).jaxpr
    assert kcb_ops[-1].P == 4096
    assert emit_gathers(kcb_jaxpr) == [(kcb_ops[-1]._w, 2)] * 2
    state = args[0][-1]
    ops[-1].flush(state)
    text = ops[-1]._flush_jit.lower(state).as_text(debug_info=True)
    for sub in PER_KEY_SCOPES[3:]:
        assert f"/{window}/{sub}/" in text, sub


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


WINDOW = "jit(step)/Key_FFAT:kff_lag_window"
OPS = [{"scope": f"{WINDOW}/insert/lookup/dot_general", "ns": 4e6},
       {"scope": f"{WINDOW}/insert/fold/scatter-add", "ns": 24e6},
       {"scope": f"{WINDOW}/insert/keys/scatter-max", "ns": 8e6},
       {"scope": f"{WINDOW}/insert/ge", "ns": 4e6},
       {"scope": f"{WINDOW}/emit/range/reduce_sum", "ns": 2e6},
       {"scope": f"{WINDOW}/emit/gather/gather", "ns": 12e6},
       {"scope": f"{WINDOW}/emit/reduce/reduce_sum", "ns": 2e6},
       {"scope": "jit(step)/insert/keys/mul", "ns": 64e6},
       {"scope": None, "ns": 16e6}]


@pytest.mark.parametrize("name,want", [("ffat_pk_insert_device_ms", 10.0),
                                       ("ffat_pk_emit_device_ms", 4.0)])
def test_the_readers_take_the_per_key_phases(name, want):
    read = reader(name).read
    run = {"trace_path": "no file is read", "slice_batches": 4,
           "span_reduce": {"device_ops": OPS}}
    assert read(run) == want
    run["span_reduce"] = {"device_ops": OPS[7:]}
    assert read(run) is None                # no phase under an operator
    assert read({"trace_path": None, "slice_batches": 0}) is None


def test_the_benchmark_declares_the_cell_and_its_two_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name: later configurations' entries follow these
    (config,) = [c for c in bench["configs"] if c["name"] == "kff_lag"]
    assert config["reduced"] == []
    assert config["file"] == "benchmark/configs/kff_lag.json"
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "kff_lag.backlog"]
    assert cell == {"name": "kff_lag.backlog", "config": "kff_lag",
                    "traffic": "backlog", "chips": 1, "why": cell["why"]}
    declared = {m["name"]: m for m in bench["per_layer"]
                if m.get("workloads") == ["kff_lag.backlog"]}
    assert declared == {name: {
        "name": name, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "compiled chain + operators",
        "moves": "tuples_per_s", "workloads": ["kff_lag.backlog"]}
        for name in ("ffat_pk_insert_device_ms", "ffat_pk_emit_device_ms")}
    # kff.backlog's traffic letter for letter
    with open(os.path.join(BENCH, "workloads", "kff.backlog.json")) as f:
        kff = json.load(f)
    with open(os.path.join(BENCH, "workloads", "kff_lag.backlog.json")) as f:
        lag = json.load(f)
    assert (lag["traffic"], lag["rehearsal"]) == (kff["traffic"],
                                                  kff["rehearsal"])


def test_rehearsal_of_the_new_cell_exits_zero(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearsal",
         "--workload", "kff_lag.backlog", "--seed", "4000000033",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""                        # a rehearsal prints no metric
    last = json.loads(proc.stderr.splitlines()[-1].split(" ", 1)[1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"]["key_clocks_not_skewed"]["value"] == 0
