"""Configuration ``kff_late`` (``kff``'s keyed sliding sum through
``Key_FFAT`` over a stream in which a tenth of the tuples arrive late, with
an allowed lateness shorter than the worst delay) at rehearsal sizes on the
CPU: its reference against a per-tuple simulation of ``Triggerer_TB``, the
served path against the reference, the engine's count of late lanes and its
firing boundary against the reference's, both controls failing, the two new
readers, and the scope that tells the value fold's scatter fallback apart."""

import dataclasses
import importlib.util
import json
import math
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
                                 equations, load_config, run_config)
from windflow_tpu.observability import names
from windflow_tpu.observability.names import STAGE_COUNTERS
from windflow_tpu.operators.win_patterns import Key_FFAT

from test_keyed_pane_fold import branch_of
from test_step_programs import fold_program
from windflow_tpu.ops.histogram import FOLD_PARTIAL

import judge  # noqa: E402 - test_ysb_wmr_config put benchmark/ on the path

N_BATCHES = 9           # 147,456 ticks: 576 windows a key, the last 64 partial


def published_config():
    mod, _ = load_config("kff_late")
    with open(os.path.join(BENCH, "configs", "kff_late.json")) as f:
        return mod, json.load(f)


def make_pool(seed, n_batches=N_BATCHES, cfg=None, batch=BATCH):
    mod, rehearsal = load_config("kff_late")
    return mod.make_pool(cfg or rehearsal, np.random.default_rng(seed), batch,
                         n_batches)


def triggerer_tb(cfg, pool, n_batches, batch):
    """``Triggerer_TB`` tuple by tuple at batch granularity, written apart
    from the reference: each tuple of a batch goes to every window that holds
    its ``ts`` and has not fired; after the batch every window whose end plus
    the delay the largest ``ts`` so far reaches fires; at the end every
    window that starts by then is flushed. -> (sums, last batch, late, drops)
    as dicts by (key, window) and counts."""
    win, slide, delay = cfg["win_len"], cfg["slide"], cfg["delay"]
    sums, last = {}, {}
    fired, wm, late, dropped = 0, -1, 0, 0
    for j in range(n_batches):
        recs = pool[j % len(pool)]
        ts = np.maximum(j * batch + recs.offset, 0)
        for k, t, v in zip(recs["key"].tolist(), ts.tolist(),
                           recs["value"].tolist()):
            holding = range(max(0, (t - win) // slide + 1), t // slide + 1)
            counted = [w for w in holding if w >= fired]
            late += bool(counted) and len(counted) < len(holding)
            dropped += not counted
            for w in counted:
                sums[k, w] = sums.get((k, w), 0) + v
                last[k, w] = j
        wm = max(wm, int(ts.max()))
        while fired * slide + win + delay <= wm:
            fired += 1
    return sums, last, late, dropped


def as_dicts(want):
    k, w = np.nonzero(want["must_deliver"])
    return ({(a, b): int(want["value"][a, b]) for a, b in zip(k, w)},
            {(a, b): int(want["last_batch"][a, b]) for a, b in zip(k, w)})


SMALL = {
    # name: overrides of the rehearsal configuration, batch, batches
    "slide_of_two_panes": (dict(win_len=48, slide=32, delay=16,
                                max_delay=120), 256, 14),
    "delay_zero": (dict(win_len=64, slide=16, delay=0, max_delay=30), 256, 12),
    "delay_past_a_batch": (dict(win_len=64, slide=16, delay=32,
                                max_delay=400), 128, 16),
    "half_late": (dict(win_len=96, slide=32, delay=8, max_delay=90,
                       late_share=0.5), 256, 10),
}


@pytest.mark.parametrize("name", sorted(SMALL))
def test_reference_equals_a_tuple_by_tuple_triggerer(name):
    mod, cfg = load_config("kff_late")
    over, batch, n = SMALL[name]
    cfg = dict(cfg, n_keys=4, **over)
    pool = make_pool(sorted(SMALL).index(name), n_batches=n, cfg=cfg,
                     batch=batch)
    want = mod.reference(cfg, pool, n, batch)
    sums, last, late, dropped = triggerer_tb(cfg, pool, n, batch)
    assert as_dicts(want) == (sums, last)
    assert (want["late_lanes"], want["old_drops"]) == (late, dropped)
    assert late > 0
    # a delay past the allowed lateness and a window drops a tuple
    assert (dropped > 0) == (over["max_delay"] > over["delay"]
                             + over["win_len"])


def test_the_reference_at_the_rehearsal_size_is_the_triggerers():
    mod, cfg = load_config("kff_late")
    pool = make_pool(7, n_batches=4)
    want = mod.reference(cfg, pool, 4, BATCH)
    sums, last, late, dropped = triggerer_tb(cfg, pool, 4, BATCH)
    assert as_dicts(want) == (sums, last)
    assert (want["late_lanes"], want["old_drops"]) == (late, 0)
    assert dropped == 0 and late > 100


def spill_lanes(cfg, pool):
    """numpy: the lanes ``keyed_pane_fold``'s partial branch scatters over
    the served batches (``test_keyed_pane_fold.branch_of``), each batch's
    ticks stamped as ``triggerer_tb`` stamps them; every batch must take
    that branch."""
    pane_len = math.gcd(cfg["win_len"], cfg["slide"])
    total = 0
    for j in range(N_BATCHES):
        ts = np.maximum(j * BATCH + pool[j % len(pool)].offset, 0)
        branch, n = branch_of(ts // pane_len, np.ones(BATCH, bool))
        assert branch == FOLD_PARTIAL, (j, branch)
        total += n
    return total


def serve(pool):
    mod, cfg = load_config("kff_late")
    ops, got = run_config("kff_late", pool)
    return mod, cfg, ops, got


@pytest.mark.parametrize("seed", [11, 3_800_000_019])
def test_served_path_equals_the_reference_and_counts_its_late_lanes(seed):
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
    counters = ops[-1].stage_counters()
    # every batch carries a straggler further back than the one-hot holds
    # from its chunk's oldest lane: each took the partial branch, which
    # scattered the lanes behind each chunk's newest window alone
    assert counters["ffat_fold_fallbacks"] == 0
    assert counters["ffat_fold_partials"] == N_BATCHES
    assert counters["ffat_fold_spill_lanes"] == spill_lanes(cfg, pool) > 0
    assert counters["ffat_late_lanes"] == want["late_lanes"] > 0
    checks = mod.program_checks(cfg, ops)
    assert set(checks) == {
        "window_not_key_ffat_value_fold_on_global_time",
        "engine_budgets_not_the_deployments", "ffat_ring_overruns",
        "old_drops", "windows_undelivered_at_eos", "late_lanes_absent"}
    assert all(v == 0 and limit == 0 for v, limit in checks.values()), checks


def test_stragglers_behind_the_delay_and_behind_the_horizon():
    """Batch 5's first tuple comes a window and a half late, past every
    window holding it: dropped, and counted in ``old_drops``, by the
    reference as by the engine. Batch 6's first tuple lies just behind the
    newest fired window's end: it counts in the windows still open alone."""
    mod, cfg = load_config("kff_late")
    pool = make_pool(29)
    win, slide, delay = cfg["win_len"], cfg["slide"], cfg["delay"]
    pool[5].offset[0] = -(delay + win + 2 * slide)       # behind the horizon
    pool[6].offset[0] = -(delay + 2 * slide)             # behind the delay
    pool[5]["value"][0] = pool[6]["value"][0] = 77
    want = mod.reference(cfg, pool, N_BATCHES, BATCH)
    assert want["old_drops"] == 1
    _, _, ops, got = serve(pool)
    assert np.array_equal(as_grid(got, want["value"].shape), want["value"])
    counters = ops[-1].stage_counters()
    assert counters["old_drops"] == 1
    assert counters["ffat_late_lanes"] == want["late_lanes"]
    assert mod.program_checks(cfg, ops)["old_drops"] == (1, 0)
    # where the two went: the reference less the reference without them
    pool[5]["value"][0] = pool[6]["value"][0] = 0
    moved = want["value"] - mod.reference(cfg, pool, N_BATCHES,
                                          BATCH)["value"]
    wm = max(j * BATCH + int(pool[j].offset.max()) for j in range(6))
    first_open = mod.fired_windows(cfg, wm)
    ts = 6 * BATCH + pool[6].offset[0]
    assert (ts - win) // slide + 1 < first_open <= ts // slide    # late
    assert set(zip(*np.nonzero(moved))) == {
        (0, w) for w in range(first_open, ts // slide + 1)}
    assert (moved[moved != 0] == 77).all()


def test_without_late_tuples_it_is_kffs_reference_cell_for_cell():
    mod, cfg = load_config("kff_late")
    kff, kff_cfg = load_config("kff")
    pool = mod.make_pool(dict(cfg, late_share=0.0),
                         np.random.default_rng(17), BATCH, N_BATCHES)
    theirs_pool = kff.make_pool(kff_cfg, np.random.default_rng(17), BATCH,
                                N_BATCHES)
    assert all(np.array_equal(a["value"], b["value"])
               for a, b in zip(pool, theirs_pool))
    ours = mod.reference(cfg, pool, N_BATCHES, BATCH)
    theirs = kff.reference(kff_cfg, theirs_pool, N_BATCHES, BATCH)
    for part in ("value", "last_batch", "must_deliver"):
        assert np.array_equal(ours[part], theirs[part]), part
    assert (ours["late_lanes"], ours["old_drops"]) == (0, 0)


@pytest.mark.parametrize("control", ["bfloat16", "in_order"])
def test_both_controls_fail_the_comparison(control):
    """The judge's own comparison, as ``run.py`` makes it, fails with either
    control in the program's place: the reference in bfloat16 (through the
    precision) and the reference that counts every tuple in every window
    holding it (through the late lanes alone)."""
    mod, cfg = load_config("kff_late")
    pool = make_pool(5)
    exact = mod.reference(cfg, pool, N_BATCHES, BATCH)
    if control == "bfloat16":
        exp = mod.reference(cfg, pool, N_BATCHES, BATCH,
                            acc_dtype=ml_dtypes.bfloat16)
        key, wid = np.nonzero(exp["must_deliver"])
        val = exp["value"][key, wid]
    else:
        key, wid, val = mod.in_order_results(mod, cfg, pool, N_BATCHES, BATCH)
    numbers = judge.compare(exact, key, wid, val, True)
    assert numbers["results_wrong"][0] > (4000 if control == "bfloat16"
                                          else 100)
    assert numbers["results_missing"] == numbers["results_twice"] == (0, 0)
    # the exact reference put in the program's place passes
    key, wid = np.nonzero(exact["must_deliver"])
    assert all(v == 0 for v, _ in judge.compare(
        exact, key, wid, exact["value"][key, wid], True).values())


def test_the_engines_firing_boundary_is_the_references():
    """``Win_SeqFFAT``'s global-time rule for the windows due (``_due_hi``,
    ``next_win`` after an emit) against ``fired_windows``, at every
    watermark around the first windows' ends and at random ones."""
    mod, cfg = load_config("kff_late")
    op = mod.build_ops(cfg, BATCH)[0]
    state = op.init_state({"id": jax.ShapeDtypeStruct((), jnp.int32),
                           "value": jax.ShapeDtypeStruct((), jnp.int32)})
    end = cfg["win_len"] + cfg["delay"]
    wms = np.concatenate([np.arange(-1, end + 3 * cfg["slide"]),
                          np.random.default_rng(3).integers(0, 1 << 30, 200)])
    due = jax.jit(jax.vmap(lambda wm: jnp.maximum(
        0, op._due_hi(dataclasses.replace(state, wm=wm), False))))
    got = np.asarray(due(jnp.asarray(wms, jnp.int32)))
    assert got.tolist() == [mod.fired_windows(cfg, int(w)) for w in wms]
    assert mod.fired_windows(cfg, end - 1) == 0
    assert mod.fired_windows(cfg, end) == 1


def test_budgets_and_checks_come_from_the_deployment():
    mod, published = published_config()
    assert published["reduced"] == []
    assert (published["late_share"], published["max_delay"],
            published["delay"]) == (0.1, 314_572, 131_072)
    # kff's 64 + 64 + 1, the delay's 8 panes and the stragglers' 20
    assert mod.engine_budgets(published, 1 << 20) == (64 + 64 + 1 + 8 + 20, 65)
    window = mod.build_ops(published, 1 << 20)[-1]
    assert type(window) is Key_FFAT and window.global_time
    assert window.spec.delay == 131_072 and window.P == 256
    ops, step, args = chain_step(published, mod, 1 << 20)
    jax.eval_shape(step, *args)
    assert mod.structure_checks(published, ops[-1]) == {
        "window_not_key_ffat_value_fold_on_global_time": (0, 0),
        "engine_budgets_not_the_deployments": (0, 0)}
    # kff's window without the allowed lateness is not this deployment's
    kff, kff_published = load_config("kff")[0], dict(published, delay=0)
    other = kff.build_ops(kff_published, 1 << 20)[-1]
    other.bind_geometry(1 << 20)
    other.count_lift = False
    assert mod.structure_checks(published, other)[
        "window_not_key_ffat_value_fold_on_global_time"] == (1, 0)
    # the stream's bytes are kff's
    assert mod.min_bytes_per_batch(published, 1 << 20) == \
        kff.min_bytes_per_batch(kff_published, 1 << 20)


def test_a_program_without_the_late_counter_is_refused_at_import(monkeypatch):
    monkeypatch.setattr(names, "STAGE_COUNTERS", tuple(
        c for c in STAGE_COUNTERS if c != "ffat_late_lanes"))
    with pytest.raises(RuntimeError, match="ffat_late_lanes"):
        load_config("kff_late")


def test_a_run_without_lateness_is_not_correct():
    """The same window over an in-order stream: nothing late, and
    ``late_lanes_absent`` fails the run."""
    mod, cfg = load_config("kff_late")
    pool = mod.make_pool(dict(cfg, late_share=0.0),
                         np.random.default_rng(3), BATCH, 4)
    ops, got = run_config("kff_late", pool)
    assert ops[-1].stage_counters()["ffat_late_lanes"] == 0
    assert mod.program_checks(cfg, ops)["late_lanes_absent"] == (1, 0)


# ---- tracing: the fallback's scope --------------------------------------

def test_the_fallbacks_carry_their_scopes():
    """The whole batch's scatters run under ``scatter``; in the partial
    branch the compaction runs under ``spill`` and its scatter under
    ``scatter``; the fast branch opens neither."""
    fold, args, _, branches = fold_program()
    in_scatter = [path for _, path in equations(branches["scatter"])]
    assert in_scatter and all(p.split("/")[0] == "scatter"
                              for p in in_scatter)
    partial = {p.split("/")[0] for _, p in equations(branches["partial"])}
    assert {"spill", "scatter"} <= partial
    scatters_in_partial = [p for e, p in equations(branches["partial"])
                           if e.primitive.name.startswith("scatter")]
    assert scatters_in_partial and all(p.split("/")[0] == "scatter"
                                       for p in scatters_in_partial)
    assert not any({"scatter", "spill"} & set(p.split("/"))
                   for _, p in equations(branches["fast"]))
    hlo = fold.lower(*args).as_text(debug_info=True)
    assert re.search(r'cond/branch_0_fun/cond/branch_0_fun/scatter/'
                     r'scatter-add"', hlo)
    assert re.search(r'cond/branch_0_fun/cond/branch_1_fun/scatter/'
                     r'scatter-add"', hlo)
    assert re.search(r'cond/branch_0_fun/cond/branch_1_fun/spill/', hlo)
    # the fast branch, the outer cond's second, opens neither scope
    assert not re.search(r'[^/]+/cond/branch_1_fun/(scatter|spill)/',
                         hlo.replace("cond/branch_0_fun/cond/", ""))


def test_the_cells_step_carries_the_scopes_under_insert_fold():
    mod, cfg = load_config("kff_late")
    ops, step, args = chain_step(cfg, mod, BATCH)
    hlo = step.lower(*args).as_text(debug_info=True)
    window = ops[-1].scope_name()
    assert window == "Key_FFAT:kff_late_window"
    fold = rf'/{window}/insert/fold/cond/branch_0_fun/cond/'
    assert re.search(fold + r'branch_0_fun/scatter/scatter-add"', hlo)
    assert re.search(fold + r'branch_1_fun/scatter/scatter-add"', hlo)
    assert re.search(fold + r'branch_1_fun/spill/', hlo)


# ---- the two readers -----------------------------------------------------

def reader(name):
    spec = importlib.util.spec_from_file_location(
        "layer_metric_" + name,
        os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


WINDOW = "jit(step)/Key_FFAT:kff_late_window"
OPS = [{"scope": f"{WINDOW}/insert/fold/cond/branch_0_fun/scatter/"
                 "scatter-add", "ns": 24e6},
       {"scope": f"{WINDOW}/insert/fold/cond/branch_0_fun/scatter/add",
        "ns": 4e6},
       {"scope": f"{WINDOW}/insert/fold/add", "ns": 2e6},
       {"scope": f"{WINDOW}/insert/fold/cond/branch_1_fun/dot_general",
        "ns": 1e6},
       {"scope": f"{WINDOW}/insert/scatter/x", "ns": 64e6},
       {"scope": f"{WINDOW}/emit/gather/gather", "ns": 1e6},
       {"scope": None, "ns": 16e6}]


def test_the_scatter_reader_reads_its_scope_and_nothing_without_it():
    read = reader("ffat_fold_scatter_device_ms").read
    run = {"trace_path": "no file is read", "slice_batches": 4,
           "span_reduce": {"device_ops": OPS}}
    assert read(run) == 7.0
    run["span_reduce"] = {"device_ops": OPS[2:]}
    assert read(run) is None
    testdata = os.path.join(BENCH, "testdata")
    assert read({"trace_path": os.path.join(testdata, "kff_timeline.xplane.pb"),
                 "slice_batches": 16}) is None          # in order: never taken
    assert read({"trace_path": None, "slice_batches": 0}) is None


def test_the_readers_take_the_partial_branchs_scatter_and_spill():
    """The partial branch's scatter lies under ``scatter`` one ``cond``
    deeper: the scatter reader takes it, and not the compaction under
    ``spill``; the fold's reader takes both."""
    scatter = reader("ffat_fold_scatter_device_ms")
    fold = reader("ffat_fold_device_ms")
    inner = f"{WINDOW}/insert/fold/cond/branch_0_fun/cond/branch_1_fun"
    ops = [{"scope": f"{inner}/scatter/scatter-add", "ns": 8e6},
           {"scope": f"{inner}/spill/reduce_sum", "ns": 4e6},
           {"scope": f"{inner}/dot_general", "ns": 12e6}]
    run = {"trace_path": "no file is read", "slice_batches": 4,
           "span_reduce": {"device_ops": ops}}
    assert scatter.read(run) == 2.0
    assert fold.read(run) == 6.0


def test_the_roofline_reader_counts_the_folds_least_bytes(tmp_path):
    mod = reader("ffat_fold_roofline")
    cfg_mod, published = published_config()
    need = mod.fold_min_bytes(cfg_mod, published, 1 << 20)
    # key, ts and value of a batch, and [512, 256] partials and counts in
    # and out
    assert need == 3 * 4 * (1 << 20) + 2 * 2 * 512 * 256 * 4
    trace = os.path.join(ROOT, ".bench_trace", "kff_late.backlog", "plugins",
                         "profile", "x", "host.xplane.pb")
    assert mod.cell_of(trace)[1:] == (published, 1 << 20)
    assert mod.cell_of(str(tmp_path / "x.xplane.pb")) is None
    run = {"trace_path": trace, "slice_batches": 4,
           "span_reduce": {"device_ops": OPS},
           "peaks": {"hbm_bytes_per_s": 819.0e9}}
    fold_ms = (24 + 4 + 2 + 1) / 4
    assert mod.read(run) == pytest.approx(
        100 * need / 819.0e9 / (fold_ms / 1e3))
    assert mod.read(dict(run, peaks=None)) is None
    assert mod.read(dict(run, span_reduce={"device_ops": OPS[4:]})) is None


def test_the_benchmark_declares_the_cell_and_its_two_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (config,) = [c for c in bench["configs"] if c["name"] == "kff_late"]
    assert config["reduced"] == []
    assert config["file"] == "benchmark/configs/kff_late.json"
    (cell,) = [w for w in bench["workloads"]
               if w["name"] == "kff_late.backlog"]
    assert cell == {
        "name": "kff_late.backlog", "config": "kff_late",
        "traffic": "backlog", "chips": 1, "why": cell["why"]}
    declared = {m["name"]: m for m in bench["per_layer"]
                if m.get("workloads") == ["kff_late.backlog"]}
    assert declared == {
        "ffat_fold_scatter_device_ms": {
            "name": "ffat_fold_scatter_device_ms", "unit": "ms",
            "better": "lower", "source": "device_trace",
            "layer": "compiled chain + operators", "moves": "tuples_per_s",
            "workloads": ["kff_late.backlog"]},
        "ffat_fold_roofline": {
            "name": "ffat_fold_roofline", "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": "tuples_per_s", "workloads": ["kff_late.backlog"]}}
    # kff.backlog's traffic letter for letter
    with open(os.path.join(BENCH, "workloads", "kff.backlog.json")) as f:
        kff = json.load(f)
    with open(os.path.join(BENCH, "workloads", "kff_late.backlog.json")) as f:
        late = json.load(f)
    assert (late["traffic"], late["rehearsal"]) == (kff["traffic"],
                                                    kff["rehearsal"])


def test_rehearsal_of_the_new_cell_exits_zero(tmp_path):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--rehearsal",
         "--workload", "kff_late.backlog", "--seed", "3800000033",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert proc.stdout == ""                        # a rehearsal prints no metric
    last = json.loads(proc.stderr.splitlines()[-1].split(" ", 1)[1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["compared"]["late_lanes_absent"]["value"] == 0
