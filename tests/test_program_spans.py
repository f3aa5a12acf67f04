"""The program's own spans (``observability/tracing.py::span``) and operator
scopes, as a profiler session records them — counts and structure on the CPU
backend, no timing.

A small ``Pipeline.run`` with ``prefetch=2`` over a ``RecordSource`` must emit
every ``wf.*`` span of docs/ARCHITECTURE.md's table, once per batch where the
table says so, children inside parents on the same thread, one ``pos`` on the
spans of one batch; with no session and no ``Tracer`` nothing changes; the
lowered chains of the two benchmark configurations name every operator and
both window-engine phases while their cost analysis stays what it was.
"""

import contextlib
import glob
import importlib.util
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu.batch import Batch
from windflow_tpu.observability import tracing
from windflow_tpu.runtime.pipeline import CompiledChain

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
import xplane_meta  # noqa: E402  (the benchmark's reader of the profiler's file)

BATCH = 4096
N_BATCHES = 9
#: once per batch on the prefetch thread / on the drive thread
PREFETCH_SPANS = ("wf.source.unpack", "wf.source.frame", "wf.source.h2d",
                  "wf.source.put")
DRIVE_SPANS = ("wf.chain.push", "wf.chain.dispatch", "wf.sink.consume",
               "wf.sink.d2h")


def load_config(name):
    spec = importlib.util.spec_from_file_location(
        "bench_cfg_" + name, os.path.join(BENCH, "configs", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    cfg.update(cfg.get("rehearsal", {}))
    return mod, cfg


def run_kcb(n_batches=N_BATCHES, **pipeline_kw):
    """The kcb chain over ``n_batches`` record chunks; everything the sink got."""
    mod, cfg = load_config("kcb")
    pool = mod.make_pool(cfg, np.random.default_rng(7), BATCH, n_batches)

    def records():
        for j, recs in enumerate(pool):
            mod.stamp(cfg, recs, j * BATCH)
            yield recs

    got = []

    def deliver(view):
        if view is not None:
            got.append((view["key"].tolist(), view["id"].tolist(),
                        np.asarray(view["payload"]).tolist()))
    src = wf.RecordSource(records, mod.RECORD, key_field=mod.KEY_FIELD,
                          ts_field=mod.TS_FIELD, name="spans_records")
    wf.Pipeline(src, mod.build_ops(cfg, BATCH), wf.Sink(deliver),
                batch_size=BATCH, prefetch=2, **pipeline_kw).run()
    return got


@contextlib.contextmanager
def profiler_session(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One profiled run: (results, {thread line: [wf.* events by start]})."""
    run_kcb(3)                      # compile outside the session
    trace_dir = tmp_path_factory.mktemp("spans_trace")
    with profiler_session(trace_dir):
        got = run_kcb()
    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    threads = {}
    for plane in xplane_meta.read(paths[0]):
        if plane["name"] != "/host:CPU":
            continue
        for line in plane["lines"]:
            evs = sorted((e for e in line["events"]
                          if e["name"].startswith("wf.")),
                         key=lambda e: (e["start_ns"], -e["end_ns"]))
            if evs:
                threads[(line["name"], line["id"])] = evs
    return got, threads


def by_name(threads):
    out = {}
    for evs in threads.values():
        for e in evs:
            out.setdefault(e["name"], []).append(e)
    return out


def test_every_span_of_the_table_once_per_batch(traced):
    _, threads = traced
    spans = by_name(threads)
    for name in PREFETCH_SPANS + DRIVE_SPANS:
        assert sorted(e["stats"]["pos"] for e in spans[name]
                      if "pos" in e["stats"]) == list(range(N_BATCHES)), name
    # the pull and the wait that find the stream's end count one more
    assert len(spans["wf.source.next"]) == N_BATCHES + 1
    assert len(spans["wf.drive.ingest_wait"]) == N_BATCHES + 1
    assert len(spans["wf.chain.flush"]) == 1
    # pushes 2, 4, 8 of the run wait for the device (CompiledChain._sampled)
    sampled = [e["stats"]["pos"] for e in spans["wf.chain.push"]
               if e["stats"]["sampled"]]
    assert sampled == [1, 3, 7]
    # the completion wait is on its batch's row: it carries the push's pos
    assert [e["stats"]["pos"] for e in spans["wf.chain.sync"]] == sampled
    # EOS: the flushed batch and the None marker reach the sink without a pos
    flushed = [e for e in spans["wf.sink.consume"] if "pos" not in e["stats"]]
    assert len(flushed) == 2
    assert len(spans["wf.sink.d2h"]) == N_BATCHES + 1
    assert len(spans["wf.sink.deliver"]) <= N_BATCHES + 1


def test_counts_ride_on_the_spans(traced):
    _, threads = traced
    spans = by_name(threads)
    mod, _ = load_config("kcb")
    assert {e["stats"]["bytes_in"] for e in spans["wf.source.unpack"]} == {
        BATCH * mod.RECORD.itemsize}
    # int32 key, id, ts + the mask, and the record's 8-byte id and value
    assert {e["stats"]["bytes_out"] for e in spans["wf.source.frame"]} == {
        BATCH * (13 + 8 + 8)}
    # the device holds both in 32 bits (no x64)
    assert {e["stats"]["bytes"] for e in spans["wf.source.h2d"]} == {
        BATCH * (13 + 4 + 4)}
    # "always 1" since scan dispatch went (PR 29): the argument went too
    assert all(set(e["stats"]) == {"pos", "sampled"}
               for e in spans["wf.chain.push"])
    assert all(set(e["stats"]) == {"pos"} for e in spans["wf.chain.dispatch"])
    assert all(0 <= e["stats"]["queued"] <= 2
               for e in spans["wf.drive.ingest_wait"])
    assert all(e["stats"]["bytes"] > 0 for e in spans["wf.sink.d2h"])
    assert sum(e["stats"]["n_live"] for e in spans["wf.sink.deliver"]) > 0


def test_children_inside_parents_on_one_thread(traced):
    _, threads = traced
    parent_of = {"wf.chain.dispatch": "wf.chain.push",
                 "wf.chain.sync": "wf.chain.push",
                 "wf.sink.d2h": "wf.sink.consume",
                 "wf.sink.deliver": "wf.sink.consume"}
    seen = set()
    for evs in threads.values():
        stack = []
        for e in evs:
            while stack and stack[-1]["end_ns"] <= e["start_ns"]:
                stack.pop()
            if stack:       # events of one thread nest, they never cross
                assert e["end_ns"] <= stack[-1]["end_ns"]
            want = parent_of.get(e["name"])
            if want is not None:
                assert stack and stack[-1]["name"] == want, e["name"]
                if "pos" in e["stats"]:
                    assert e["stats"]["pos"] == stack[-1]["stats"]["pos"]
                seen.add(e["name"])
            stack.append(e)
    assert seen == set(parent_of)


def test_dispatch_once_a_batch_inside_its_push(traced):
    """``wf.chain.dispatch`` is the ``jit`` call alone: one a push, on the
    drive thread, inside that push's span and before its sampled sync."""
    _, threads = traced
    (drive,) = [evs for evs in threads.values()
                if any(e["name"] == "wf.chain.push" for e in evs)]
    pushes = [e for e in drive if e["name"] == "wf.chain.push"]
    dispatches = [e for e in drive if e["name"] == "wf.chain.dispatch"]
    syncs = {e["stats"]["pos"]: e for e in drive
             if e["name"] == "wf.chain.sync"}
    assert len(dispatches) == len(pushes) == N_BATCHES
    for push, dispatch in zip(pushes, dispatches):
        assert dispatch["stats"]["pos"] == push["stats"]["pos"]
        assert push["start_ns"] <= dispatch["start_ns"]
        assert dispatch["end_ns"] <= push["end_ns"]
        sync = syncs.get(push["stats"]["pos"])
        if sync is not None:
            assert dispatch["end_ns"] <= sync["start_ns"]


def test_source_spans_on_the_prefetch_thread(traced):
    _, threads = traced
    where = {}
    for (line_name, line_id), evs in threads.items():
        for e in evs:
            where.setdefault(e["name"].split(".")[1], set()).add(
                (line_name, line_id))
    assert len(where["source"]) == 1
    assert where["drive"] == where["chain"] == where["sink"]
    assert len(where["drive"]) == 1 and where["drive"] != where["source"]
    if sys.platform.startswith("linux"):    # tracing.name_thread
        assert {name for name, _ in where["source"]} == {"wf-prefetch"}


def test_results_identical_without_a_session(traced):
    got, _ = traced
    assert tracing.get_active() is None
    assert run_kcb() == got


def test_flight_recorder_rows_unchanged(tmp_path, traced):
    got, _ = traced
    out_dir = tmp_path / "flight"
    assert run_kcb(trace=str(out_dir)) == got
    records, meta = tracing.load_flight(str(out_dir))
    assert meta["minted"] == N_BATCHES
    rows = {}
    for r in records:
        rows.setdefault((r["stage"], r["kind"]), []).append(
            tracing.trace_pos(r["tid"]))
    every = list(range(N_BATCHES))
    assert sorted(rows[("ingest", tracing.K_INGEST)]) == every
    for stage in ("chain", "sink"):
        assert sorted(rows[(stage, tracing.K_BEGIN)]) == every
        assert sorted(rows[(stage, tracing.K_END)]) == every
    assert {stage for stage, _ in rows} == {"ingest", "chain", "sink"}


def test_span_leaves_out_none_and_keeps_aborted_rows_open(tmp_path):
    with tracing.span("wf.test", pos=None, n=3):
        pass
    tracer = tracing.Tracer(tracing.TraceConfig(out_dir=str(tmp_path)),
                            "spans").start()
    try:
        b = Batch.empty(4, {})
        tracing.ingest(b, 5)
        assert tracing.pos_of(b) == 5
        out = Batch.empty(4, {})
        tracing.carry(b, out)
        assert (tracing.pos_of(out), tracing.tid_of(out)) == (5, tracing.tid_of(b))
        with pytest.raises(RuntimeError):
            with tracing.span("stage", b):
                raise RuntimeError("fault")
        kinds = [r["kind"] for r in tracer.records() if r["stage"] == "stage"]
        assert kinds == [tracing.K_BEGIN]
        tracer.abort_open("fault")
        ends = [r for r in tracer.records() if r["kind"] == tracing.K_END]
        assert [r.get("aborted") for r in ends] == ["fault"]
    finally:
        tracer.finish()


def test_xprof_trace_is_readable_and_holds_the_spans(tmp_path):
    """``wf.xprof_trace`` opens its session with the Python tracer off: the
    capture holds the program's spans and no event per Python call (those are
    named ``$<file>:<line> <function>``)."""
    with wf.xprof_trace(str(tmp_path)):
        run_kcb(3)
    paths = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(paths) == 1
    names = [e["name"] for plane in xplane_meta.read(paths[0])
             if plane["name"] == "/host:CPU"
             for line in plane["lines"] for e in line["events"]]
    assert names.count("wf.chain.push") == 3
    assert names.count("wf.source.h2d") == 3
    assert not [n for n in names if n.startswith("$")]


# ---- operator scopes in the lowered programs --------------------------------


def lowered_chain(name, scoped, monkeypatch):
    mod, cfg = load_config(name)
    if not scoped:
        monkeypatch.setattr(jax, "named_scope",
                            lambda _name: contextlib.nullcontext())
    ops = mod.build_ops(cfg, BATCH)
    src = wf.RecordSource(lambda: iter(()), mod.RECORD,
                          key_field=mod.KEY_FIELD, ts_field=mod.TS_FIELD)
    chain = CompiledChain(ops, src.payload_spec(), batch_capacity=BATCH)
    batch = Batch.empty(BATCH, chain.specs[0])
    return ops, chain._step_fn(0).lower(tuple(chain.states), batch)


def cost(compiled):
    ca = compiled.cost_analysis()
    return ca[0] if isinstance(ca, (list, tuple)) else ca


#: a projection that only drops columns traces no equation to name
NO_EQUATIONS = {"BatchMap:ysb_project"}


#: what each window engine's insert says its work is
INSERT_SCOPES = {
    "ysb": (),
    # ops/segment.py::segment_run_fold and the run-sized table writes
    "kcb": ("rank/sort", "rank/runs", "rank/scan", "fold/write"),
    # the archive engine (operators/win_seq.py::_insert): one sort, the rows
    # it cuts, the per-key counts and the row moves
    "ysb_wmr": ("rank/sort", "rank/runs", "count", "write"),
}


@pytest.mark.parametrize("name", ["ysb", "kcb", "ysb_wmr"])
def test_lowered_chain_names_every_operator_and_phase(name, monkeypatch):
    ops, lowered = lowered_chain(name, True, monkeypatch)
    hlo = lowered.as_text(debug_info=True)
    for op in ops:
        if op.scope_name() not in NO_EQUATIONS:
            assert f"/{op.scope_name()}/" in hlo, op.scope_name()
    window = ops[-1].scope_name()
    engine = "Win_MapReduce:" if name == "ysb_wmr" else "Key_FFAT:"
    assert window.startswith(engine)
    for phase in ("insert", "emit"):
        assert f"/{window}/{phase}/" in hlo, phase
    for sub in INSERT_SCOPES[name]:
        assert f"/{window}/insert/{sub}/" in hlo, sub
    # the compiled program keeps the scopes as op_name metadata and nothing
    # else of it moved
    compiled = lowered.compile()
    assert f'op_name="jit(step)/{window}/insert/' in compiled.as_text()
    _, unscoped = lowered_chain(name, False, monkeypatch)
    assert engine not in unscoped.as_text(debug_info=True)
    assert cost(compiled) == cost(unscoped.compile())


CACHED_SCOPES = """
import contextlib, sys
import jax, jax.numpy as jnp
from windflow_tpu.runtime.compile_cache import enable_compile_cache
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
def make(scope):
    def step(x):
        with (jax.named_scope(scope) if scope else contextlib.nullcontext()):
            return jnp.sort(x * 2 + 1).sum()
    return jax.jit(step)
x = jnp.arange(4096.0)
make(None)(x).block_until_ready()       # the unscoped program, persisted
jax.clear_caches()
print("SCOPED", 'op_name="jit(step)/Filter:f/mul"'
      in make("Filter:f").lower(x).compile().as_text())
hits = []
jax.monitoring.register_event_listener(
    lambda event, **kw: hits.append(event)
    if event == "/jax/compilation_cache/cache_hits" else None)
def site_a():
    return make("Filter:f")(x).block_until_ready()
def site_b():
    return make("Filter:f")(x).block_until_ready()
jax.clear_caches()
site_a()
before = len(hits)
jax.clear_caches()
site_b()                                # the same program, another call stack
print("SHARED", len(hits) > before)
"""


def test_compile_cache_does_not_hand_back_an_unscoped_executable(tmp_path):
    """JAX's default cache key strips metadata: a program that differs from a
    cached one only by its scopes got the old executable, and PR 25's first
    traced chip run showed no scope at all. ``enable_compile_cache`` keys on
    the metadata too, and locates an operation by its own source line, so
    that the same program built from another call site (the benchmark's
    measured pipeline after its throw-away one) still finds its executable."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, "-c", CACHED_SCOPES], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "SCOPED True" in proc.stdout
    assert "SHARED True" in proc.stdout
    assert os.listdir(tmp_path)         # something was persisted
