"""Pillar-1 gate: ``analysis.validate`` passes every real topology shipped in
this repo (the examples' graphs, the mp_test matrix) with zero errors, and
every ``WF1xx`` diagnostic code fires on a minimally-broken graph — the
shift-left counterpart of discovering the same misconfiguration mid-stream."""

import jax.numpy as jnp
import numpy as np
import pytest

import windflow_tpu as wf
from windflow_tpu import ControlConfig, FaultPlan
from windflow_tpu.analysis import ValidationError, validate
from windflow_tpu.basic import win_type_t
from windflow_tpu.benchmarks import ysb
from windflow_tpu.operators.source import GeneratorSource
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.runtime.supervisor import SupervisedPipeline

from test_mp_matrix import CASES, K, TOTAL  # noqa: F401 — topology fixtures


def _sink():
    return wf.Sink(lambda view: None)


def _src(total=200, num_keys=1):
    return wf.Source(lambda i: {"v": ((i * 13) % 23).astype(jnp.float32)},
                     total=total, num_keys=num_keys)


# ---------------------------------------------------- positive: repo graphs


def test_example_01_wordcount_graph_validates():
    """The graph of examples/01_wordcount.py, built but not run."""
    VOCAB = 50

    def make_words(i):
        return {"w": jnp.stack([(i * 7) % VOCAB, (i * 13) % VOCAB,
                                (i * 29) % VOCAB])}

    def split_words(t, shipper):
        for j in range(3):
            shipper.push({"word": t.w[j]})

    g = wf.PipeGraph("wordcount", batch_size=256)
    (g.add_source(wf.Source(make_words, total=3000))
     .add(wf.FlatMap(split_words, max_fanout=3))
     .add(wf.Map(lambda t: {"one": jnp.ones((), jnp.int32), "word": t.word}))
     .add(wf.KeyBy(lambda t: t.word, num_keys=VOCAB))
     .add(wf.Accumulator(lambda t: t.data["one"], init_value=0,
                         num_keys=VOCAB))
     .add_sink(_sink()))
    report = validate(g)
    assert report.ok, str(report)
    assert not report.warnings, str(report)


def test_example_02_ysb_pipeline_validates():
    """The YSB pipeline of examples/02_ysb_windows.py."""
    p = wf.Pipeline(ysb.make_source(total=40_000), list(ysb.make_ops()),
                    _sink(), batch_size=4096)
    report = validate(p)
    assert report.ok, str(report)


def test_example_03_checkpoint_chain_validates():
    """The raw CompiledChain of examples/03_checkpoint_resume.py."""
    src = _src(total=4000, num_keys=8)
    op = wf.Key_FFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(64, 32, win_type_t.CB), num_keys=8)
    chain = wf.CompiledChain([op], src.payload_spec(), batch_capacity=256)
    report = validate(chain)
    assert report.ok, str(report)


def test_example_04_multichip_chain_validates():
    """The (unsharded) chain of examples/04_multichip.py — sharding wraps
    the same compiled chain, so its spec flow is the validated surface."""
    src = wf.Source(lambda i: {"v": ((i * 7) % 31).astype(jnp.float32)},
                    total=8000, num_keys=16)
    op = wf.Key_FFAT(lambda t: t.v, jnp.add,
                     spec=WindowSpec(50, 25, win_type_t.TB), num_keys=16)
    chain = wf.CompiledChain([op], src.payload_spec(), batch_capacity=512)
    report = validate(chain)
    assert report.ok, str(report)


def test_example_05_supervised_pipeline_validates():
    """The SupervisedPipeline of examples/05_recovery_and_backpressure.py."""
    TOT, BATCH, KK = 2000, 100, 4

    def factory(from_batch=0):
        def gen():
            for s in range(from_batch * BATCH, TOT, BATCH):
                ids = np.arange(s, s + BATCH, dtype=np.int32)
                yield ({"v": ((ids * 7) % 31).astype(np.float32)},
                       ids % KK, ids)
        return gen()

    src = GeneratorSource(factory, {"v": jnp.zeros((), jnp.float32)})
    op = wf.Win_Seq(lambda wid, it: it.sum("v"),
                    WindowSpec(25, 25, win_type_t.TB), num_keys=KK)
    sp = SupervisedPipeline(src, [op], _sink(), batch_size=BATCH)
    report = validate(sp)
    assert report.ok, str(report)


@pytest.mark.parametrize("case", sorted(CASES))
def test_mp_matrix_topologies_validate(case):
    """Every mp_test-matrix topology flows specs cleanly end to end."""
    src = _src(total=TOTAL, num_keys=K)
    ops = CASES[case]()
    if not isinstance(ops, (list, tuple)):
        ops = [ops]
    p = wf.Pipeline(src, list(ops), _sink(), batch_size=48)
    report = validate(p)
    assert report.ok, f"{case}:\n{report}"


def test_threaded_pipeline_with_window_validates():
    """A ThreadedPipeline containing a geometry-sensitive (windowed)
    operator validates clean — pins the validator against corrupting the
    already-bound segment chains (bind_geometry must NOT be re-invoked with
    validator-chosen values)."""
    src = _src(total=192, num_keys=K)
    win = wf.Win_Seq(lambda wid, it: it.sum("v"),
                     WindowSpec(12, 6, win_type_t.TB), num_keys=K)
    tp = wf.ThreadedPipeline(src, [[wf.Map(lambda t: {"v": t.v + 1.0})],
                                   [win]],
                             _sink(), batch_size=32, control=False)
    a_before = win.A
    report = validate(tp)
    assert report.ok, str(report)
    assert win.A == a_before, "validator re-bound an already-bound chain"
    assert any(d.where.startswith("seg") for d in report.diagnostics) \
        or not report.diagnostics


def test_split_merge_graph_validates():
    """A split/merge DAG (the PipeGraph-native shape) validates clean."""
    g = wf.PipeGraph("diamond", batch_size=64)
    mp = g.add_source(_src(total=400))
    mp.add(wf.Map(lambda t: {"v": t.v + 1.0}))
    mp.split(lambda t: (t.data["v"] > 10.0).astype(jnp.int32), 2)
    b0 = mp.select(0).add(wf.Map(lambda t: {"v": t.v * 2.0}))
    b1 = mp.select(1).add(wf.Map(lambda t: {"v": t.v * 3.0}))
    merged = b0.merge(b1)
    merged.add(wf.Filter(lambda t: t.v > 0.0)).add_sink(_sink())
    report = validate(g)
    assert report.ok, str(report)
    assert not report.warnings, str(report)


# ------------------------------------------------- negative: each code fires


def test_wf100_empty_graph():
    report = validate(wf.PipeGraph("empty"))
    assert [d.code for d in report.errors] == ["WF100"]


def test_wf100_unknown_object():
    report = validate(object())
    assert [d.code for d in report.errors] == ["WF100"]


def test_wf101_spec_mismatch_between_chained_operators():
    """The tentpole case: an operator destructures a field its upstream does
    not produce — caught pre-run with the operator path in the diagnostic."""
    g = wf.PipeGraph("broken", batch_size=64)
    (g.add_source(_src())
     .add(wf.Map(lambda t: {"x": t.v * 2.0}))       # renames v -> x
     .add(wf.Map(lambda t: {"y": t.v + 1.0}))       # still expects v: broken
     .add_sink(_sink()))
    report = validate(g)
    assert not report.ok
    [err] = report.errors
    assert err.code == "WF101"
    assert "ops[1]" in err.where
    assert "payload" in err.hint


def test_wf101_bad_split_function():
    g = wf.PipeGraph("badsplit", batch_size=64)
    mp = g.add_source(_src())
    mp.split(lambda t: (t.data["nope"] > 0).astype(jnp.int32), 2)
    for i in range(2):
        mp.select(i).add_sink(_sink())
    report = validate(g)
    assert "WF101" in report.codes()
    assert any(".split" in d.where for d in report.errors)


def test_wf102_weak_type_drift():
    """A Python-scalar payload leaf — the retrace hazard — warns, and names
    the leaf."""
    g = wf.PipeGraph("weak", batch_size=64)
    (g.add_source(_src())
     .add(wf.Map(lambda t: {"v": t.v, "c": 1.0}))   # weak f32 constant
     .add_sink(_sink()))
    report = validate(g)
    assert report.ok                                 # warning, not error
    [warn] = [d for d in report.diagnostics if d.code == "WF102"]
    assert "c" in warn.message


def test_wf103_fault_site_not_threaded_through_driver():
    plan = FaultPlan([{"site": "checkpoint.save", "at": [1]}])
    tp = wf.ThreadedPipeline(_src(), [[wf.Map(lambda t: {"v": t.v})]],
                             _sink(), batch_size=32, control=False)
    report = validate(tp, faults=plan)
    [d] = [d for d in report.diagnostics if d.code == "WF103"]
    assert d.severity == "warning" and "checkpoint.save" in d.message
    # the same site IS threaded under supervision: no WF103 there
    p = wf.Pipeline(_src(), [wf.Map(lambda t: {"v": t.v})], _sink(),
                    batch_size=32, control=False)
    assert "WF103" not in validate(p, faults=plan, supervised=True).codes()


def test_wf103_unparseable_plan_is_error():
    p = wf.Pipeline(_src(), [wf.Map(lambda t: {"v": t.v})], _sink(),
                    batch_size=32, control=False)
    report = validate(p, faults='{"faults": [{"site": "not.a.site"}]}')
    [d] = [d for d in report.diagnostics if d.code == "WF103"]
    assert d.severity == "error"


def test_wf104_watermarks_degenerate_on_tiny_ring():
    tp = wf.ThreadedPipeline(_src(), [[wf.Map(lambda t: {"v": t.v})]],
                             _sink(), batch_size=32, queue_capacity=1,
                             control=ControlConfig(backpressure=True,
                                                   autotune=False))
    report = validate(tp)
    hits = [d for d in report.diagnostics if d.code == "WF104"]
    assert hits and all("capacity 1" in d.message for d in hits)


def test_wf104_illegal_graph_edge_capacity_is_an_error():
    """queue_capacity resolving < 1 would ValueError mid-run(threaded=True);
    the validator surfaces it pre-run — but only under threaded=True, since
    the push driver never builds rings."""
    g = wf.PipeGraph("badcap", batch_size=64, queue_capacity=0)
    g.add_source(_src()).add_sink(_sink())
    [d] = [d for d in validate(g, threaded=True).diagnostics
           if d.code == "WF104"]
    assert d.severity == "error" and "queue_capacity" in d.where
    assert validate(g).ok, "push-driver validation must not check rings"


def test_wf104_clean_on_roomy_ring():
    tp = wf.ThreadedPipeline(_src(), [[wf.Map(lambda t: {"v": t.v})]],
                             _sink(), batch_size=32, queue_capacity=8,
                             control=ControlConfig(backpressure=True,
                                                   autotune=False))
    assert "WF104" not in validate(tp).codes()


def test_wf105_wall_clock_bucket_under_supervision():
    p = wf.Pipeline(_src(), [wf.Map(lambda t: {"v": t.v})], _sink(),
                    batch_size=32, control=False)
    cfg = ControlConfig(admission=True, rate_tps=100.0, autotune=False,
                        backpressure=False)
    report = validate(p, control=cfg, supervised=True)
    [d] = report.errors
    assert d.code == "WF105"
    # the deterministic bucket is legal under supervision
    det = ControlConfig(admission=True, refill_per_batch=32.0,
                        autotune=False, backpressure=False)
    assert validate(p, control=det, supervised=True).ok
    # and the wall-clock bucket is fine WITHOUT supervision
    assert validate(p, control=cfg).ok


def test_wf106_prefetch_exceeds_ring():
    tp = wf.ThreadedPipeline(_src(), [[wf.Map(lambda t: {"v": t.v})]],
                             _sink(), batch_size=32, queue_capacity=4,
                             prefetch=16, control=False)
    [d] = [d for d in validate(tp).diagnostics if d.code == "WF106"]
    assert "16" in d.message and d.severity == "warning"


def test_wf107_dangling_branch():
    g = wf.PipeGraph("dangle", batch_size=64)
    mp = g.add_source(_src())
    mp.split(lambda t: (t.data["v"] > 3).astype(jnp.int32), 2)
    mp.select(0).add_sink(_sink())
    mp.select(1).add(wf.Map(lambda t: {"v": t.v}))   # leaf, no sink
    [d] = [d for d in validate(g).diagnostics if d.code == "WF107"]
    assert d.severity == "warning"


def test_wf107_reduce_sink_is_a_real_terminal():
    """An in-graph ReduceSink terminates a branch — no dangling warning."""
    g = wf.PipeGraph("reduce", batch_size=64)
    (g.add_source(_src())
     .add(wf.ReduceSink(lambda t: t.v, name="total")))
    assert "WF107" not in validate(g).codes()


def test_raise_if_errors():
    g = wf.PipeGraph("broken", batch_size=64)
    (g.add_source(_src())
     .add(wf.Map(lambda t: {"y": t.nope}))
     .add_sink(_sink()))
    report = validate(g)
    with pytest.raises(ValidationError) as ei:
        report.raise_if_errors()
    assert "WF101" in str(ei.value)
    assert ei.value.report is report


def test_report_json_roundtrip():
    g = wf.PipeGraph("empty")
    j = validate(g).to_json()
    assert j["diagnostics"][0]["code"] == "WF100"
    assert j["target"].startswith("PipeGraph")
