"""The mp_test matrix, TPU edition: {Win_Seq, Win_Farm, Key_Farm, Key_FFAT,
Pane_Farm, Win_MapReduce} × {CB, TB} × randomized geometry.

The reference's 36-test mp_test_cpu suite re-runs each topology with random
parallelism degrees in [1,9] and asserts the sink total is invariant
(src/graph_test/test_graph_1.cpp:77-87). The TPU analogue of "parallelism degree" is
execution geometry: batch size and window budgets. Each case runs the same stream
under randomized geometries and asserts identical window results."""

import numpy as np
import pytest
import jax.numpy as jnp

import windflow_tpu as wf
from windflow_tpu.basic import win_type_t
from windflow_tpu.operators.window import WindowSpec
from windflow_tpu.operators.win_seq import Win_Seq
from windflow_tpu.operators.win_patterns import (Win_Farm, Key_Farm, Key_FFAT,
                                                 Pane_Farm, Win_MapReduce)

TOTAL, K = 240, 3
rng = np.random.default_rng(7)


def build_case(make_op):
    """One case's stream, operators and collector: ``(src, ops, results,
    cb)`` — ``cb`` appends each delivered ``(key, id, value)`` to ``results``
    in delivery order.  Shared with the driver-matrix files
    (tests/test_driver_matrix_*.py)."""
    src = wf.Source(lambda i: {"v": ((i * 13) % 23).astype(jnp.float32)},
                    total=TOTAL, num_keys=K)
    results = []

    def cb(view):
        if view is None:
            return
        for k, w, r in zip(view["key"].tolist(), view["id"].tolist(),
                           np.asarray(view["payload"]).tolist()):
            results.append((k, w, round(float(r), 3)))

    ops = make_op()
    if not isinstance(ops, (list, tuple)):
        ops = [ops]
    return src, list(ops), results, cb


def delivered(make_op, drive):
    """What ``drive(src, ops, sink)`` delivers for one case, in delivery
    order."""
    src, ops, results, cb = build_case(make_op)
    drive(src, ops, wf.Sink(cb))
    return results


#: the batch size of the driver-matrix files: six batches of the stream
DRIVER_BATCH = 40


def pipeline_delivered(make_op, batch_size=DRIVER_BATCH):
    """The reference of the driver matrix: what ``wf.Pipeline`` delivers."""
    return delivered(make_op, lambda src, ops, sink: wf.Pipeline(
        src, ops, sink, batch_size=batch_size).run())


def run_case(make_op, batch_size):
    return sorted(pipeline_delivered(make_op, batch_size))


CASES = {
    "win_seq_cb": lambda: Win_Seq(lambda wid, it: it.sum("v"),
                                  WindowSpec(8, 4, win_type_t.CB), num_keys=K),
    "win_seq_tb": lambda: Win_Seq(lambda wid, it: it.sum("v"),
                                  WindowSpec(12, 6, win_type_t.TB), num_keys=K),
    "win_farm_cb": lambda: Win_Farm(lambda wid, it: it.sum("v"),
                                    WindowSpec(10, 5, win_type_t.CB),
                                    parallelism=4, num_keys=K),
    "key_farm_cb": lambda: Key_Farm(lambda wid, it: it.max("v"),
                                    WindowSpec(6, 3, win_type_t.CB),
                                    parallelism=3, num_keys=K),
    "key_ffat_cb": lambda: Key_FFAT(lambda t: t.v, jnp.add,
                                    spec=WindowSpec(8, 2, win_type_t.CB),
                                    num_keys=K),
    "key_ffat_tb": lambda: Key_FFAT(lambda t: t.v, jnp.add,
                                    spec=WindowSpec(10, 5, win_type_t.TB),
                                    num_keys=K),
    "pane_farm_cb": lambda: Pane_Farm(lambda pid, it: it.sum("v"),
                                      lambda wid, it: it.sum(),
                                      WindowSpec(9, 3, win_type_t.CB), num_keys=K),
    "wmr_cb": lambda: Win_MapReduce(lambda wid, it: it.sum("v"),
                                    lambda wid, it: it.sum(),
                                    WindowSpec(8, 8, win_type_t.CB),
                                    map_parallelism=2, num_keys=K),
    "win_farm_tb": lambda: Win_Farm(lambda wid, it: it.sum("v"),
                                    WindowSpec(12, 4, win_type_t.TB),
                                    parallelism=4, num_keys=K),
    "key_farm_tb": lambda: Key_Farm(lambda wid, it: it.max("v"),
                                    WindowSpec(10, 5, win_type_t.TB),
                                    parallelism=3, num_keys=K),
    "pane_farm_tb": lambda: Pane_Farm(lambda pid, it: it.sum("v"),
                                      lambda wid, it: it.sum(),
                                      WindowSpec(12, 4, win_type_t.TB), num_keys=K),
    "wmr_tb": lambda: Win_MapReduce(lambda wid, it: it.sum("v"),
                                    lambda wid, it: it.sum(),
                                    WindowSpec(12, 12, win_type_t.TB),
                                    map_parallelism=3, num_keys=K),
    "nested_wf_pf_cb": lambda: Win_Farm(
        Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                  WindowSpec(9, 3, win_type_t.CB), num_keys=K), parallelism=2),
    "nested_kf_wmr_cb": lambda: Key_Farm(
        Win_MapReduce(lambda wid, it: it.sum("v"), lambda wid, it: it.sum(),
                      WindowSpec(8, 8, win_type_t.CB), map_parallelism=2,
                      num_keys=K), parallelism=2),
    # remaining reference nesting combos (test_mp_wf+wmr_*.cpp, test_mp_kf+pf_*.cpp)
    "nested_wf_wmr_cb": lambda: Win_Farm(
        Win_MapReduce(lambda wid, it: it.sum("v"), lambda wid, it: it.sum(),
                      WindowSpec(8, 8, win_type_t.CB), map_parallelism=2,
                      num_keys=K), parallelism=2),
    "nested_kf_pf_cb": lambda: Key_Farm(
        Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                  WindowSpec(9, 3, win_type_t.CB), num_keys=K), parallelism=2),
    "nested_wf_pf_tb": lambda: Win_Farm(
        Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                  WindowSpec(12, 4, win_type_t.TB), num_keys=K), parallelism=2),
    # chaining variants (test_mp_*_chaining.cpp): stateless ops fused ahead of
    # the windowed pattern — one compiled program, same results
    "kf_cb_chaining": lambda: [wf.Map(lambda t: {"v": t.v + 1.0}),
                               wf.Filter(lambda t: t.v > 2.0),
                               Key_Farm(lambda wid, it: it.max("v"),
                                        WindowSpec(6, 3, win_type_t.CB),
                                        parallelism=3, num_keys=K)],
    "pf_tb_chaining": lambda: [wf.Map(lambda t: {"v": t.v * 2.0}),
                               Pane_Farm(lambda pid, it: it.sum("v"),
                                         lambda wid, it: it.sum(),
                                         WindowSpec(12, 4, win_type_t.TB),
                                         num_keys=K)],
    "wmr_cb_chaining": lambda: [wf.Filter(lambda t: t.v % 2 == 0),
                                Win_MapReduce(lambda wid, it: it.sum("v"),
                                              lambda wid, it: it.sum(),
                                              WindowSpec(8, 8, win_type_t.CB),
                                              map_parallelism=2, num_keys=K)],
    # remaining nested TB combos (test_mp_kf+pf_tb.cpp, test_mp_kf+wmr_tb.cpp,
    # test_mp_wf+wmr_tb.cpp)
    "nested_kf_pf_tb": lambda: Key_Farm(
        Pane_Farm(lambda pid, it: it.sum("v"), lambda wid, it: it.sum(),
                  WindowSpec(12, 4, win_type_t.TB), num_keys=K), parallelism=2),
    "nested_kf_wmr_tb": lambda: Key_Farm(
        Win_MapReduce(lambda wid, it: it.sum("v"), lambda wid, it: it.sum(),
                      WindowSpec(12, 12, win_type_t.TB), map_parallelism=2,
                      num_keys=K), parallelism=2),
    "nested_wf_wmr_tb": lambda: Win_Farm(
        Win_MapReduce(lambda wid, it: it.sum("v"), lambda wid, it: it.sum(),
                      WindowSpec(12, 12, win_type_t.TB), map_parallelism=3,
                      num_keys=K), parallelism=2),
    # remaining chaining combos (test_mp_wf_cb_chaining.cpp, kf_tb_chaining,
    # pf_cb_chaining, wmr_tb_chaining)
    "wf_cb_chaining": lambda: [wf.Map(lambda t: {"v": t.v + 0.5}),
                               Win_Farm(lambda wid, it: it.sum("v"),
                                        WindowSpec(10, 5, win_type_t.CB),
                                        parallelism=4, num_keys=K)],
    "kf_tb_chaining": lambda: [wf.Filter(lambda t: t.v != 3.0),
                               Key_Farm(lambda wid, it: it.max("v"),
                                        WindowSpec(10, 5, win_type_t.TB),
                                        parallelism=3, num_keys=K)],
    "pf_cb_chaining": lambda: [wf.Map(lambda t: {"v": t.v * 3.0}),
                               Pane_Farm(lambda pid, it: it.sum("v"),
                                         lambda wid, it: it.sum(),
                                         WindowSpec(9, 3, win_type_t.CB),
                                         num_keys=K)],
    "wmr_tb_chaining": lambda: [wf.Filter(lambda t: t.v > 1.0),
                                Win_MapReduce(lambda wid, it: it.sum("v"),
                                              lambda wid, it: it.sum(),
                                              WindowSpec(12, 12, win_type_t.TB),
                                              map_parallelism=2, num_keys=K)],
    # _2 geometry variants (the reference's *_tb_2 files re-run with a second
    # window/slide pair)
    "win_seq_tb_2": lambda: Win_Seq(lambda wid, it: it.sum("v"),
                                    WindowSpec(20, 4, win_type_t.TB), num_keys=K),
    "key_farm_tb_2": lambda: Key_Farm(lambda wid, it: it.max("v"),
                                      WindowSpec(15, 5, win_type_t.TB),
                                      parallelism=3, num_keys=K),
    "pane_farm_tb_2": lambda: Pane_Farm(lambda pid, it: it.sum("v"),
                                        lambda wid, it: it.sum(),
                                        WindowSpec(16, 4, win_type_t.TB),
                                        num_keys=K),
    "wmr_tb_2": lambda: Win_MapReduce(lambda wid, it: it.sum("v"),
                                      lambda wid, it: it.sum(),
                                      WindowSpec(18, 18, win_type_t.TB),
                                      map_parallelism=3, num_keys=K),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_result_invariance_under_geometry(case):
    make_op = CASES[case]
    sizes = sorted(set([int(rng.integers(16, 120)), 60, TOTAL]))
    runs = [run_case(make_op, bs) for bs in sizes]
    assert runs[0], f"{case}: produced no windows"
    for r, bs in zip(runs[1:], sizes[1:]):
        assert r == runs[0], f"{case}: results differ at batch_size={bs}"


STRING_OPS = {
    "kf_ffat": lambda: Key_FFAT(lambda t: t.v, jnp.add,
                                spec=WindowSpec(8, 4, win_type_t.CB), num_keys=8),
    "key_farm": lambda: Key_Farm(lambda wid, it: it.max("v"),
                                 WindowSpec(6, 3, win_type_t.CB),
                                 parallelism=3, num_keys=8),
    "win_farm": lambda: Win_Farm(lambda wid, it: it.sum("v"),
                                 WindowSpec(10, 5, win_type_t.CB),
                                 parallelism=4, num_keys=8),
    "pane_farm": lambda: Pane_Farm(lambda pid, it: it.sum("v"),
                                   lambda wid, it: it.sum(),
                                   WindowSpec(9, 3, win_type_t.CB), num_keys=8),
    "wmr": lambda: Win_MapReduce(lambda wid, it: it.sum("v"),
                                 lambda wid, it: it.sum(),
                                 WindowSpec(8, 8, win_type_t.CB),
                                 map_parallelism=2, num_keys=8),
}


@pytest.mark.parametrize("op_name", sorted(STRING_OPS))
def test_string_keyed_windows(op_name):
    """The *_string variants (mp_common_string.hpp: kf/pf/wf/wmr over
    string-keyed tuples): non-integer keys hashed to slots at ingest
    (hash(key) % n); window results invariant under batch size and consistent
    per logical key."""
    import jax
    from windflow_tpu.operators.source import GeneratorSource

    names = np.array(["alpha", "beta", "gamma"])

    def run(bs):
        def it():
            for s in range(0, TOTAL, 60):
                i = np.arange(s, s + 60, dtype=np.int32)
                yield ({"v": ((i * 13) % 23).astype(np.float32)},
                       names[i % 3], i)
        src = GeneratorSource(it, {"v": jax.ShapeDtypeStruct((), jnp.float32)},
                              num_keys=8)
        results = []

        def cb(view):
            if view is None:
                return
            results.extend((int(k), int(w), round(float(r), 3))
                           for k, w, r in zip(view["key"].tolist(),
                                              view["id"].tolist(),
                                              np.asarray(view["payload"]).tolist()))
        wf.Pipeline(src, [STRING_OPS[op_name]()],
                    wf.Sink(cb), batch_size=bs).run()
        return sorted(results)

    a, b = run(60), run(120)
    assert a == b and a
    assert len({k for k, _, _ in a}) == 3       # three logical keys, hashed slots
