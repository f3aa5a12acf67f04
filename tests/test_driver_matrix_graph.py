"""Driver matrix, PipeGraph: every window configuration of the mp_test
matrix (tests/test_mp_matrix.py CASES) delivers through a one-pipe
``PipeGraph`` exactly what ``wf.Pipeline`` delivers, in delivery order.  One
of three files (threaded / graph / supervised) that are the net under
ROADMAP C1."""

import pytest

import windflow_tpu as wf

from test_mp_matrix import CASES, DRIVER_BATCH, delivered, pipeline_delivered


def drive(src, ops, sink):
    g = wf.PipeGraph("g", batch_size=DRIVER_BATCH)
    mp = g.add_source(src)
    for op in ops:
        mp = mp.chain(op)
    mp.add_sink(sink)
    g.run()


@pytest.mark.parametrize("case", sorted(CASES))
def test_graph_delivers_what_pipeline_delivers(case):
    want = pipeline_delivered(CASES[case])
    assert want, f"{case}: produced no windows"
    assert delivered(CASES[case], drive) == want
