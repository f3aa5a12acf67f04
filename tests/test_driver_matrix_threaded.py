"""Driver matrix, ThreadedPipeline: every window configuration of the
mp_test matrix (tests/test_mp_matrix.py CASES) delivers through
``ThreadedPipeline`` exactly what ``wf.Pipeline`` delivers, in delivery
order.  One of three files (threaded / graph / supervised) that are the net
under ROADMAP C1: a drive loop may be merged into another only while these
stay green."""

import pytest

import windflow_tpu as wf

from test_mp_matrix import CASES, DRIVER_BATCH, delivered, pipeline_delivered


def drive(src, ops, sink):
    # two operators run as two segments (a ring between them); anything else
    # as one
    segments = [[op] for op in ops] if len(ops) == 2 else [ops]
    wf.ThreadedPipeline(src, segments, sink, batch_size=DRIVER_BATCH,
                        pin=False).run()


@pytest.mark.parametrize("case", sorted(CASES))
def test_threaded_delivers_what_pipeline_delivers(case):
    want = pipeline_delivered(CASES[case])
    assert want, f"{case}: produced no windows"
    assert delivered(CASES[case], drive) == want
