"""Driver matrix, SupervisedPipeline: every window configuration of the
mp_test matrix (tests/test_mp_matrix.py CASES) delivers through
``SupervisedPipeline`` (no fault; two checkpoints inside the six batches)
exactly what ``wf.Pipeline`` delivers, in delivery order.  One of three files
(threaded / graph / supervised) that are the net under ROADMAP C1."""

import pytest

import windflow_tpu as wf

from test_mp_matrix import CASES, DRIVER_BATCH, delivered, pipeline_delivered


def drive(src, ops, sink):
    wf.SupervisedPipeline(src, ops, sink, batch_size=DRIVER_BATCH,
                          checkpoint_every=3).run()


@pytest.mark.parametrize("case", sorted(CASES))
def test_supervised_delivers_what_pipeline_delivers(case):
    want = pipeline_delivered(CASES[case])
    assert want, f"{case}: produced no windows"
    assert delivered(CASES[case], drive) == want
