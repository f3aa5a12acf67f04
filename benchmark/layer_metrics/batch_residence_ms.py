"""``batch_residence_ms``: from the end of a batch's ``wf.source.next`` to the end
of its ``wf.sink.deliver`` (of its ``wf.sink.consume`` where it delivers
nothing), joined on ``pos``: the program's own reading of a result's latency
in a closed loop.  Median over the batches of the traced slice
(``timeline_reduce.py``); None under 8 rows, and for a program without
``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "batch_residence_ms")
