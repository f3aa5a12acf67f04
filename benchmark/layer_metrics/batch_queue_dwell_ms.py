"""``batch_queue_dwell_ms``: from the end of a batch's ``wf.source.put`` to the
end of the ``wf.drive.ingest_wait`` that took it, joined on ``pos``: how long
a device batch sat in the prefetch queue.  Median over the batches of the
traced slice (``timeline_reduce.py``); None under 8 rows, and for a program
without ``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "batch_queue_dwell_ms")
