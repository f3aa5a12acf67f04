"""``step_done_to_host_ms``: from the end of the step's ``XLA Modules`` event, the
device plane put on the host's clock by the offset causality bounds
(``timeline_reduce.py``: uncertain by half of ``device_clock_slack_ms``), to
``wf.sink.d2h``'s end.  Median over the batches of the traced slice; None
under 8 rows, and for a program without ``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "step_done_to_host_ms")
