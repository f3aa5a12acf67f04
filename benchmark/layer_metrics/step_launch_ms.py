"""``step_launch_ms``: from ``wf.chain.dispatch``'s start to the start of the
step's ``XLA Modules`` event, the device plane put on the host's clock by the
offset causality bounds (``timeline_reduce.py``: uncertain by half of
``device_clock_slack_ms``).  Median over the batches of the traced slice; None
under 8 rows, and for a program without ``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "step_launch_ms")
