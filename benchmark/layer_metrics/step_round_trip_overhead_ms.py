"""``step_round_trip_overhead_ms``: (``wf.sink.d2h``'s end - ``wf.chain.dispatch``'s
start) - the duration of the step's ``XLA Modules`` event, per batch: all the
host-visible time around a step that is not the step.  Two differences, each
on one clock, so it needs no offset.  Median over the batches of the traced
slice (``timeline_reduce.py``); None under 8 rows, and for a program without
``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "step_round_trip_overhead_ms")
