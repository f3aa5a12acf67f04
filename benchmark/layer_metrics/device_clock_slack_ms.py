"""``device_clock_slack_ms``: the width of the interval causality leaves for the
device plane's offset (``timeline_reduce.py``): what ``step_launch_ms`` and
``step_done_to_host_ms`` are uncertain by, and the smallest launch plus the
smallest completion latency the slice saw.  One value a slice; None under 8
paired steps, and for a program without ``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "device_clock_slack_ms")
