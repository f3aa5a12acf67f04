"""``device_clock_offset_ms``: how much later the device plane belongs on the host
plane's clock: the middle of the interval causality leaves over the steps of
the traced slice (``timeline_reduce.py``).  One value a slice; None under 8
paired steps, and for a program without ``wf.chain.dispatch``."""

import timeline_reduce


def read(run):
    return timeline_reduce.metric(run, "device_clock_offset_ms")
