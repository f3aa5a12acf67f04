"""``source_frame_ms``: time per batch of the traced slice inside the
program's ``wf.source.frame`` span, read from the profiler's file: the
prefetch thread in ``_frame``: pad, narrow, ids, mask."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.source.frame")
