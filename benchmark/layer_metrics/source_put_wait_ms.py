"""``source_put_wait_ms``: time per batch of the traced slice inside the
program's ``wf.source.put`` span, read from the profiler's file: the
prefetch thread blocked on the full queue: the drive loop is not taking."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.source.put")
