"""``drive_ingest_wait_ms``: time per batch of the traced slice inside the
program's ``wf.drive.ingest_wait`` span, read from the profiler's file: the
drive thread blocked on the prefetch queue (``prefetch_to_device``'s
consumer): the inside twin of the harness's ``ingest_wait_ms``."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.drive.ingest_wait")
