"""``sink_d2h_ms``: time per batch of the traced slice inside the program's
``wf.sink.d2h`` span, read from the profiler's file: the sink's copy back:
waits for the device to finish the batch, then copies it to the host."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.sink.d2h")
