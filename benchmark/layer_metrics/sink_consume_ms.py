"""``sink_consume_ms``: time per batch of the traced slice inside the
program's ``wf.sink.consume`` span, read from the profiler's file:
``Sink.consume``: the inside twin of the harness's ``sink_wait_ms``."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.sink.consume")
