"""``source_h2d_ms``: time per batch of the traced slice inside the program's
``wf.source.h2d`` span, read from the profiler's file: the prefetch thread
in ``jax.device_put`` of the framed batch."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.source.h2d")
