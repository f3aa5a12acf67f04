"""``source_unpack_ms``: time per batch of the traced slice inside the
program's ``wf.source.unpack`` span, read from the profiler's file: the
prefetch thread in the native AoS-to-SoA unpack of a chunk of records (and
the key's slot)."""

import span_reduce


def read(run):
    return span_reduce.span_ms(run, "wf.source.unpack")
