"""``archive_insert_device_ms``: device time per batch of the traced slice
under the archive window engine's ``insert`` phase (``Win_Seq._insert``: the
rank, the per-key count and watermark, the per-lane writes into the rings),
self time by the ``XLA Ops`` line. None where the program scopes no engine."""

import span_reduce


def read(run):
    return span_reduce.window_ms(run, "insert")
