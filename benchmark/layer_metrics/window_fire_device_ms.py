"""``window_fire_device_ms``: device time per batch of the traced slice under
the archive window engine's ``emit`` phase (``Win_Seq._emit``: the fired
range, the row gather, and the window function, here MAP and REDUCE), self
time by the ``XLA Ops`` line. None where the program scopes no engine."""

import span_reduce


def read(run):
    return span_reduce.window_ms(run, "emit")
