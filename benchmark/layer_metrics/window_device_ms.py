"""``window_device_ms``: device time per batch of the traced slice in
operations traced under a window engine's scope (an operator whose
``Class:name`` scope holds an ``insert`` or ``emit`` phase), self time by the
``XLA Ops`` line, from each operation's ``tf_op``."""

import span_reduce


def read(run):
    return span_reduce.window_ms(run)
