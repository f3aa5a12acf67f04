"""``ffat_emit_device_ms``: device time per batch of the traced slice under
``Key_FFAT``'s ``emit`` phase on the global-time path (the gather of every
fired window's panes out of the ring, their sum, the clearing of the fired
panes), self time by the ``XLA Ops`` line. None where the program scopes no
such phase."""

import pane_reduce


def read(run):
    return pane_reduce.stage_ms(run, "emit")
