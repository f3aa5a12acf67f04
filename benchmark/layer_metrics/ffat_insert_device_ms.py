"""``ffat_insert_device_ms``: device time per batch of the traced slice under
``Key_FFAT``'s ``insert`` phase on the global-time path (the occupancy
histogram, the lift, the fold of a batch's values into the pane ring), self
time by the ``XLA Ops`` line. None where the program scopes no such phase."""

import pane_reduce


def read(run):
    return pane_reduce.stage_ms(run, "insert")
