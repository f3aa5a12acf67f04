"""``pane_wlq_device_ms``: device time per batch of the traced slice under
``Pane_Farm``'s ``wlq`` scope (the window-level engine over the pane
results: their sort and ring writes, the gather of every closed window's
panes, the window function), self time by the ``XLA Ops`` line. None where
the program scopes no such stage."""

import pane_reduce


def read(run):
    return pane_reduce.stage_ms(run, "wlq")
