"""``pane_plq_device_ms``: device time per batch of the traced slice under
``Pane_Farm``'s ``plq`` scope (the pane-level engine: sort and ring writes of
the batch's tuples, the gather of every closed pane's row, the pane
function), self time by the ``XLA Ops`` line. None where the program scopes
no such stage."""

import pane_reduce


def read(run):
    return pane_reduce.stage_ms(run, "plq")
