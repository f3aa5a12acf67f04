"""``ffat_pk_emit_device_ms``: device time per batch of the traced slice
under ``Key_FFAT``'s ``emit`` phase on the per-key path: the due windows'
keys and ids (``emit/range``), their ``[W, wpanes]`` gathers out of the pane
ids and the partials (``emit/gather``), their sums (``emit/reduce``); self
time by the ``XLA Ops`` line. None where the program scopes no such
phase."""

import pane_reduce


def read(run):
    return pane_reduce.stage_ms(run, "emit")
